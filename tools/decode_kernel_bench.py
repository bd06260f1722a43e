"""Time the paged decode kernels on the chip at the benchmark cells' live sets.

One decode step's attention is the kernel run once a layer over the whole
stacked pool, so each reading here is a ``lax.scan`` over the 36 layers of
qwen2.5-3b (16 query / 2 kv heads of 128, 128 slots, 128-token pages,
64 page places a slot, 1,915 pages: the pool `benchmark/` runs with) and
compares with the ledger's ``decode_attn_ms``. Three live sets:

- ``decode-long``: 128 sequences of 1,024-2,048 tokens (~1,600 live pages);
- ``chat``: 68 of the 128 slots live, log-normal contexts of median 320
  (~3.5 pages each), the others empty;
- ``full-len``: the pool filled by 29 sequences of ``max_model_len`` (8,192)
  tokens: no dead page place to save inside a live slot.

``--case latent`` times the LATENT decode attention instead, at the two
layer-pattern cells' shapes (128 slots, 128-token pages of 640 bf16 values,
rank 512): ``openpangu`` (128 heads, 5 latent layers, 3,200 pages, 32 page
places, contexts 1,664-3,584: about 2,690 live pages) and ``ling`` (32
heads, 1 latent layer, 2,305 pages, 64 places, contexts 1,024-2,048: about
1,600 live). Three readings a shape: ``xla`` (the loop of
``ops/attention.latent_paged_decode_attention``), ``latent_live`` (the
kernel) and ``copies`` (the kernel with its arithmetic taken out: what the
schedule's copies and waits cost alone). ``--groups 4,16`` times the kernel
again under other pages a chunk (one softmax update a chunk). A reading
holds the scan's own accumulation of ``[128, heads, 512]`` float32 a layer
(about 0.1 ms a layer at 128 heads), which a model step does not have.

Usage (through the chip tool; refuses a CPU)::

    python tools/decode_kernel_bench.py [--iters 20] [--layers 36]
    python tools/decode_kernel_bench.py --case latent [--groups 4,16]

One JSON line a (live set, kernel) on stdout and in
``chiprun_out/decode_kernel_bench.jsonl`` (``..._latent.jsonl``): ms a
step, us a live page, the largest difference from v1 over all slots and
from the XLA reference (``ops/attention.py``, float32 upcast) over the
first 8 slots (latent: from the XLA loop over all slots).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llmq_tpu.ops import attention as xla_ops  # noqa: E402
from llmq_tpu.ops import pallas_attention as pk  # noqa: E402

H, NKV, D, PAGE, SLOTS, PLACES, POOL_PAGES = 16, 2, 128, 128, 128, 64, 1915
SCALE = D**-0.5
WINDOW = jnp.asarray([1 << 30], jnp.int32)

KERNELS = {
    "v1": pk.paged_decode_attention_pallas,
    "live": pk.paged_decode_attention_live,
}


def live_sets(rng: np.random.Generator) -> dict:
    long = rng.integers(1024, 2049, SLOTS)
    chat = np.zeros(SLOTS, np.int64)
    on = rng.choice(SLOTS, 68, replace=False)
    chat[on] = np.clip(rng.lognormal(np.log(320), 0.8, 68), 32, 2560)
    full = np.zeros(SLOTS, np.int64)
    full[: (POOL_PAGES - 1) // PLACES] = PLACES * PAGE
    return {"decode-long": long, "chat": chat, "full-len": full}


def block_tables(rng: np.random.Generator, ctx: np.ndarray) -> np.ndarray:
    """Every live page place gets a pool page of its own, scattered."""
    need = -(-ctx // PAGE)
    assert need.sum() < POOL_PAGES, "live set does not fit the pool"
    pages = rng.permutation(np.arange(1, POOL_PAGES))
    bt, at = np.zeros((SLOTS, PLACES), np.int32), 0
    for s, n in enumerate(need):
        bt[s, :n] = pages[at : at + n]
        at += n
    return bt


def make_pool(key, layers: int):
    """Random bf16 K and V pools, filled a layer at a time (a whole
    pool drawn at once needs its float32 twice over)."""
    fill = jax.jit(
        lambda pool, l, k: pool.at[l].set(
            jax.random.normal(k, pool.shape[1:], jnp.bfloat16) * 0.5
        ),
        donate_argnums=0,
    )
    pools = []
    for side in jax.random.split(key, 2):
        pool = jnp.zeros((layers, POOL_PAGES, PAGE, NKV, D), jnp.bfloat16)
        for l, k in enumerate(jax.random.split(side, layers)):
            pool = fill(pool, l, k)
        pools.append(pool)
    return pools


def step_fn(kernel, layers: int):
    """All layers' attention of one decode step, as the model's layer
    scan runs it: the stacked pool whole, the layer a traced index."""

    @jax.jit
    def step(q, kp, vp, bt, cl):
        def layer(carry, li):
            out = kernel(q, kp, vp, bt, cl, WINDOW, li, scale=SCALE)
            return carry + out.astype(jnp.float32), None

        total, _ = jax.lax.scan(
            layer, jnp.zeros(q.shape, jnp.float32), jnp.arange(layers)
        )
        return total

    return step


LATENT_W, LATENT_WP, LATENT_RANK = 576, 640, 512
LATENT_SCALE = 192**-0.5
#: shape -> (heads, latent layers, pool pages, page places, contexts)
LATENT_SHAPES = {
    "openpangu": (128, 5, 3200, 32, (1664, 3584)),
    "ling": (32, 1, 2305, 64, (1024, 2048)),
}


def _copies_only(q, lat_ref, kpos, ctx, m_ref, l_ref, acc_ref, **_):
    """In the place of the kernel's arithmetic: one tile of the chunk
    read, so the waits keep something to wait for."""
    acc_ref[:16, :128] = acc_ref[:16, :128] + lat_ref[:16, :128].astype(jnp.float32)
    l_ref[...] = jnp.ones_like(l_ref)


def latent_case(args, dev) -> int:
    """``--case latent``: see the module's docstring."""
    rng = np.random.default_rng(args.seed)
    kernel_fold, kernel_schedule = pk._latent_fold, pk._latent_decode_schedule
    variants = [("xla", None, None), ("latent_live", None, None), ("copies", _copies_only, None)]
    for G in (int(n) for n in filter(None, args.groups.split(","))):
        variants.append((f"latent_live_{G}", None, G))
        variants.append((f"copies_{G}", _copies_only, G))
    out_path = Path("chiprun_out/decode_kernel_bench_latent.jsonl")
    out_path.parent.mkdir(exist_ok=True)
    lines = []
    for shape, (heads, layers, pool_pages, places, (lo, hi)) in LATENT_SHAPES.items():
        ctx = rng.integers(lo, hi + 1, SLOTS)
        need = -(-ctx // PAGE)
        assert need.sum() < pool_pages, "live set does not fit the pool"
        order = rng.permutation(np.arange(1, pool_pages))
        # dead places hold page ids of other rows: nothing may read them
        bt = rng.integers(1, pool_pages, (SLOTS, places)).astype(np.int32)
        at = 0
        for s, n in enumerate(need):
            bt[s, :n] = order[at : at + n]
            at += n
        bt, cl = jnp.asarray(bt), jnp.asarray(ctx, jnp.int32)
        pool = (
            jax.random.normal(
                jax.random.key(args.seed), (layers, pool_pages, PAGE, LATENT_WP),
                jnp.bfloat16,
            ) * 0.5
        ).at[..., LATENT_W:].set(0)
        q = (
            jax.random.normal(jax.random.key(1), (SLOTS, heads, LATENT_W)) * 0.5
        ).astype(jnp.bfloat16)
        base = None
        for name, fold, group in variants:
            pk._latent_fold = fold or kernel_fold
            pk._latent_decode_schedule = (
                (lambda *_, G=group: G) if group else kernel_schedule
            )
            jax.clear_caches()
            if name == "xla":
                def attend(q, pool, bt, cl, li):
                    return xla_ops.latent_paged_decode_attention(
                        q, pool, bt, cl, scale=LATENT_SCALE, rank=LATENT_RANK, layer=li
                    )
            else:
                def attend(q, pool, bt, cl, li):
                    return pk.latent_paged_decode_attention_live(
                        q, pool, bt, cl, li, scale=LATENT_SCALE, rank=LATENT_RANK
                    )

            @jax.jit
            def step(q, pool, bt, cl):
                def layer(carry, li):
                    return carry + attend(q, pool, bt, cl, li).astype(jnp.float32), None

                total, _ = jax.lax.scan(
                    layer, jnp.zeros((SLOTS, heads, LATENT_RANK), jnp.float32),
                    jnp.arange(layers),
                )
                return total

            try:
                one = np.asarray(step(q, pool, bt, cl), np.float32)
            except Exception as exc:  # noqa: BLE001 — a schedule the compiler refuses
                print(json.dumps({"shape": shape, "kernel": name, "error": str(exc)[:300]}), flush=True)
                continue
            base = one if base is None else base
            for _ in range(3):
                step(q, pool, bt, cl).block_until_ready()
            # Steps back to back, one wait at the end: a step of one layer
            # is 0.5 ms, and a wait a step costs 0.6 ms on this machine.
            t0 = time.perf_counter()
            outs = [step(q, pool, bt, cl) for _ in range(args.iters)]
            outs[-1].block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / args.iters
            lines.append(
                {
                    "shape": shape, "kernel": name, "heads": heads,
                    "ms_per_step": round(ms, 3),
                    "us_per_live_page": round(1e3 * ms / (layers * int(need.sum())), 4),
                    "live_pages": int(need.sum()), "layers": layers,
                    "pages_a_chunk": pk._latent_decode_schedule(
                        PAGE * LATENT_WP * 2, heads, PAGE
                    ),
                    "max_abs_vs_xla": (
                        None if name.startswith("copies")
                        else float(np.abs(one - base).max())
                    ),
                    "device": dev.device_kind,
                }
            )
            print(json.dumps(lines[-1]), flush=True)
    pk._latent_fold, pk._latent_decode_schedule = kernel_fold, kernel_schedule
    out_path.write_text("".join(json.dumps(l) + "\n" for l in lines))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--case", choices=("kv", "latent"), default="kv")
    ap.add_argument("--groups", default="", help="latent: pages a chunk, G[,G...]")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    if args.case == "latent":
        return latent_case(args, dev)
    rng = np.random.default_rng(args.seed)
    kp, vp = make_pool(jax.random.key(args.seed), args.layers)
    q = (jax.random.normal(jax.random.key(1), (SLOTS, H, D)) * 0.5).astype(
        jnp.bfloat16
    )
    out_path = Path("chiprun_out/decode_kernel_bench.jsonl")
    out_path.parent.mkdir(exist_ok=True)
    lines = []
    for set_name, ctx in live_sets(rng).items():
        bt = jnp.asarray(block_tables(rng, ctx))
        cl = jnp.asarray(ctx, jnp.int32)
        live_pages = int((-(-ctx // PAGE)).sum())
        li = jnp.asarray(args.layers // 2, jnp.int32)
        ref = xla_ops.paged_decode_attention(
            q[:8].astype(jnp.float32), kp[li].astype(jnp.float32),
            vp[li].astype(jnp.float32), bt[:8], cl[:8], scale=SCALE,
        )
        base = None
        for name, kernel in KERNELS.items():
            one = np.asarray(
                kernel(q, kp, vp, bt, cl, WINDOW, li, scale=SCALE),
                np.float32,
            )
            base = one if base is None else base
            step = step_fn(kernel, args.layers)
            for _ in range(3):
                step(q, kp, vp, bt, cl).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(args.iters):
                step(q, kp, vp, bt, cl).block_until_ready()
            ms = (time.perf_counter() - t0) * 1e3 / args.iters
            lines.append(
                {
                    "set": set_name, "kernel": name,
                    "ms_per_step": round(ms, 3),
                    "us_per_live_page": round(
                        1e3 * ms / (args.layers * live_pages), 4
                    ),
                    "live_pages": live_pages,
                    "live_seqs": int((ctx > 0).sum()),
                    "layers": args.layers,
                    "max_abs_vs_v1": float(np.abs(one - base).max()),
                    "max_abs_vs_xla_f32": float(
                        np.abs((one[:8] - np.asarray(ref))[ctx[:8] > 0]).max()
                    ),
                    "device": dev.device_kind,
                }
            )
            print(json.dumps(lines[-1]), flush=True)
    out_path.write_text("".join(json.dumps(l) + "\n" for l in lines))
    return 0


if __name__ == "__main__":
    sys.exit(main())
