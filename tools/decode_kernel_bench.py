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

Usage (through the chip tool; refuses a CPU)::

    python tools/decode_kernel_bench.py [--iters 20] [--layers 36]

One JSON line a (live set, kernel) on stdout and in
``chiprun_out/decode_kernel_bench.jsonl``: ms a step, us a live page, the
largest difference from v1 over all slots and from the XLA reference
(``ops/attention.py``, float32 upcast) over the first 8 slots.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--layers", type=int, default=36)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
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
