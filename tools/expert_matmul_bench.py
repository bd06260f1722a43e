"""Time the routed experts' product of one layer alone, on the chip, in
every form ``models/hybrid.py`` has, at the three expert cells' shapes.

A shape is a configuration's held experts at its published widths:

- ``ling`` (``ling-3.0-flash-ep4``): 128 of 512 experts held, 8 a token,
  2,560 x 768;
- ``openpangu`` (``openpangu-ultra-moe-718b-ep16``): 16 of 256 held, 8 a
  token, 7,680 x 2,048;
- ``lfm2`` (``lfm2-24b-a2b-pp5``): all 64 held, 4 a token, 2,048 x 1,536.

For each ``--rows`` (tokens; default 256,512,1024,4096) the tokens' picks
are drawn evenly over all the experts (seeded weights route more
unevenly: PERF.md section 4), and one layer's product, from the sorted
assignments to the ``[rows, hidden]`` float32 sum, is timed as:

- ``dense``: ``_experts_dense`` on the layer's own matrices (what a
  decode step takes, up to ``DENSE_EXPERT_ROWS`` rows);
- ``ragged``: ``_experts_grouped`` with ``lax.ragged_dot`` on the layer's
  own matrices, handed in as buffers of their own (no copy in front);
- ``ragged_scan``: the same under a ``lax.scan`` over the stack's layers,
  the matrices the scan's slices: what a prefill ran until PR 51, the
  three copies a layer included;
- ``stacked``: ``_experts_grouped`` with the kernel
  (``ops/pallas_grouped_matmul.grouped_matmul_stacked``) on the whole
  stack under the same scan; ``--tile-rows 128,256,512`` times it again
  with other tiles of rows than ``pallas_grouped_matmul.tiles`` gives.

ms a layer each (a scan's time over its layers), and the largest
difference of the sum from ``ragged``'s over the spread of ``ragged``'s.
``--interpret`` with ``--shapes tiny`` runs a small shape on a CPU, for the
tests, and gives no time.

Usage (through the chip tool; refuses a CPU)::

    python tools/expert_matmul_bench.py [--shapes ling,lfm2] [--rows 512,4096]

One JSON line a (shape, rows, form) on stdout and in
``chiprun_out/expert_matmul_bench.jsonl``.
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

from llmq_tpu.models import hybrid  # noqa: E402
from llmq_tpu.ops import dispatch  # noqa: E402
from llmq_tpu.ops import pallas_grouped_matmul as pgm  # noqa: E402

#: name: (experts in all, held here, picks a token, hidden, expert width)
SHAPES = {
    "ling": (512, 128, 8, 2560, 768),
    "openpangu": (256, 16, 8, 7680, 2048),
    "lfm2": (64, 64, 4, 2048, 1536),
    "tiny": (8, 4, 2, 128, 256),
}
LAYERS = 2


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="ling,openpangu,lfm2")
    ap.add_argument("--rows", default="256,512,1024,4096")
    ap.add_argument("--tile-rows", default="", help="stacked again with these tiles")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true", help="on a CPU, interpreted")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        print(f"no TPU here ({dev.platform}): a time from it would mean nothing", file=sys.stderr)
        return 1
    if args.interpret:
        dispatch._interpret = lambda: True
    lines = []
    for shape in args.shapes.split(","):
        E, held, k, H, I = SHAPES[shape]
        keys = jax.random.split(jax.random.key(args.seed), 4)
        stack = {
            name: jax.random.normal(key, (LAYERS, held) + dims, jnp.bfloat16) * dims[0] ** -0.5
            for name, key, dims in zip(hybrid.EXPERT_LEAVES, keys, ((H, I), (H, I), (I, H)))
        }
        last = {name: w[LAYERS - 1] for name, w in stack.items()}
        for rows in map(int, args.rows.split(",")):
            rng = np.random.default_rng(args.seed + rows)
            x = jnp.asarray(rng.normal(size=(rows, H)), jnp.bfloat16)
            picks = np.stack([rng.choice(E, size=k, replace=False) for _ in range(rows)])
            local = jnp.asarray(picks, jnp.int32)  # the held experts are 0 .. held - 1
            here = local < held
            top_w = jnp.asarray(rng.uniform(0.5, 1.5, size=(rows, k)), jnp.float32)

            def scanned(body, layers):
                """The last of ``LAYERS`` layers' products, each from ``x``."""
                def step(x, stack):
                    def layer(_, xs):
                        return None, body(x, xs, stack)

                    return jax.lax.scan(layer, None, layers(stack))[1][-1]

                return jax.jit(step), LAYERS

            def grouped(x, lp, whole=None):
                return hybrid._experts_grouped(x, lp, local, here, top_w, held, whole)[0]

            forms = {
                "dense": (jax.jit(lambda x, lp: hybrid._experts_dense(x, lp, local, here, top_w, held)[0]), 1),
                "ragged": (jax.jit(grouped), 1),
                "ragged_scan": scanned(lambda x, lp, stack: grouped(x, lp), lambda stack: stack),
                "stacked": scanned(
                    lambda x, li, stack: grouped(x, None, (stack, li)),
                    lambda stack: jnp.arange(LAYERS, dtype=jnp.int32),
                ),
            }
            base = None
            tiles = [0] + [int(t) for t in args.tile_rows.split(",") if t]
            for name, tile in [(n, 0) for n in forms] + [("stacked", t) for t in tiles[1:]]:
                fn, layers = forms[name]
                arg = last if layers == 1 else stack
                kernel_tiles = pgm.tiles
                if tile:
                    pgm.tiles = lambda k_, n_, t=tile: (t, kernel_tiles(k_, n_)[1])
                    jax.clear_caches()
                try:
                    out = np.asarray(fn(x, arg), np.float32)
                    ms = None
                    if not args.interpret:
                        for _ in range(2):
                            fn(x, arg).block_until_ready()
                        t0 = time.perf_counter()
                        outs = [fn(x, arg) for _ in range(args.iters)]
                        outs[-1].block_until_ready()
                        ms = round((time.perf_counter() - t0) * 1e3 / args.iters / layers, 4)
                except Exception as exc:  # noqa: BLE001 — a form the compiler refuses
                    lines.append({"shape": shape, "rows": rows, "form": name, "error": str(exc)[:300]})
                    print(json.dumps(lines[-1]), flush=True)
                    continue
                finally:
                    pgm.tiles = kernel_tiles
                if name == "ragged":
                    base = out
                lines.append({
                    "shape": shape, "rows": rows, "form": name, "ms_a_layer": ms,
                    "tile_rows": (tile or pgm.tiles(H, I)[0]) if name == "stacked" else None,
                    "assignments_here": int(here.sum()),
                    "experts_hit": int(np.unique(picks[picks < held]).size),
                    "max_diff_over_spread": None if base is None else round(
                        float(np.abs(out - base).max() / base.std()), 5
                    ),
                    "device": dev.device_kind,
                })
                print(json.dumps(lines[-1]), flush=True)
    out_path = Path("chiprun_out/expert_matmul_bench.jsonl")
    out_path.parent.mkdir(exist_ok=True)
    out_path.write_text("".join(json.dumps(l) + "\n" for l in lines))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
