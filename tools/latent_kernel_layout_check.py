#!/usr/bin/env python3
"""Is the latent decode kernel's output for a row a function of that row
alone? On the chip, at a cell's shapes:

    python3 tools/latent_kernel_layout_check.py [--heads 128] [--trials 12]

Each trial draws ``--rows`` rows (a context of 1 to ``places`` pages,
their pages' contents and their query) and lays them out twice: other
slots, other pages of the pool, other live neighbours with caches of other
lengths between them (so every row follows a predecessor with another
number of chunks, on another parity of the two buffers). The kernel
(``pallas_attention.latent_paged_decode_attention_live``) has to give
every row the SAME BITS in both layouts, and again when a layout is run a
second time; the XLA loop is held to the same, and the two are compared.
A race between grid steps (a copy into a buffer the arithmetic still
reads, a wait on the wrong semaphore) shows as rows that differ; the
interpret-mode tests cannot see one. One JSON line a trial, a summary
last; exit code 1 if any row differed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

W, WP, RANK, PAGE, S, PLACES, L = 576, 640, 512, 128, 128, 32, 5


def main() -> int:
    import jax
    import jax.numpy as jnp

    from llmq_tpu.ops import attention as xo
    from llmq_tpu.ops import pallas_attention as pk

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--rows", type=int, default=48)
    ap.add_argument("--trials", type=int, default=12)
    ap.add_argument("--pool-pages", type=int, default=3200)
    ap.add_argument("--places", type=int, default=PLACES)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true", help="off the chip: tiny sizes")
    args = ap.parse_args()
    H, P, places = args.heads, args.pool_pages, args.places
    scale = 192**-0.5
    lanes = (jnp.arange(WP) < W).astype(jnp.bfloat16)

    def noise(key, shape):
        return (jax.random.normal(key, shape, jnp.float32) * 0.5).astype(jnp.bfloat16)

    place = jax.jit(lambda pool, ids, pages: pool.at[:, ids].set(pages), donate_argnums=0)
    key = jax.random.key(args.seed)
    # Two pools of other pages' noise, made once: a trial writes its rows'
    # pages over some of it.
    pools = {lay: noise(jax.random.fold_in(key, i), (L, P, PAGE, WP)) * lanes
             for i, lay in enumerate("ab")}
    bad = 0
    for trial in range(args.trials):
        rng = np.random.default_rng([args.seed, trial])
        n = args.rows
        # Any length, and the edges: one token, a whole page, one past a
        # page, the check's fillers, a whole chunk, one past it, the last place.
        edges = [c for c in (1, PAGE, PAGE + 1, 161, 1024, 1025, 2049, places * PAGE) if c <= places * PAGE]
        ctxs = np.concatenate([edges, rng.integers(1, places * PAGE, n)])[:n]
        need = [-(-int(c) // PAGE) for c in ctxs]
        first = np.concatenate([[0], np.cumsum(need)])
        tkey = jax.random.fold_in(key, 1000 + trial)
        contents = noise(tkey, (L, int(first[-1]), PAGE, WP)) * lanes
        q_rows = np.asarray(noise(jax.random.fold_in(tkey, 1), (n, H, W)), np.float32)
        outs = {}
        for lay in "ab":
            r = np.random.default_rng([args.seed, trial, ord(lay)])
            q = (r.normal(size=(S, H, W)) * 0.5).astype(np.float32)
            bt = r.integers(1, P, (S, places)).astype(np.int32)  # dead places: any page
            cl = np.zeros(S, np.int32)
            free = list(r.permutation(np.arange(1, P)))
            slots = sorted(r.choice(S, n, replace=False).tolist())
            order = r.permutation(n)
            ids = np.zeros(int(first[-1]), np.int32)
            for s, i in zip(slots, order):
                q[s], cl[s] = q_rows[i], ctxs[i]
                for j in range(need[i]):
                    bt[s, j] = ids[first[i] + j] = free.pop()
            for s in range(S):  # neighbours of other lengths, and empty slots
                c = int(r.integers(1, places * PAGE))
                if s not in slots and r.random() < 0.6 and -(-c // PAGE) <= len(free):
                    cl[s] = c
                    for j in range(-(-c // PAGE)):
                        bt[s, j] = free.pop()
            pools[lay] = place(pools[lay], jnp.asarray(ids), contents)
            dev = (jnp.asarray(q, jnp.bfloat16), pools[lay], jnp.asarray(bt), jnp.asarray(cl))
            at = [slots[b] for b in np.argsort(order)]  # row i sits in slots[back[i]]
            for li in (0, L - 1):
                layer = jnp.asarray(li, jnp.int32)
                for run in (0, 1):
                    outs["kernel", lay, li, run] = np.asarray(
                        pk.latent_paged_decode_attention_live(
                            *dev, layer, scale=scale, rank=RANK, interpret=args.interpret
                        ), np.float32)[at]
                outs["xla", lay, li] = np.asarray(
                    xo.latent_paged_decode_attention(*dev, scale=scale, rank=RANK, layer=layer),
                    np.float32)[at]
        line = {"trial": trial, "rows": n, "heads": H}
        for li in (0, L - 1):
            k = outs["kernel", "a", li, 0]

            def rows_differ(x, y):
                return int((np.abs(x - y).max(axis=(1, 2)) > 0).sum())

            rerun = max(rows_differ(outs["kernel", lay, li, 0], outs["kernel", lay, li, 1]) for lay in "ab")
            moved = rows_differ(k, outs["kernel", "b", li, 0])
            line[f"layer{li}"] = {
                "kernel_rows_differ_between_layouts": moved,
                "kernel_rows_differ_between_runs": rerun,
                "xla_rows_differ_between_layouts": rows_differ(outs["xla", "a", li], outs["xla", "b", li]),
                "kernel_vs_xla_max_abs": float(np.abs(k - outs["xla", "a", li]).max()),
                "nan": bool(np.isnan(k).any()),
            }
            bad += moved + rerun + int(np.isnan(k).any())
        print(json.dumps(line), flush=True)
    print(json.dumps({"line": "summary", "trials": args.trials, "kernel_rows_that_differed": bad,
                      "device": jax.devices()[0].device_kind}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
