#!/usr/bin/env python3
"""Time the prefill attention of expanded latent attention on the chip, the
XLA form against the flash kernel, and say where the kernel pays.

``ops/dispatch.mla_prefill_plan`` engages the kernel from a size of the
attention, ``num_heads x T`` of the padded bucket
(``dispatch.MLA_FLASH_HEAD_TOKENS``). This tool is where that number comes
from: the size from which the kernel saves about a twentieth or more of
the shape's WHOLE prefill program in every preset that has the layer
(``--programs``; the table is in PERF.md section 6, PR 57).

    python tools/mla_prefill_bench.py                      # the attention alone
    python tools/mla_prefill_bench.py --blocks 256x256,512x512,1024x512
    python tools/mla_prefill_bench.py --programs \
        ling-3.0-flash-ep4:2048,4096 openpangu-ultra-moe-718b-ep16:512,1024,2048

Readings, one JSON line each on stdout and in
``chiprun_out/mla_prefill_bench.jsonl``:

- ``attention``: for each ``--heads`` (32: ling, 128: openpangu) and each
  ``--tokens`` (256 ... 8,192), one row of that many positions, heads of
  128 + 64 and 128, bf16: ms of ``xla`` (the slices, the concatenations
  and ``ops/attention.blocked_prefill_attention``, as ``_mla_expanded``
  has them) and of ``flash`` (``pallas_attention.mla_flash_prefill_attention``
  with the queries' transposes) at each of ``--blocks``, over a full prompt and over
  one of three quarters of the bucket, and the largest difference of the
  two forms over the prompt's own rows.
- ``program``: for each ``preset:T,T`` of ``--programs``, the whole 1 x T
  ``jit(model.prefill)`` of the preset on seeded weights under each plan
  (the threshold set so that the plan is ``xla``, then ``flash``): ms of
  both, the share of the program the kernel saves, the seconds each took
  to trace and lower (``*_trace_s``: where Pallas lowers the kernel to
  Mosaic, in Python, whatever the persistent compile cache holds: what a
  kernel instance costs every run's set-up) and to compile
  (``*_compile_s``: what a cache hit saves), and the plan the model named
  each time (``xla`` twice on a CPU or at a tiny preset's head sizes).

Refuses a CPU, but for ``--interpret`` (tiny shapes, for the tests: the
differences and the plans, no time).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from llmq_tpu.ops import dispatch  # noqa: E402
from llmq_tpu.ops import pallas_attention as pk  # noqa: E402

D_C, D_R, D_V = 128, 64, 128
SCALE = (D_C + D_R) ** -0.5


def timed(fn, *args, iters: int):
    """ms a call, after one call that compiles; none off the chip."""
    jax.block_until_ready(fn(*args))
    if jax.devices()[0].platform != "tpu":
        return None
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def attention_lines(heads, tokens, blocks, iters, interpret):
    for n in heads:
        for T in tokens:
            keys = jax.random.split(jax.random.key(n * 100_003 + T), 4)
            shapes = ((1, T, n, D_C), (1, T, n, D_R), (1, T, n, D_C + D_V), (1, T, D_R))
            rows = [jax.random.normal(k, s, jnp.bfloat16) for k, s in zip(keys, shapes)]
            xla = jax.jit(partial(dispatch.mla_prefill_attention, scale=SCALE, plan="xla"))
            for length in (T, max(1, 3 * T // 4)):
                lengths = jnp.asarray([length], jnp.int32)
                line = {
                    "line": "attention", "heads": n, "tokens": T, "length": length,
                    "head_tokens": n * T,
                    "xla_ms": timed(partial(xla, lengths=lengths), *rows, iters=iters),
                }
                want = xla(*rows, lengths=lengths)[0, :length].astype(jnp.float32)
                for bq, bk in blocks:
                    flash = jax.jit(partial(
                        pk.mla_flash_prefill_attention, scale=SCALE,
                        block_q=bq, block_kv=bk, interpret=interpret,
                    ))
                    line[f"flash_{bq}x{bk}_ms"] = timed(flash, *rows, lengths, iters=iters)
                    got = flash(*rows, lengths)[0, :length].astype(jnp.float32)
                    line[f"flash_{bq}x{bk}_max_diff"] = float(jnp.abs(got - want).max())
                yield line


def program_lines(specs, iters, dtype=jnp.bfloat16):
    from llmq_tpu.models.presets import get_preset
    from llmq_tpu.models.transformer import build_model, init_params, make_kv_pages

    threshold = dispatch.MLA_FLASH_HEAD_TOKENS
    for spec in specs:
        preset, _, buckets = spec.partition(":")
        cfg = get_preset(preset)
        model = build_model(cfg, attn_backend="auto")
        params = jax.jit(partial(init_params, cfg, dtype=dtype))(jax.random.key(0))
        for T in (int(t) for t in buckets.split(",")):
            places = -(-T // 128)
            kp, vp = make_kv_pages(cfg, places + 1, 128, dtype, state_rows=2)
            args = (
                params, jnp.ones((1, T), jnp.int32), jnp.asarray([T], jnp.int32), kp, vp,
                jnp.arange(1, places + 1, dtype=jnp.int32)[None], jnp.asarray([1], jnp.int32),
            )
            line = {"line": "program", "preset": preset, "tokens": T,
                    "head_tokens": cfg.num_heads * T}
            try:
                for plan, at in (("xla", 1 << 62), ("flash", 0)):
                    dispatch.MLA_FLASH_HEAD_TOKENS = at
                    line[f"{plan}_plan"] = model.mla_prefill_plan(T, dtype)
                    start = time.perf_counter()
                    lowered = jax.jit(model.prefill).lower(*args)
                    line[f"{plan}_trace_s"] = time.perf_counter() - start
                    start = time.perf_counter()
                    step = lowered.compile()
                    line[f"{plan}_compile_s"] = time.perf_counter() - start
                    line[f"{plan}_ms"] = timed(step, *args, iters=iters)
            finally:
                dispatch.MLA_FLASH_HEAD_TOKENS = threshold
            if line["xla_ms"] is not None:
                line["saved_pct"] = 100 * (1 - line["flash_ms"] / line["xla_ms"])
            yield line
        del params, args, kp, vp


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--heads", default="32,128")
    ap.add_argument("--tokens", default="256,512,1024,2048,4096,8192")
    ap.add_argument("--blocks", default="512x512", help="query x key blocks of the kernel, e.g. 256x256,512x512")
    ap.add_argument("--programs", nargs="*", default=[], help="preset:T,T whole prefill programs under both plans")
    ap.add_argument("--no-attention", action="store_true")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--interpret", action="store_true", help="the kernel interpreted on a CPU (tiny shapes)")
    ap.add_argument("--out", default="chiprun_out/mla_prefill_bench.jsonl")
    args = ap.parse_args()
    if not args.interpret and jax.devices()[0].platform != "tpu":
        print("mla_prefill_bench: no TPU here; a time comes only from the chip", file=sys.stderr)
        return 2
    ints = lambda text: [int(x) for x in text.split(",")]
    blocks = [tuple(int(x) for x in b.split("x")) for b in args.blocks.split(",")]
    lines = [] if args.no_attention else attention_lines(
        ints(args.heads), ints(args.tokens), blocks, args.iters, args.interpret
    )
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    device = jax.devices()[0]
    with out.open("a") as f:
        for source in (lines, program_lines(args.programs, args.iters)):
            for line in source:
                line["device"] = f"{device.platform}:{device.device_kind}"
                text = json.dumps(line)
                print(text, flush=True)
                f.write(text + "\n")
                f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
