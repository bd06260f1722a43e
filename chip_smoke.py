#!/usr/bin/env python
"""The quickest proof that llmq-tpu still starts, compiles and answers on a TPU.

    python chip_smoke.py             # one chip: kernels, then qwen2.5-3b bf16
                                     # served through worker, broker, gateway
    python chip_smoke.py --chips 4   # four chips only: tp=1 against tp=4 at
                                     # llama3.1-8b widths (kernels under
                                     # shard_map), then qwen2.5-7b served at
                                     # tp=4 by the worker

One process owns the chip from the first ``jax.devices()`` to the end; the
script starts no child. Each phase prints one JSON line as it ends. The
first phase that fails prints what failed and the script exits non-zero
at once. The last line of standard output is the result the chip tool
reads:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

With no TPU the *device* phase fails: there is no switch to the CPU.
``--rehearse-cpu`` (which insists on ``JAX_PLATFORMS=cpu``) walks the same
phases at ``preset://tiny`` with interpreted kernels, to find wrong paths
and arguments before a chip call; its last line says ``"platform": "cpu"``
and is proof of nothing else. Rehearse ``--chips 4`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

Weights and inputs are random, made from ``--seed``; nothing is read from
the network, ``$HOME`` or a checkpoint. Logs go to standard error.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import http.client
import importlib.metadata
import io
import json
import os
import sys
import time
import traceback
from functools import partial

QUEUE = "chip-smoke"
MAX_TOKENS = 48
#: Prompt lengths in byte-tokens, so that an unaligned prefill bucket (320)
#: and aligned ones (512 and 1024 are multiples of the 128-token page)
#: both compile. The two 300s and the two 500s are identical prompts.
BATCH_PROMPTS = (300, 300, 300, 500, 500, 1000, 1000, 1000)
HTTP_PROMPTS = (300, 500)  # each asked blocking, then again with SSE
#: Kernel outputs against the ops/attention.py / jnp reference: absolute
#: for attention (outputs are averages of |v| ~ 0.3 values, one bf16 ulp
#: there is 2e-3), relative to the largest reference value for matmuls.
ATTN_TOL = 2e-2
MATMUL_TOL = 2e-2
#: tp=1 against tp=4, first-token logits: the same bf16 weights, but the
#: tp=4 row-parallel matmuls sum four partial products in another order.
#: Relative to the largest logit magnitude.
TP_LOGIT_TOL = 5e-2


def emit(line: dict) -> None:
    print(json.dumps(line), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def run_phase(name: str, fn, *args) -> dict:
    """Run one phase and print its line. A failure is reported and ends
    the script non-zero — it is never turned into a note."""
    t0 = time.monotonic()
    try:
        facts = fn(*args)
    except Exception as exc:  # noqa: BLE001 — report, then exit non-zero
        traceback.print_exc()
        emit(
            {
                "phase": name,
                "ok": False,
                "seconds": round(time.monotonic() - t0, 1),
                "error": f"{type(exc).__name__}: {exc}"[:4000],
            }
        )
        sys.exit(1)
    emit(
        {"phase": name, "ok": True,
         "seconds": round(time.monotonic() - t0, 1), **facts}
    )
    return facts


@dataclasses.dataclass(frozen=True)
class Mode:
    chips: int
    rehearse_cpu: bool
    seed: int

    @property
    def platform(self) -> str:
        return "cpu" if self.rehearse_cpu else "tpu"

    @property
    def preset(self) -> str:
        """The model the worker serves."""
        if self.rehearse_cpu:
            return "tiny"
        return "qwen2.5-7b" if self.chips == 4 else "qwen2.5-3b"

    @property
    def compare_preset(self) -> str:
        """tp=1 against tp=4: a model whose shards keep two kv heads, so
        the Pallas kernels run under shard_map (qwen2.5-7b's single head a
        shard takes the XLA path — ops/dispatch._tp_heads_ok)."""
        return "tiny" if self.rehearse_cpu else "llama3.1-8b"


# --- phase: device ---------------------------------------------------------


def phase_device(mode: Mode) -> dict:
    import jax
    import jaxlib

    from llmq_tpu.utils.platform import enable_compile_cache

    if mode.rehearse_cpu:
        check(
            os.environ.get("JAX_PLATFORMS") == "cpu",
            "--rehearse-cpu runs on the CPU on purpose: set JAX_PLATFORMS=cpu",
        )
    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(
        dev.platform == mode.platform,
        f"JAX came up on {dev.platform!r} ({dev.device_kind}), this run needs "
        f"{mode.platform!r}: no TPU was found, or another process holds it",
    )
    check(
        len(devices) == mode.chips,
        f"{len(devices)} device(s) visible, this run needs {mode.chips} "
        "(--chips 4 is for a four-chip host)",
    )
    mem = dev.memory_stats() or {}
    if not mode.rehearse_cpu:
        check(bool(mem.get("bytes_limit")), f"{dev} reports no bytes_limit")
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    return {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "bytes_limit": mem.get("bytes_limit"),
        "compile_cache_dir": cache_dir,
    }


# --- phase: kernels --------------------------------------------------------


def phase_kernels(mode: Mode) -> dict:
    """Each kernel of the default path, compiled (on the chip: a Mosaic
    custom call in the executable, never the interpreter) at the widths of
    the preset, on device arrays made from the seed, against the plain
    reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llmq_tpu.models import quant as qm
    from llmq_tpu.models.presets import get_preset
    from llmq_tpu.ops import attention as ref
    from llmq_tpu.ops import dispatch
    from llmq_tpu.ops import pallas_attention as pk
    from llmq_tpu.ops import pallas_matmul as pm

    cfg = get_preset(mode.preset)
    H, NKV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    hidden, inter = cfg.hidden_size, cfg.intermediate_size
    # Sizes: the widths are the preset's; lengths and counts are the
    # worker's defaults (64 slots, 128-token pages) — or small on a CPU,
    # where the interpreter is slow and proves nothing about the chip.
    if mode.rehearse_cpu:
        PAGE, S, T, C, L = 16, 4, 64, 32, 2
    else:
        PAGE, S, T, C, L = 128, 64, 1024, 512, 2
    interpret = mode.rehearse_cpu
    scale = D**-0.5
    keys = iter(jax.random.split(jax.random.key(mode.seed), 32))
    window = jnp.asarray([1 << 30], jnp.int32)
    layer = jnp.asarray([L - 1], jnp.int32)
    li = jnp.asarray(L - 1, jnp.int32)

    def rnd(shape, dtype=jnp.bfloat16, std=0.3):
        return (jax.random.normal(next(keys), shape, jnp.float32) * std).astype(
            dtype
        )

    def run_compiled(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        if not interpret:
            check(
                "tpu_custom_call" in compiled.as_text(),
                "no Mosaic kernel in the compiled program: it was interpreted "
                "or replaced",
            )
        return compiled(*args)

    def max_err(got, want, valid=None):
        diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
        if valid is not None:
            diff = diff[np.asarray(valid)]
        check(bool(np.isfinite(diff).all()), "non-finite kernel output")
        return float(diff.max())

    out = {}

    # Flash prefill (bucketed whole-prompt prefill).
    B = 4
    q, k, v = rnd((B, T, H, D)), rnd((B, T, NKV, D)), rnd((B, T, NKV, D))
    lengths = jnp.asarray([T, T - 24, T // 3, 1], jnp.int32)
    got = run_compiled(
        partial(pk.flash_prefill_attention_pallas, scale=scale, interpret=interpret),
        q, k, v, lengths, window,
    )
    want = ref.full_prefill_attention(q, k, v, scale=scale, lengths=lengths)
    valid = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    out["flash_prefill"] = max_err(got, want, valid)

    # Paged (chunked) prefill against a layer-stacked pool.
    pages_per_row = -(-(T + C) // PAGE)
    P = 1 + B * pages_per_row
    kp, vp = rnd((L, P, PAGE, NKV, D)), rnd((L, P, PAGE, NKV, D))
    bt = jnp.arange(1, P, dtype=jnp.int32).reshape(B, pages_per_row)
    start = jnp.asarray([0, PAGE, 2 * PAGE + 5, 37], jnp.int32)
    n_valid = jnp.asarray([C, C, C // 2 + 3, 1], jnp.int32)
    qc = rnd((B, C, H, D))
    got = run_compiled(
        partial(pk.paged_prefill_attention_pallas, scale=scale, interpret=interpret),
        qc, kp, vp, bt, start, n_valid, window, layer,
    )
    idx = jnp.arange(C)[None, :]
    q_pos = jnp.where(idx < n_valid[:, None], start[:, None] + idx, -1)
    want = ref.paged_prefill_attention(
        qc, kp, vp, bt, q_pos, scale=scale, layer=li
    )
    out["paged_prefill"] = max_err(got, want, np.asarray(q_pos) >= 0)

    # The decode schedule the worker will run on a pool of this shape,
    # through the engine's dispatch.
    pages_per_seq = 8192 // PAGE + 1  # the worker's default max_model_len
    live = 8  # pages a slot may touch here: contexts up to 8 pages
    P = 1 + S * live
    kp, vp = rnd((L, P, PAGE, NKV, D)), rnd((L, P, PAGE, NKV, D))
    kernel = dispatch.decode_kernel_plan(H, NKV, kp.dtype, backend="pallas")
    bt = np.zeros((S, pages_per_seq), np.int32)
    bt[:, :live] = np.arange(1, P).reshape(S, live)
    ctx = jax.random.randint(next(keys), (S,), 1, live * PAGE, jnp.int32)
    ctx = ctx.at[0].set(1).at[1].set(live * PAGE)
    qd = rnd((S, H, D))
    got = run_compiled(
        lambda *a: dispatch.decode_attention(
            *a, scale=scale, backend="pallas", layer=li
        ),
        qd, kp, vp, jnp.asarray(bt), ctx,
    )
    want = ref.paged_decode_attention(
        qd, kp, vp, jnp.asarray(bt), ctx, scale=scale, layer=li
    )
    out[f"decode_{kernel}"] = max_err(got, want)
    for name, err in out.items():
        check(err <= ATTN_TOL, f"{name}: |kernel - reference| {err} > {ATTN_TOL}")

    # Weight-only matmuls at the MLP shapes: int8 (LLMQ_INT8_MATMUL=pallas)
    # and the int4 group kernel, whose scale tile the TPU never accepted
    # before PR 24 (up: K=hidden; down: K=intermediate).
    x_up, x_down = rnd((S, hidden), std=1.0), rnd((S, inter), std=1.0)
    w8 = qm.quantize_array(rnd((hidden, inter), jnp.float32, 0.05), axis=-2,
                           scale_dtype=jnp.bfloat16)
    got = run_compiled(
        partial(pm.int8_matmul_pallas, interpret=interpret),
        x_up, w8["q"], w8["scale"],
    )
    want = jnp.matmul(
        x_up.astype(jnp.float32), w8["q"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    ) * w8["scale"].astype(jnp.float32)
    rel = {"int8_matmul": max_err(got, want) / float(jnp.abs(want).max())}
    for name, x, shape in (
        ("int4_matmul_up", x_up, (hidden, inter)),
        ("int4_matmul_down", x_down, (inter, hidden)),
    ):
        w4 = qm.quantize_array_int4(rnd(shape, jnp.float32, 0.05),
                                    scale_dtype=jnp.bfloat16)
        got = run_compiled(
            partial(pm.int4_matmul_pallas, interpret=interpret),
            x, w4["q"], w4["scale"], w4["zero"],
        )
        want = jnp.matmul(
            x.astype(jnp.float32),
            qm.dequantize_int4_parts(
                w4["q"], w4["scale"], w4["zero"], jnp.float32
            ),
            precision=jax.lax.Precision.HIGHEST,
        )
        rel[name] = max_err(got, want) / float(jnp.abs(want).max())
    for name, err in rel.items():
        check(
            err <= MATMUL_TOL,
            f"{name}: |kernel - reference| / max|reference| {err} > {MATMUL_TOL}",
        )
    return {
        "widths": f"{mode.preset}: {H}/{NKV} heads, d={D}, hidden={hidden}, "
        f"mlp={inter}",
        "compiled": not interpret,
        "max_abs_err": {k: round(v, 5) for k, v in out.items()},
        "attn_tolerance": ATTN_TOL,
        "max_rel_err": {k: round(v, 5) for k, v in rel.items()},
        "matmul_tolerance": MATMUL_TOL,
    }


# --- phase: serve ----------------------------------------------------------


def _bytes_total_and_on(device, arrays) -> tuple:
    """(bytes of ``arrays`` in all, bytes of them held by ``device``)."""
    return (
        sum(x.nbytes for x in arrays),
        sum(
            shard.data.nbytes
            for x in arrays
            for shard in x.addressable_shards
            if shard.device == device
        ),
    )


def _request(n: int) -> dict:
    """A greedy request with a deterministic n-byte prompt."""
    return {"prompt": _prompt(n), "temperature": 0.0,
            "max_tokens": MAX_TOKENS, "ignore_eos": True}


def _prompt(n: int) -> str:
    """A deterministic n-byte prompt (one byte = one token; no braces,
    which the job template would read as fields)."""
    words = "the quick brown fox jumps over the lazy dog while llmq serves ".split()
    text, i = "", 0
    while len(text) < n:
        text += words[(i * 7 + n) % len(words)] + " "
        i += 1
    return text[:n]


def _post(port: int, body: dict) -> tuple:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    conn.request(
        "POST", "/v1/completions", json.dumps(body),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _sse(data: bytes) -> tuple:
    """(text, finish_reason) of a streamed completion."""
    text, finish = "", None
    for event in data.split(b"\n\n"):
        if not event.startswith(b"data: ") or event == b"data: [DONE]":
            continue
        choice = json.loads(event[6:])["choices"][0]
        text += choice.get("text", "")
        finish = choice.get("finish_reason") or finish
    return text, finish


async def _serve(mode: Mode, meter, n_batch: int, n_http_prompts: int) -> dict:
    import jax

    from llmq_tpu.broker.manager import FAILED_SUFFIX, QUARANTINE_SUFFIX, BrokerManager
    from llmq_tpu.cli.receive import ResultReceiver
    from llmq_tpu.cli.submit import JobSubmitter
    from llmq_tpu.cli.worker import build_tpu_worker
    from llmq_tpu.core.config import get_config
    from llmq_tpu.gateway import ServingGateway
    from llmq_tpu.utils.logging import setup_logging

    # The broker of a single-process deployment; every component below
    # finds it through LLMQ_BROKER_URL, as on a real host.
    os.environ["LLMQ_BROKER_URL"] = f"memory://chip-smoke-{mode.seed}"
    # Results carry their token ids (and a digest the receiver checks):
    # the byte tokenizer turns most ids of a 152k vocabulary into no text
    # at all, so "the same answer" is compared on ids.
    os.environ["LLMQ_RESULT_DIGEST"] = "1"
    t_start = time.monotonic()
    compile_before = meter.snapshot()
    worker = build_tpu_worker(f"preset://{mode.preset}", QUEUE)
    setup_logging(structured=False)  # worker logs to stderr: stdout is results
    wtask = asyncio.ensure_future(worker.run())
    gateway = None
    try:
        while not worker.running:
            if wtask.done():
                wtask.result()  # the worker's own error, if it raised
                raise RuntimeError("the worker stopped before it was ready")
            await asyncio.sleep(0.2)
        ready_s = time.monotonic() - t_start
        compile_ready = meter.snapshot()

        # Batch path: the submit and receive commands' own classes.
        async def submit_and_receive(rows: list) -> dict:
            stdin, sys.stdin = sys.stdin, io.StringIO(
                "".join(json.dumps(r) + "\n" for r in rows)
            )
            try:
                submitted = await JobSubmitter(QUEUE, "-").run()
            finally:
                sys.stdin = stdin
            check(submitted == len(rows), f"submitted {submitted}/{len(rows)}")
            captured = io.StringIO()
            with contextlib.redirect_stdout(captured):
                received = await ResultReceiver(
                    QUEUE, timeout=900.0, limit=len(rows)
                ).run()
            got = {
                r["id"]: r
                for r in map(json.loads, captured.getvalue().splitlines())
            }
            check(
                received == len(rows) and set(got) == {r["id"] for r in rows},
                f"received {received} result(s) {sorted(got)} for "
                f"{len(rows)} jobs",
            )
            for rid, res in got.items():
                check(
                    res.get("usage", {}).get("completion_tokens") == MAX_TOKENS
                    and res.get("finish_reason") == "length"
                    and len(res.get("token_ids") or ()) == MAX_TOKENS,
                    f"{rid}: usage={res.get('usage')} "
                    f"finish_reason={res.get('finish_reason')} "
                    f"token_ids={len(res.get('token_ids') or ())}",
                )
            return got

        def job(rid: str, n: int) -> dict:
            return {"id": rid, **_request(n)}

        t_answer = time.monotonic()
        rows = [job(f"batch-{i}", n) for i, n in enumerate(BATCH_PROMPTS[:n_batch])]
        results = await submit_and_receive(rows)
        # Two identical requests, each alone in the engine: the same
        # programs on the same inputs, so the same tokens — not close ones.
        twins = [
            (await submit_and_receive([job(rid, BATCH_PROMPTS[0])]))[rid]
            for rid in ("twin-0", "twin-1")
        ]
        check(
            twins[0]["token_ids"] == twins[1]["token_ids"],
            f"two identical requests gave different tokens: "
            f"{twins[0]['token_ids']} != {twins[1]['token_ids']}",
        )

        # Online path: the gateway of `llmq-tpu serve`, one request at a
        # time — blocking, then the same prompt streamed. Alone in the
        # engine both run the same programs on the same inputs, so the
        # two texts must be equal, not merely close.
        gateway = ServingGateway(QUEUE, port=0, request_timeout_s=900.0)
        await gateway.astart()
        http_answers = []
        for n in HTTP_PROMPTS[:n_http_prompts]:
            body = _request(n)
            status, raw = await asyncio.to_thread(_post, gateway.port, body)
            check(status == 200, f"blocking /v1/completions: {status} {raw[:300]!r}")
            choice = json.loads(raw)["choices"][0]
            status, raw = await asyncio.to_thread(
                _post, gateway.port, {**body, "stream": True}
            )
            check(status == 200, f"streamed /v1/completions: {status} {raw[:300]!r}")
            sse_text, sse_finish = _sse(raw)
            check(
                choice["finish_reason"] == "length" and sse_finish == "length",
                f"finish_reason blocking={choice['finish_reason']!r} "
                f"sse={sse_finish!r}",
            )
            check(
                sse_text == choice["text"],
                f"SSE text differs from the blocking text of the same "
                f"request: {sse_text!r} != {choice['text']!r}",
            )
            http_answers += [choice["text"], sse_text]
        answer_s = time.monotonic() - t_answer
        compile_done = meter.snapshot()

        stats = worker._engine_stats()
        core = worker.engine.core
        layers = core.model_config.num_layers
        device0 = core.mesh.devices.flat[0]
        held = {
            "params": _bytes_total_and_on(device0, jax.tree.leaves(core.params)),
            "kv_pool": _bytes_total_and_on(device0, [core.k_pages, core.v_pages]),
        }
        mgr = BrokerManager(get_config())
        await mgr.connect()
        try:
            dead = {
                suffix: (await mgr.get_queue_stats(QUEUE + suffix)).message_count
                for suffix in (FAILED_SUFFIX, QUARANTINE_SUFFIX)
            }
        finally:
            await mgr.disconnect()
    finally:
        if gateway is not None:
            await gateway.astop()
        worker.request_shutdown()
        await asyncio.wait_for(wtask, timeout=120)

    n_batch_answered = len(results) + len(twins)
    n_answered = n_batch_answered + len(http_answers)
    check(not any(dead.values()), f"dead-lettered or quarantined jobs: {dead}")
    check(
        worker.jobs_processed == n_answered and worker.jobs_failed == 0,
        f"worker processed {worker.jobs_processed} / failed {worker.jobs_failed}, "
        f"{n_answered} answered",
    )
    check(
        stats["generated_tokens"] == n_answered * MAX_TOKENS,
        f"engine generated {stats['generated_tokens']} tokens, "
        f"{n_answered} x {MAX_TOKENS} asked",
    )
    faults = {
        key: stats.get(key, 0)
        for key in ("engine_rebuilds", "hbm_oom_events", "preemptions",
                    "swap_refused", "watchdog_trips", "guard_trips")
    }
    check(not any(faults.values()), f"faults, rebuilds or fallbacks: {faults}")
    expect_backend = "xla" if mode.rehearse_cpu else "pallas"
    check(
        stats["attn_backend"] == expect_backend,
        f"attention backend {stats['attn_backend']!r}, not {expect_backend!r}",
    )
    if not mode.rehearse_cpu:
        # qwen2.5-7b at tp=4 leaves one kv head a shard: XLA attention by
        # the shape rule of ops/dispatch._tp_heads_ok. Everything else
        # the smoke serves runs the decode kernel.
        kernels = ("xla",) if mode.chips == 4 else ("live", "v1")
        check(
            stats["decode_kernel"] in kernels,
            f"decode kernel {stats['decode_kernel']!r}, not one of {kernels}",
        )
        check(stats["page_size"] == 128, f"page size {stats['page_size']}")
        check(
            stats["num_pages"] != 4096 and stats.get("hbm_bytes_limit"),
            f"pool of {stats['num_pages']} pages was not sized from bytes_limit "
            f"{stats.get('hbm_bytes_limit')}",
        )
        check(
            stats["kv_pool_bytes"] / stats["devices"]
            < stats["hbm_bytes_limit"] * worker.config.hbm_utilization,
            "the pool is larger than the HBM budget it was sized from",
        )
    return {
        "model": f"preset://{mode.preset}",
        "dtype": "bfloat16",
        "layers": layers,
        "answered": {"batch": n_batch_answered,
                     "http_blocking": len(http_answers) // 2,
                     "http_sse": len(http_answers) // 2},
        "max_tokens": MAX_TOKENS,
        "identical_requests_identical_tokens": True,
        "sse_text_equals_blocking_text": True,
        # Reported, not asserted: rows of one job wave may be prefilled by
        # different programs (1-row or 4-row), whose bf16 rounding may
        # differ.
        "same_prompt_same_tokens_within_a_wave": all(
            len({tuple(results[f"batch-{i}"]["token_ids"]) for i in group}) == 1
            for group in (
                [i for i, m in enumerate(BATCH_PROMPTS[:n_batch]) if m == n]
                for n in set(BATCH_PROMPTS[:n_batch])
            )
        ),
        "distinct_tokens_in_a_twin": len(set(twins[0]["token_ids"])),
        "http_text_chars": [len(t) for t in http_answers],
        "seconds_to_engine_ready": round(ready_s, 1),
        "seconds_answering": round(answer_s, 1),
        "compile_seconds_until_ready": round(
            compile_ready["compile_seconds"] - compile_before["compile_seconds"], 1
        ),
        "compile_seconds_while_answering": round(
            compile_done["compile_seconds"] - compile_ready["compile_seconds"], 1
        ),
        "compile_cache": {
            k: compile_done[k] - compile_before[k]
            for k in ("cache_requests", "cache_hits", "cache_misses")
        },
        "dead_letters": dead,
        "faults": faults,
        "attn_backend": stats["attn_backend"],
        "decode_kernel": stats["decode_kernel"],
        "page_size": stats["page_size"],
        "num_pages": stats["num_pages"],
        "slots": stats["slots"],
        "devices": stats["devices"],
        "kv_pool_bytes": stats["kv_pool_bytes"],
        "bytes_total_and_on_device0": held,
        "hbm_bytes_limit": stats.get("hbm_bytes_limit"),
        "hbm_peak_bytes_in_use": stats.get("hbm_peak_bytes_in_use"),
        "worker_stats": {
            k: stats.get(k)
            for k in ("prefills", "decode_steps", "prompt_tokens",
                      "generated_tokens", "ttft_p50_ms", "itl_p50_ms")
        },
    }


def phase_serve(mode: Mode, meter) -> dict:
    return asyncio.run(_serve(mode, meter, len(BATCH_PROMPTS), len(HTTP_PROMPTS)))


# --- phases: four chips ----------------------------------------------------


def phase_tp1_vs_tp4(mode: Mode) -> dict:
    """The same model, same weights, at tp=1 on one of the four devices
    and at tp=4 across them: first-token logits and greedy tokens. Widths
    are the preset's; depth is cut to what one chip can hold beside its
    own start-up transients (the cut is printed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding

    from llmq_tpu.models.presets import get_preset
    from llmq_tpu.models.transformer import (
        Transformer, init_params, make_kv_pages,
    )
    from llmq_tpu.parallel import make_mesh
    from llmq_tpu.parallel.sharding import kv_page_pspec, param_shardings

    full = get_preset(mode.compare_preset)
    if mode.rehearse_cpu:
        full = dataclasses.replace(full, num_heads=4, num_kv_heads=4)
        layers, why = full.num_layers, "tiny: not cut"
        page, T, steps, dtype = 16, 32, 8, jnp.float32
    else:
        # Weights at tp=1 may take half of one chip's HBM; the rest is for
        # the random init's transients, the pool, activations and logits.
        limit = jax.devices()[0].memory_stats()["bytes_limit"]

        def weight_bytes(n_layers: int) -> int:
            shapes = jax.eval_shape(
                partial(
                    init_params,
                    dataclasses.replace(full, num_layers=n_layers),
                    dtype=jnp.bfloat16,
                ),
                jax.random.key(0),
            )
            return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))

        per_layer = weight_bytes(2) - weight_bytes(1)
        fixed = weight_bytes(1) - per_layer
        layers = int(min(full.num_layers, (limit // 2 - fixed) // per_layer))
        why = (
            f"{layers} of {full.num_layers} layers: bf16 weights at tp=1 held "
            f"to half of bytes_limit={limit}"
        )
        page, T, steps, dtype = 128, 256, 32, jnp.bfloat16
    cfg = dataclasses.replace(full, num_layers=layers)
    B = 4
    pages_per_seq = -(-(T + steps) // page) + 1
    P = 1 + B * pages_per_seq
    rng = np.random.default_rng(mode.seed)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.asarray([T, T - 7, T // 2, T // 3], jnp.int32)
    bt = jnp.asarray(
        np.arange(1, P).reshape(B, pages_per_seq), jnp.int32
    )

    def run(tp: int, forced=None):
        """Prefill, then ``steps`` greedy decode steps. ``forced`` feeds
        another run's tokens instead of this run's own (teacher forcing),
        so one early near-tie does not turn every later token into a
        disagreement."""
        mesh = make_mesh(tensor_parallel=tp, devices=jax.devices()[:tp])
        model = Transformer(cfg, mesh=mesh)
        shardings = param_shardings(mesh, cfg)
        params = jax.jit(
            partial(init_params, cfg, dtype=dtype), out_shardings=shardings
        )(jax.random.key(mode.seed))
        kv = Format(
            Layout(tuple(range(5))),
            NamedSharding(mesh, kv_page_pspec(cfg, tp)),
        )
        kp, vp = make_kv_pages(cfg, P, page, dtype, placement=kv)
        prefill = jax.jit(
            model.prefill, in_shardings=(shardings, None, None, kv, kv, None),
            out_shardings=(None, kv, kv), donate_argnums=(3, 4),
        )
        decode = jax.jit(
            model.decode, in_shardings=(shardings, None, None, kv, kv, None, None),
            out_shardings=(None, kv, kv), donate_argnums=(3, 4),
        )
        facts = {}
        if tp > 1 and not mode.rehearse_cpu:
            text = decode.lower(
                params, tokens[:, 0], lengths, kp, vp, bt, jnp.ones((B,), bool)
            ).compile().as_text()
            check(
                "tpu_custom_call" in text,
                "no Mosaic attention kernel in the tp=4 decode step",
            )
            facts["decode_step_collectives"] = sorted(
                {op for op in ("all-reduce", "all-gather", "reduce-scatter",
                               "collective-permute") if op in text}
            )
        device0 = mesh.devices.flat[0]
        facts["param_bytes"], facts["param_bytes_device0"] = _bytes_total_and_on(
            device0, jax.tree.leaves(params)
        )
        _, facts["kv_pool_bytes_device0"] = _bytes_total_and_on(device0, [kp, vp])
        logits0, kp, vp = prefill(params, tokens, lengths, kp, vp, bt)
        logits0 = np.asarray(logits0, np.float32)
        check(bool(np.isfinite(logits0).all()), f"tp={tp}: non-finite logits")
        picked = [logits0.argmax(-1)]
        ctx, active = lengths, jnp.ones((B,), bool)
        for i in range(steps):
            cur = picked[-1] if forced is None else forced[i]
            logits, kp, vp = decode(
                params, jnp.asarray(cur, jnp.int32), ctx, kp, vp, bt, active
            )
            picked.append(np.asarray(logits, np.float32).argmax(-1))
            ctx = ctx + 1
        return logits0, np.stack(picked), facts

    logits1, tokens1, facts1 = run(1)
    logits4, tokens4, facts4 = run(4, forced=tokens1)
    scale = float(np.abs(logits1).max())
    err = float(np.abs(logits4 - logits1).max()) / scale
    agreement = float((tokens4 == tokens1).mean())
    check(
        err <= TP_LOGIT_TOL,
        f"first-token logits: max|tp4 - tp1| / max|tp1| = {err} > {TP_LOGIT_TOL}",
    )
    heads_divide = cfg.num_kv_heads % 4 == 0 and cfg.num_heads % 4 == 0
    if heads_divide:
        share = facts4["param_bytes_device0"] / facts4["param_bytes"]
        check(
            share < 0.3,
            f"tp=4 parameters are not sharded: device 0 holds {share:.2f} of them",
        )
        check(
            facts4["kv_pool_bytes_device0"] * 3 < facts1["kv_pool_bytes_device0"] * 1.01,
            "tp=4 KV pool is not sharded over the kv heads",
        )
    return {
        "model": f"preset://{mode.compare_preset}",
        "depth": why,
        "widths": f"{cfg.num_heads}/{cfg.num_kv_heads} heads, d={cfg.head_dim_}, "
        f"hidden={cfg.hidden_size}, mlp={cfg.intermediate_size}, "
        f"vocab={cfg.vocab_size}",
        "prompts": f"{B} x up to {T} tokens, {steps} greedy steps "
        "(tp=4 teacher-forced with tp=1's tokens)",
        "first_token_logits_max_rel_err": round(err, 5),
        "logit_tolerance": TP_LOGIT_TOL,
        "first_token_agrees": int((tokens4[0] == tokens1[0]).sum()),
        "greedy_token_agreement": round(agreement, 4),
        "tp1": facts1,
        "tp4": facts4,
    }


def phase_tp4_serve(mode: Mode, meter) -> dict:
    """The worker as the CLI builds it, on all four chips (its default),
    at the full depth of the preset, answering a few requests; then what
    each device actually holds."""
    facts = asyncio.run(_serve(mode, meter, 4, 1))
    check(facts["devices"] == 4, f"the worker's mesh has {facts['devices']} device(s)")
    if not mode.rehearse_cpu:  # tiny's two kv heads do not divide four
        for name, (total, on_device0) in facts["bytes_total_and_on_device0"].items():
            check(
                on_device0 < 0.3 * total,
                f"{name} not sharded: device 0 holds {on_device0} of {total} bytes",
            )
    return facts


# --- main ------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip path and its comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="walk the phases on the CPU at preset://tiny "
                         "(needs JAX_PLATFORMS=cpu); proves nothing about a chip")
    args = ap.parse_args()
    mode = Mode(chips=args.chips, rehearse_cpu=args.rehearse_cpu, seed=args.seed)

    device = run_phase("device", phase_device, mode)
    from llmq_tpu.utils.platform import CompileMeter

    meter = CompileMeter()  # nothing has been compiled yet
    if mode.chips == 1:
        run_phase("kernels", phase_kernels, mode)
        run_phase("serve", phase_serve, mode, meter)
    else:
        run_phase("tp1_vs_tp4", phase_tp1_vs_tp4, mode)
        run_phase("tp4_serve", phase_tp4_serve, mode, meter)
    emit(
        {
            "ok": True,
            "device": {
                "platform": device["platform"],
                "kind": device["device_kind"],
                "count": device["count"],
            },
        }
    )


if __name__ == "__main__":
    main()
