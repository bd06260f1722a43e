"""End-to-end probe of the SLO serving plane (priority + streaming).

Three legs, each printing a ``probe: <leg> ok`` line:

1. **sse** — OpenAI-style SSE round-trip over the memory broker: the
   gateway publishes a streaming job, a streaming worker answers with
   absolute-offset token-delta frames, and the assembled SSE text is
   byte-identical to the non-streaming result for the same prompt (and
   the request actually rode the interactive fast lane).
2. **preempt** — co-scheduled interactive + batch traffic through the
   engine twice over the same request set: a priority-off golden run,
   then a priority-on run where interactive admission preempts a
   running batch sequence — greedy outputs stay token-identical per
   request while at least one priority preemption fires.
3. **cancel** — a mid-decode cancel (the client-disconnect path)
   settles the request with ``finish_reason="cancelled"`` and returns
   every KV page it held to the pool.

Runs on CPU (preflight) and on device (hardware_session rungs)
identically.

    python tools/serve_probe.py
"""

import asyncio
import http.client
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from llmq_tpu.core.config import Config
from llmq_tpu.engine.engine import EngineConfig, EngineCore
from llmq_tpu.engine.sampling import SamplingParams
from llmq_tpu.engine.tokenizer import ByteTokenizer
from llmq_tpu.gateway import ServingGateway
from llmq_tpu.models.presets import get_preset
from llmq_tpu.models.transformer import init_params
from llmq_tpu.parallel import make_mesh
from llmq_tpu.workers.dummy import DummyWorker

_model_config = get_preset("tiny")
_params = init_params(_model_config, jax.random.key(0), dtype=jnp.float32)


def build_core(**overrides) -> EngineCore:
    cfg = EngineConfig(
        max_num_seqs=4,
        max_model_len=128,
        page_size=8,
        num_pages=96,
        kv_dtype=jnp.float32,
        **overrides,
    )
    return EngineCore(
        _model_config,
        _params,
        ByteTokenizer(),
        mesh=make_mesh(tensor_parallel=1),
        engine_config=cfg,
    )


def sampling(max_tokens=16):
    return SamplingParams(
        max_tokens=max_tokens, temperature=0.0, ignore_eos=True
    )


# --- leg 1: SSE round-trip ---------------------------------------------------

def _post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(
        "POST", path, json.dumps(body), {"Content-Type": "application/json"}
    )
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _post_sse(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request(
        "POST", path, json.dumps(body), {"Content-Type": "application/json"}
    )
    resp = conn.getresponse()
    events, buf = [], b""
    while True:
        chunk = resp.read1(65536)
        if not chunk:
            break
        buf += chunk
        while b"\n\n" in buf:
            ev, buf = buf.split(b"\n\n", 1)
            if ev.startswith(b"data: "):
                events.append(ev[6:].decode())
    conn.close()
    return resp.status, events


async def _sse_leg_async():
    cfg = Config(broker_url="memory://serve_probe")
    gw = ServingGateway("spq", config=cfg, port=0, request_timeout_s=60)
    await gw.astart()
    worker = DummyWorker("spq", delay=0, config=cfg, concurrency=4)
    wtask = asyncio.ensure_future(worker.run())
    try:
        prompt = "serve probe round trip"
        status, raw = await asyncio.to_thread(
            _post, gw.port, "/v1/completions", {"prompt": prompt}
        )
        assert status == 200, raw
        blocking_text = json.loads(raw)["choices"][0]["text"]

        status, events = await asyncio.to_thread(
            _post_sse,
            gw.port,
            "/v1/completions",
            {"prompt": prompt, "stream": True},
        )
        assert status == 200 and events[-1] == "[DONE]", events[-3:]
        streamed = "".join(
            json.loads(e)["choices"][0]["text"] for e in events[:-1]
        )
        finish = json.loads(events[-2])["choices"][0]["finish_reason"]
        assert streamed == blocking_text, (
            f"SSE text {streamed!r} != blocking result {blocking_text!r}"
        )
        assert finish == "stop", finish
        assert gw.mgr.interactive_routed >= 2, (
            "gateway requests never rode the interactive fast lane"
        )
        assert worker.stream_frames_published > 0
        return streamed, len(events)
    finally:
        worker.request_shutdown()
        await asyncio.wait_for(wtask, timeout=30)
        await gw.astop()


def run_sse_leg():
    streamed, n_events = asyncio.run(_sse_leg_async())
    print(
        f"probe: sse leg ok — {n_events} SSE events reassembled "
        f"byte-identical to the blocking result ({streamed!r}), "
        "fast-lane routed"
    )


# --- leg 2: priority preemption with token parity ----------------------------

def _co_scheduled_run(priority_on: bool):
    """6 batch requests saturating 4 slots, then 2 short interactive
    requests injected mid-decode. Returns (token_ids by rid, stats)."""
    core = build_core()
    for i in range(6):
        core.add_request(
            f"b{i}",
            prompt=("batch load " + "xy " * (i + 2)),
            params=sampling(24),
        )
    tokens, steps, added = {}, 0, 0
    while core.has_work or added < 2:
        if steps >= 3 and added < 2:
            core.add_request(
                f"i{added}",
                prompt=f"interactive {added}",
                params=sampling(8),
                priority="interactive" if priority_on else "batch",
            )
            added += 1
        for out in core.step():
            tokens[out.rid] = list(out.token_ids)
        steps += 1
    return tokens, core.stats()


def run_preempt_leg():
    golden, base_stats = _co_scheduled_run(priority_on=False)
    assert base_stats.get("priority_preemptions", 0) == 0
    prio, stats = _co_scheduled_run(priority_on=True)
    assert set(golden) == set(prio), (sorted(golden), sorted(prio))
    mismatched = [r for r in golden if golden[r] != prio[r]]
    assert not mismatched, (
        f"priority scheduling changed greedy tokens for {mismatched}"
    )
    preempts = stats.get("priority_preemptions", 0)
    assert preempts > 0, (
        "interactive admission never preempted a batch victim "
        f"(stats: { {k: v for k, v in stats.items() if 'inter' in k or 'preempt' in k} })"
    )
    print(
        f"probe: preempt leg ok — {len(golden)} requests token-identical "
        f"priority-on vs priority-off, {preempts} batch preemption(s)"
    )


# --- leg 3: cancel frees pages ----------------------------------------------

def run_cancel_leg():
    core = build_core()
    avail0 = core.scheduler.allocator.available
    core.add_request("keep", prompt="survivor request", params=sampling(12))
    core.add_request("c0", prompt="doomed request", params=sampling(64))
    for _ in range(3):
        core.step()
    core.cancel_request("c0")
    finished = {}
    while core.has_work:
        for out in core.step():
            finished[out.rid] = out.finish_reason
    assert finished.get("c0") == "cancelled", finished
    assert finished.get("keep") == "length", finished
    avail1 = core.scheduler.allocator.available
    assert avail1 == avail0, (
        f"cancel leaked KV pages: {avail0} free before, {avail1} after"
    )
    assert core.stats().get("cancellations") == 1
    print(
        "probe: cancel leg ok — mid-decode cancel settled with "
        "finish_reason=cancelled and returned every KV page "
        f"({avail0} free)"
    )


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    run_sse_leg()
    run_preempt_leg()
    run_cancel_leg()
    print("metric: serve_probe_ok legs=3")


if __name__ == "__main__":
    main()
