"""Serving gateway: OpenAI-compatible HTTP/SSE over the queue broker.

Everything runs in-process against the memory broker via
``ServingGateway.astart()`` (the gateway shares the test's event loop —
the memory core is loop-affine), with ``DummyWorker`` as the streaming
backend or the test itself acting as the worker on the raw queues.
"""

import asyncio
import http.client
import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from llmq_tpu.broker.manager import (
    BrokerManager,
    ctl_queue_name,
    interactive_queue_name,
    stream_queue_name,
)
from llmq_tpu.core.config import Config
from llmq_tpu.core.models import Job, Result
from llmq_tpu.gateway import ServingGateway
from llmq_tpu.gateway.server import _GatewayHandler
from llmq_tpu.workers.dummy import DummyWorker

REPO = Path(__file__).resolve().parents[1]


# --- HTTP helpers (handler threads; call via asyncio.to_thread) ------------

def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, json.loads(data)


def _post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(
        "POST", path, json.dumps(body), {"Content-Type": "application/json"}
    )
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _post_sse(port, path, body, *, hang_up_after=None):
    """POST a streaming request and collect SSE ``data:`` payloads.

    ``hang_up_after=N`` closes the socket hard after N events — the
    client-disconnect path under test."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request(
        "POST", path, json.dumps(body), {"Content-Type": "application/json"}
    )
    resp = conn.getresponse()
    events, buf = [], b""
    while True:
        if hang_up_after is not None and len(events) >= hang_up_after:
            # The gateway sends Connection: close, so http.client hands
            # the socket to the response; closing it here drops the TCP
            # connection with data still in flight — a real hang-up.
            resp.close()
            break
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


def _sse_text(events):
    return "".join(
        json.loads(e)["choices"][0].get("text", "") for e in events[:-1]
    )


async def _wait_for(cond, timeout=10.0, what="condition"):
    deadline = asyncio.get_running_loop().time() + timeout
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, f"timed out: {what}"
        await asyncio.sleep(0.02)


class TestGatewayWithWorker:
    """Full path: HTTP -> broker -> DummyWorker -> frames/result -> client."""

    async def test_blocking_completion_and_discovery(self, mem_url):
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        worker = DummyWorker("gq", delay=0, config=cfg, concurrency=4)
        wtask = asyncio.ensure_future(worker.run())
        try:
            status, health = await asyncio.to_thread(_get, gw.port, "/healthz")
            assert (status, health["queue"]) == (200, "gq")
            status, models = await asyncio.to_thread(_get, gw.port, "/v1/models")
            assert status == 200
            assert models["data"][0]["id"] == "llmq-tpu"

            status, raw = await asyncio.to_thread(
                _post, gw.port, "/v1/completions", {"prompt": "hello gateway"}
            )
            assert status == 200, raw
            body = json.loads(raw)
            assert body["choices"][0]["text"] == "echo hello gateway"
            assert body["choices"][0]["finish_reason"] == "stop"
            assert body["object"] == "text_completion"
            # Requests default to the interactive class -> fast lane.
            assert gw.mgr.interactive_routed == 1
            assert gw.requests_total == 1 and gw.requests_streamed == 0
        finally:
            worker.request_shutdown()
            await asyncio.wait_for(wtask, timeout=15)
            await gw.astop()

    async def test_sse_stream_matches_blocking_result(self, mem_url):
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        worker = DummyWorker("gq", delay=0, config=cfg, concurrency=4)
        wtask = asyncio.ensure_future(worker.run())
        try:
            prompt = "stream me three words"
            status, raw = await asyncio.to_thread(
                _post, gw.port, "/v1/completions", {"prompt": prompt}
            )
            blocking = json.loads(raw)["choices"][0]["text"]

            status, events = await asyncio.to_thread(
                _post_sse,
                gw.port,
                "/v1/completions",
                {"prompt": prompt, "stream": True},
            )
            assert status == 200
            assert events[-1] == "[DONE]"
            assert _sse_text(events) == blocking == f"echo {prompt}"
            final = json.loads(events[-2])
            assert final["choices"][0]["finish_reason"] == "stop"
            assert gw.requests_streamed == 1
            assert worker.stream_frames_published > 1
        finally:
            worker.request_shutdown()
            await asyncio.wait_for(wtask, timeout=15)
            await gw.astop()

    async def test_chat_sse_deltas(self, mem_url):
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        worker = DummyWorker("gq", delay=0, config=cfg, concurrency=4)
        wtask = asyncio.ensure_future(worker.run())
        try:
            status, events = await asyncio.to_thread(
                _post_sse,
                gw.port,
                "/v1/chat/completions",
                {
                    "messages": [{"role": "user", "content": "chat stream"}],
                    "stream": True,
                },
            )
            assert status == 200 and events[-1] == "[DONE]"
            text = "".join(
                json.loads(e)["choices"][0].get("delta", {}).get("content", "")
                for e in events[:-1]
            )
            assert text == "echo chat stream"
            assert json.loads(events[0])["object"] == "chat.completion.chunk"
        finally:
            worker.request_shutdown()
            await asyncio.wait_for(wtask, timeout=15)
            await gw.astop()


class TestGatewayWire:
    """The test plays the worker on the raw queues: job pickup off the
    fast lane, frame dedup, tail reconciliation, disconnect cancel."""

    async def _fetch_job(self, mgr, queue, timeout=10.0):
        lane = interactive_queue_name(queue)
        deadline = asyncio.get_running_loop().time() + timeout
        while True:
            msg = await mgr.broker.get(lane)
            if msg is not None:
                await msg.ack()
                return Job(**json.loads(msg.body))
            assert asyncio.get_running_loop().time() < deadline, (
                f"no job arrived on {lane}"
            )
            await asyncio.sleep(0.02)

    async def _frame(self, mgr, queue, job_id, off, text, *, done=False,
                     finish=None, worker_id="wk-test"):
        sq = stream_queue_name(queue, job_id)
        await mgr.broker.declare_queue(
            sq, ttl_ms=60_000, max_redeliveries=1_000_000_000
        )
        frame = {
            "id": job_id,
            "text_offset": off,
            "text": text,
            "worker_id": worker_id,
        }
        if done:
            frame["done"] = True
            frame["finish_reason"] = finish or "stop"
        await mgr.broker.publish(
            sq,
            json.dumps(frame).encode("utf-8"),
            message_id=f"{job_id}.{off}.{int(done)}",
        )

    async def test_fast_lane_payload_and_field_whitelist(self, mem_url):
        """The published job rides <q>.interactive, carries the priority
        class and whitelisted sampling fields, and drops anything a
        client tries to smuggle (broker-internal fields)."""
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        try:
            post = asyncio.ensure_future(
                asyncio.to_thread(
                    _post,
                    gw.port,
                    "/v1/completions",
                    {
                        "prompt": "whitelist check",
                        "max_tokens": 17,
                        "temperature": 0.5,
                        "deadline_at": 1.0,  # smuggled: must be dropped
                        "worker_affinity": "evil",  # smuggled
                    },
                )
            )
            async with BrokerManager(cfg) as mgr:
                job = await self._fetch_job(mgr, "gq")
                payload = json.loads(job.model_dump_json())
                assert payload["priority"] == "interactive"
                assert payload["max_tokens"] == 17
                assert payload["temperature"] == 0.5
                assert payload["deadline_at"] is None
                assert "worker_affinity" not in payload
                await mgr.publish_result(
                    "gq",
                    Result(id=job.id, prompt="whitelist check",
                           result="done", worker_id="wk-test", duration_ms=1.0),
                )
            status, raw = await post
            assert status == 200
            assert json.loads(raw)["choices"][0]["text"] == "done"
        finally:
            await gw.astop()

    async def test_sse_offset_dedup_across_restream(self, mem_url):
        """A worker resumed on a peer re-streams from offset 0; the
        gateway's character high-water mark emits every byte exactly
        once."""
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        try:
            post = asyncio.ensure_future(
                asyncio.to_thread(
                    _post_sse,
                    gw.port,
                    "/v1/completions",
                    {"prompt": "p", "stream": True},
                )
            )
            async with BrokerManager(cfg) as mgr:
                job = await self._fetch_job(mgr, "gq")
                await self._frame(mgr, "gq", job.id, 0, "Hello ")
                # Restream from zero (kill + resume), overlapping then new:
                await self._frame(mgr, "gq", job.id, 0, "Hello ")
                await self._frame(mgr, "gq", job.id, 6, "wor")
                await self._frame(mgr, "gq", job.id, 0, "Hello world")
                await self._frame(
                    mgr, "gq", job.id, 11, "", done=True, finish="stop"
                )
                await mgr.publish_result(
                    "gq",
                    Result(id=job.id, prompt="p", result="Hello world",
                           worker_id="wk-test", duration_ms=1.0),
                )
            status, events = await post
            assert status == 200 and events[-1] == "[DONE]"
            assert _sse_text(events) == "Hello world"
            assert json.loads(events[-2])["choices"][0]["finish_reason"] == "stop"
        finally:
            await gw.astop()

    async def test_sse_tail_reconciled_from_result(self, mem_url):
        """Lost done frame (worker died, nobody resumed the stream): the
        final Result settles the request and the handler emits the
        missing tail before [DONE]."""
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        try:
            post = asyncio.ensure_future(
                asyncio.to_thread(
                    _post_sse,
                    gw.port,
                    "/v1/completions",
                    {"prompt": "p", "stream": True},
                )
            )
            async with BrokerManager(cfg) as mgr:
                job = await self._fetch_job(mgr, "gq")
                await self._frame(mgr, "gq", job.id, 0, "partial ")
                await mgr.publish_result(
                    "gq",
                    Result(id=job.id, prompt="p", result="partial answer",
                           worker_id="wk-test", duration_ms=1.0),
                )
            status, events = await post
            assert status == 200 and events[-1] == "[DONE]"
            assert _sse_text(events) == "partial answer"
        finally:
            await gw.astop()

    async def test_disconnect_cancels_on_worker_ctl_queue(self, mem_url):
        """Client hangs up mid-stream: the gateway publishes a cancel to
        the serving worker's ctl queue and the eventual Result lands as
        an acked orphan — nothing requeues, nothing leaks."""
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        try:
            post = asyncio.ensure_future(
                asyncio.to_thread(
                    _post_sse,
                    gw.port,
                    "/v1/completions",
                    {"prompt": "p", "stream": True},
                    hang_up_after=1,
                )
            )
            async with BrokerManager(cfg) as mgr:
                job = await self._fetch_job(mgr, "gq")
                await self._frame(mgr, "gq", job.id, 0, "chunk one ")
                await post  # client read one event and closed the socket
                # Keep feeding frames until a write trips the dead socket.
                off = 10
                for i in range(200):
                    if gw.cancels_sent:
                        break
                    await self._frame(mgr, "gq", job.id, off, f"more{i} ")
                    off += len(f"more{i} ")
                    await asyncio.sleep(0.02)
                assert gw.cancels_sent == 1, "disconnect never sent a cancel"
                ctl = ctl_queue_name("gq", "wk-test")
                msg = await mgr.broker.get(ctl)
                assert msg is not None, "no cancel on the worker ctl queue"
                assert json.loads(msg.body) == {"cancel": job.id}
                await msg.ack()
                # The worker still finishes the decode it had in flight;
                # its Result is acked-and-counted, not requeued.
                await mgr.publish_result(
                    "gq",
                    Result(id=job.id, prompt="p", result="too late",
                           worker_id="wk-test", duration_ms=1.0),
                )
                await _wait_for(
                    lambda: gw.orphan_results == 1, what="orphan counted"
                )
                stats = await mgr.get_queue_stats("gq.results")
                assert stats.message_count == 0
        finally:
            await gw.astop()

    async def test_unknown_result_acked_as_orphan(self, mem_url):
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=30)
        await gw.astart()
        try:
            async with BrokerManager(cfg) as mgr:
                await mgr.publish_result(
                    "gq",
                    Result(id="not-ours", prompt="x", result="y",
                           worker_id="w", duration_ms=1.0),
                )
                await _wait_for(
                    lambda: gw.orphan_results == 1, what="orphan counted"
                )
                stats = await mgr.get_queue_stats("gq.results")
                assert stats.message_count == 0
        finally:
            await gw.astop()


class TestGatewayValidation:
    async def test_request_validation_errors(self, mem_url):
        cfg = Config(broker_url=mem_url)
        gw = ServingGateway("gq", config=cfg, port=0, request_timeout_s=5)
        await gw.astart()
        try:
            for path, body, needle in (
                ("/v1/completions", {}, "prompt"),
                ("/v1/completions", {"prompt": ""}, "prompt"),
                ("/v1/chat/completions", {"messages": []}, "messages"),
                ("/v1/chat/completions", {"messages": "hi"}, "messages"),
                (
                    "/v1/completions",
                    {"prompt": "p", "priority": "urgent"},
                    "priority",
                ),
            ):
                status, raw = await asyncio.to_thread(_post, gw.port, path, body)
                assert status == 400, (path, body, raw)
                assert needle in json.loads(raw)["error"]["message"]
            status, raw = await asyncio.to_thread(
                _post, gw.port, "/v1/nope", {"prompt": "p"}
            )
            assert status == 404
            # No request ever reached the broker or the registry.
            assert gw.requests_total == 0 and not gw._pending
        finally:
            await gw.astop()

    def test_build_payload_priority_and_whitelist(self):
        """Unit: body -> job payload mapping (no sockets involved)."""
        h = object.__new__(_GatewayHandler)
        h.gateway = SimpleNamespace(default_priority="interactive")
        errors = []
        h._error = lambda code, msg: errors.append((code, msg))

        p = h._build_payload(
            {"prompt": "x", "max_tokens": 5, "stop": ["\n"],
             "ignore_eos": True, "priority": "batch", "internal_field": 1},
            chat=False,
        )
        assert p["priority"] == "batch"
        assert p["max_tokens"] == 5 and p["stop"] == ["\n"]
        assert p["ignore_eos"] is True
        assert "internal_field" not in p
        assert p["id"].startswith("gw-")

        p = h._build_payload({"prompt": "x"}, chat=False)
        assert p["priority"] == "interactive"  # gateway default

        assert h._build_payload({"prompt": "x", "priority": "now"}, False) is None
        assert errors and errors[-1][0] == 400

    def test_default_priority_validated(self):
        with pytest.raises(ValueError):
            ServingGateway("q", config=Config(broker_url="memory://x"),
                           default_priority="urgent")


@pytest.mark.slow
def test_serve_probe_end_to_end():
    """The hardware-ladder probe (gateway SSE parity, priority preemption
    token parity, cancel-frees-pages) passes on CPU."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "serve_probe.py")],
        capture_output=True,
        text=True,
        timeout=900,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "metric: serve_probe_ok legs=3" in proc.stdout
