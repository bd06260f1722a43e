"""TPUWorker end-to-end over the in-memory broker: the full
submit→queue→engine→result path with a preset (random-weight) model —
the suite-level analogue of the reference's DummyWorker integration tests,
but exercising the real engine."""

import asyncio

from llmq_tpu.broker.manager import BrokerManager
from llmq_tpu.core.config import Config
from llmq_tpu.core.models import Job, Result
from llmq_tpu.workers.tpu_worker import TPUWorker


def make_worker(mem_url, queue="tpu-q", **kw):
    config = Config(broker_url=mem_url)
    kw.setdefault("model", "preset://tiny")
    kw.setdefault("tensor_parallel", 1)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("num_pages", 40)
    kw.setdefault("page_size", 8)
    kw.setdefault("dtype", "float32")
    kw.setdefault("max_num_seqs", 4)
    return TPUWorker(queue, config=config, concurrency=4, **kw)


async def submit_and_collect(mem_url, queue, jobs, worker, timeout=120.0):
    broker = BrokerManager(Config(broker_url=mem_url))
    await broker.connect()
    await broker.setup_queue_infrastructure(queue)
    for job in jobs:
        await broker.publish_job(queue, job)

    task = asyncio.create_task(worker.run())
    results = []
    try:

        async def handler(message):
            results.append(Result.model_validate_json(message.body))
            await message.ack()

        await broker.consume_results(queue + ".results", handler)
        deadline = asyncio.get_event_loop().time() + timeout
        while len(results) < len(jobs):
            if asyncio.get_event_loop().time() > deadline:
                raise TimeoutError(f"got {len(results)}/{len(jobs)} results")
            await asyncio.sleep(0.05)
    finally:
        worker.request_shutdown()
        await asyncio.wait_for(task, timeout=30)
        await broker.disconnect()
    return results


async def test_tpu_worker_end_to_end(mem_url):
    jobs = [
        Job(
            id=f"job-{i}",
            prompt="say {word}",
            word=f"w{i}",
            temperature=0.0,
            max_tokens=4,
            ignore_eos=True,
        )
        for i in range(5)
    ]
    worker = make_worker(mem_url)
    results = await submit_and_collect(mem_url, "tpu-q", jobs, worker)
    assert {r.id for r in results} == {f"job-{i}" for i in range(5)}
    for r in results:
        assert r.usage == {"prompt_tokens": 6, "completion_tokens": 4}
        assert r.worker_id.startswith("tpu-worker-")
        assert r.duration_ms > 0
        # extra-field passthrough
        assert r.model_dump()["word"].startswith("w")
        # The engine's terminal finish_reason rides the Result (these
        # jobs hit max_tokens under ignore_eos → "length"): the gateway's
        # blocking path reports it, so it must match the stream done
        # frame, not default to "stop".
        assert r.model_dump()["finish_reason"] == "length"


def test_worker_id_unique_in_process(mem_url):
    """Two workers in ONE process (the disagg prefill/decode pair) must
    not share a worker_id: host+pid alone collided, which made peer
    discovery see the pair as one worker and the KV handoff silently
    take the snapshot fallback every time (PERF_NOTES round 16). The id
    also carries the configured role so heartbeats and queue names are
    self-describing."""
    a = make_worker(mem_url)
    b = make_worker(mem_url)
    assert a.worker_id != b.worker_id
    assert a.worker_id.startswith("tpu-worker-")
    assert "-unified-i" in a.worker_id
    # Role rides in the id: a prefill-role worker is distinguishable
    # from a decode-role worker on the same host+pid at a glance.
    config = Config(broker_url=mem_url, worker_role="prefill")
    c = TPUWorker(
        "tpu-q", config=config, model="preset://tiny", tensor_parallel=1,
        dtype="float32", max_num_seqs=4,
    )
    assert "-prefill-i" in c.worker_id
    assert len({a.worker_id, b.worker_id, c.worker_id}) == 3


async def test_tpu_worker_messages_job(mem_url):
    jobs = [
        Job(
            id="chat-1",
            messages=[{"role": "user", "content": "hello"}],
            temperature=0.0,
            max_tokens=3,
            ignore_eos=True,
        )
    ]
    worker = make_worker(mem_url, queue="chat-q")
    results = await submit_and_collect(mem_url, "chat-q", jobs, worker)
    assert results[0].usage["completion_tokens"] == 3


async def test_tpu_worker_sampling_options_object(mem_url):
    jobs = [
        Job(
            id="s-1",
            prompt="hi",
            sampling={"temperature": 0.0, "max_tokens": 2},
            ignore_eos=True,
        )
    ]
    worker = make_worker(mem_url, queue="s-q")
    results = await submit_and_collect(mem_url, "s-q", jobs, worker)
    assert results[0].usage["completion_tokens"] == 2


def test_worker_id_encodes_topology():
    worker = make_worker("memory://wid-test", tensor_parallel=2)
    assert "-tp2-dp1" in worker.worker_id


async def test_worker_starts_without_a_probing_child(monkeypatch):
    """No engine factory and ``tp_overlap`` not ``auto``: start-up is the
    engine build alone. Even where a probe would apply (a TPU host, the
    chip not yet held) the worker starts no child process."""
    import subprocess

    import jax

    import llmq_tpu.engine.kernel_autotune as ka

    def never(*a, **k):
        raise AssertionError("the worker started a child process")

    jax.devices()  # JAX has read JAX_PLATFORMS=cpu; the probes' gate has not
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("LLMQ_KERNEL_AUTOTUNE", raising=False)
    monkeypatch.setattr(ka, "_probe_blocked", lambda: None)
    monkeypatch.setattr(subprocess, "run", never)
    monkeypatch.setattr(subprocess, "Popen", never)
    worker = make_worker("memory://no-probe")
    await worker._initialize_processor()
    try:
        stats = worker._engine_stats()
        assert stats["decode_kernel"] == "xla"  # a CPU run
        assert "decode_kernel_probe_s" not in stats
    finally:
        worker.engine.shutdown()


async def test_tpu_worker_result_carries_engine_trace(mem_url):
    """The result's lifecycle trace includes the engine-phase events
    (tokenized/prefill_start/first_token/decode) backfilled from the
    engine's per-sequence stamps, in monotone wall-clock order."""
    from llmq_tpu.obs import timeline, trace_from_payload

    jobs = [
        Job(
            id="traced-1",
            prompt="hello trace",
            temperature=0.0,
            max_tokens=4,
            ignore_eos=True,
        )
    ]
    worker = make_worker(mem_url, queue="trace-q")
    results = await submit_and_collect(mem_url, "trace-q", jobs, worker)
    payload = results[0].model_dump()
    trace = trace_from_payload(payload)
    assert trace is not None
    assert trace["redeliveries"] == 0
    names = [e["name"] for e in trace["events"]]
    for needed in (
        "submitted",
        "claimed",
        "tokenized",
        "prefill_start",
        "first_token",
        "decode",
        "finished",
    ):
        assert needed in names, f"missing '{needed}' in {names}"
    assert names.count("claimed") == 1 and names.count("finished") == 1
    rows = timeline(trace)
    walls = [r["t_wall"] for r in rows]
    assert walls == sorted(walls), f"timeline not monotone: {names}"
    decode = next(e for e in trace["events"] if e["name"] == "decode")
    assert decode["tokens"] == 4


async def test_stream_frames_hold_back_a_split_multibyte_character(mem_url):
    """The streamed frames must add up to the text of the final Result.
    A character whose bytes arrive in different tokens decodes, while
    incomplete, to U+FFFD — which must not be published, because a frame
    cannot be taken back once the character completes."""
    import json
    from types import SimpleNamespace

    from llmq_tpu.engine.tokenizer import ByteTokenizer

    tok = ByteTokenizer()
    engine = SimpleNamespace(
        core=SimpleNamespace(tokenizer=tok),
        set_token_callback=lambda rid, cb: None,
        clear_token_callback=lambda rid: None,
    )
    worker = make_worker(mem_url, engine_factory=lambda w: engine)
    worker.engine = engine
    await worker.broker.connect()
    try:
        job = Job(id="s-utf8", prompt="x", stream=True)
        assert await worker._stream_begin(job)
        ids = tok.encode("a\u20acb")  # the euro sign is three bytes
        for n_out, token in enumerate(ids, 1):
            worker._note_stream_token(job.id, token, n_out)
            await asyncio.sleep(0)  # one flush per token: the worst case
            while worker._streams[job.id]["flushing"]:
                await asyncio.sleep(0)
        queue = worker._streams[job.id]["queue"]
        await worker._stream_finish(
            job, SimpleNamespace(finish_reason="length", text="a\u20acb")
        )
        text, frames = "", 0
        while (msg := await worker.broker.broker.get(queue)) is not None:
            frame = json.loads(msg.body)
            assert frame["text_offset"] == len(text)
            text += frame["text"]
            frames += 1
            await msg.ack()
        assert text == "a\u20acb" and "\ufffd" not in text
        assert frames >= 3  # "a", the euro sign once whole, "b", done
    finally:
        await worker.broker.disconnect()
