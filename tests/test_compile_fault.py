"""A step program the compiler refuses is not a device fault.

The fault plane (PR 13) rebuilds the engine and retries after a device
fault. A program that does not *compile* — it does not fit HBM, Mosaic
rejects a kernel — fails the same way on every rebuild and for every
job, so retrying it makes a worker that is up, exits 0 and answers
nothing. These tests pin the other behaviour: the engine tells the two
apart (``_StepProgram``), stops, and the worker exits non-zero with the
jobs back on the queue.
"""

import asyncio

import jax.numpy as jnp
import pytest

from llmq_tpu.broker.manager import FAILED_SUFFIX, BrokerManager
from llmq_tpu.core.config import Config
from llmq_tpu.core.faults import FAULT_OOM, StepCompileError, classify_failure
from llmq_tpu.core.models import Job
from llmq_tpu.engine.engine import _StepProgram
from llmq_tpu.workers.tpu_worker import TPUWorker


class _FakeJit:
    """Stands in for the jitted callable: a cold call (it 'traces', which
    is what marks a call cold) that raises ``error``; compiling it on its
    own raises ``compile_error`` if given."""

    def __init__(self, program, error, compile_error=None):
        self.program, self.error, self.compile_error = program, error, compile_error
        self.compiles = 0

    def __call__(self, *args):
        self.program._cold = True
        raise self.error

    def lower(self, *args):
        return self

    def compile(self):
        self.compiles += 1
        if self.compile_error is not None:
            raise self.compile_error


class TestStepProgram:
    def test_a_program_that_cannot_be_traced_is_a_compile_error(self):
        def step(x):
            raise ValueError("The Pallas TPU lowering currently requires ...")

        program = _StepProgram(step)
        with pytest.raises(StepCompileError, match="'step' does not compile"):
            program(jnp.ones(3))

    def test_cold_call_refused_by_the_compiler_is_a_compile_error(self):
        refused = RuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
            "memory in memory space hbm. Used 18.93G of 15.75G hbm."
        )
        program = _StepProgram(lambda x: x)
        program._jit = _FakeJit(program, refused, compile_error=refused)
        with pytest.raises(StepCompileError, match="Used 18.93G of 15.75G"):
            program(jnp.ones(3))

    def test_cold_call_that_compiles_but_fails_to_run_keeps_its_fault(self):
        # The first run of a fresh program hits an allocation fault: the
        # program compiles fine on its own, so this is a device fault and
        # goes to the OOM ladder as before.
        oom = RuntimeError("RESOURCE_EXHAUSTED: Error allocating device buffer")
        program = _StepProgram(lambda x: x)
        program._jit = fake = _FakeJit(program, oom)
        with pytest.raises(RuntimeError) as raised:
            program(jnp.ones(3))
        assert raised.value is oom and fake.compiles == 1
        assert classify_failure(raised.value) == FAULT_OOM

    def test_warm_call_is_never_recompiled(self):
        program = _StepProgram(lambda x: x + 1)
        assert float(program(jnp.ones(()))) == 2.0  # compiles, runs: warm

        class Boom(_FakeJit):
            def __call__(self, *args):
                raise self.error  # warm: the body is not traced again

        program._jit = fake = Boom(program, RuntimeError("INTERNAL: dispatch"))
        with pytest.raises(RuntimeError, match="INTERNAL: dispatch"):
            program(jnp.ones(()))
        assert fake.compiles == 0

    def test_keeps_the_step_name_and_lowers_like_a_jit(self):
        def decode_step(x):
            return x * 2

        program = _StepProgram(decode_step)
        assert program.name == "decode_step"
        assert "decode_step" in program.lower(jnp.ones(2)).as_text()


async def test_worker_exits_nonzero_and_requeues_when_a_step_does_not_compile(
    mem_url,
):
    """End to end: the first dispatch raises what the compiler raises.
    No rebuild, no retry, no dead letter — the worker's run() raises the
    error (the CLI then exits non-zero) and the job is back on the queue
    for a worker that can compile."""
    config = Config(broker_url=mem_url)
    worker = TPUWorker(
        "cq", config=config, concurrency=4, model="preset://tiny",
        tensor_parallel=1, max_model_len=64, num_pages=40, page_size=8,
        dtype="float32", max_num_seqs=4,
    )
    build = worker._build_engine
    rebuilds = []

    def build_with_refusing_compiler():
        engine = build()
        engine.rebuild_core = lambda: rebuilds.append(1)

        def refused(*args):
            raise StepCompileError(
                "step program 'prefill_step' does not compile: "
                "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error."
            )

        for program in engine.core._prefill_jits.values():
            program._jit = refused
        return engine

    worker._build_engine = build_with_refusing_compiler
    async with BrokerManager(config) as mgr:
        await mgr.setup_queue_infrastructure("cq")
        await mgr.publish_job(
            "cq", Job(id="j1", prompt="hello", temperature=0.0, max_tokens=4)
        )
        with pytest.raises(StepCompileError, match="does not compile"):
            await asyncio.wait_for(worker.run(), timeout=120)
        assert not rebuilds, "a compile failure was retried as a device fault"
        assert worker.jobs_processed == 0
        assert (await mgr.get_queue_stats("cq")).message_count == 1
        assert not (await mgr.get_queue_stats("cq" + FAILED_SUFFIX)).message_count
