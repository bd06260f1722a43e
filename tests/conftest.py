"""Test harness configuration.

- Forces JAX onto a virtual 8-device CPU platform *before* jax is imported
  anywhere, so the whole suite (including multi-chip sharding tests) runs
  without TPU hardware — the pattern the task prescribes for multi-chip
  validation.
- Runs ``async def`` tests via ``asyncio.run`` (no pytest-asyncio in the
  image).
"""

import asyncio
import inspect
import os

# Must happen before jax is imported: tests run on the CPU
# (JAX_PLATFORMS=cpu — the one switch the program itself honours, see
# llmq_tpu/utils/platform.py) with eight virtual devices for the meshes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402

import llmq_tpu.broker.memory as memory_broker  # noqa: E402
from llmq_tpu.analysis.pytest_plugin import (  # noqa: E402
    pytest_configure,  # noqa: F401 — registers the task_sanitizer marker
    run_async_test,
)


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        # Lenient by default (log + cancel leaked tasks — what asyncio.run
        # already does); `@pytest.mark.task_sanitizer` or
        # LLMQ_TASK_SANITIZER=strict makes leaks fail the test.
        run_async_test(fn, kwargs, pyfuncitem)
        return True
    return None


@pytest.fixture()
def mem_ns(request):
    """A fresh, isolated memory-broker namespace per test."""
    ns = f"test-{request.node.name}-{id(request)}"
    yield ns
    memory_broker.reset_namespace(ns)


@pytest.fixture()
def mem_url(mem_ns):
    return f"memory://{mem_ns}"


@pytest.fixture()
def sample_job_dict():
    return {
        "id": "job-1",
        "prompt": "Translate {text} to {lang}",
        "text": "hello world",
        "lang": "Dutch",
    }
