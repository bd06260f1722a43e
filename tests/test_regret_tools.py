"""The three tools PR 40's records rest on run end to end off the chip:
the latent kernel's layout check (interpreted, tiny sizes), the
served-regret probe (the benchmark's CPU rehearsal) and the lowering
hashes. What they read on the chip is in PERF.md; here they only have to
keep working."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _run(args, timeout):
    # One device, as on the chip the tools are for: the test session's
    # XLA_FLAGS asks for eight.
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT), "XLA_FLAGS": ""}
    proc = subprocess.run(
        [sys.executable, *args], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")], proc.stdout


def test_latent_kernel_layout_check_runs_interpreted():
    lines, _ = _run(
        ["tools/latent_kernel_layout_check.py", "--interpret", "--rows", "6", "--trials", "1",
         "--pool-pages", "80", "--places", "10", "--heads", "32"],
        timeout=600,
    )
    trial, summary = lines
    assert summary == {"line": "summary", "trials": 1, "kernel_rows_that_differed": 0,
                       "device": summary["device"]}
    for layer in ("layer0", "layer4"):
        assert trial[layer]["kernel_rows_differ_between_layouts"] == 0
        assert trial[layer]["xla_rows_differ_between_layouts"] == 0
        assert trial[layer]["kernel_vs_xla_max_abs"] < 2e-2 and not trial[layer]["nan"]


def test_served_regret_probe_rehearses_on_the_cpu():
    lines, _ = _run(
        ["tools/served_regret_probe.py", "--workload",
         "openpangu-ultra-moe-718b-ep16.decode-latent", "--seeds", "7", "--rehearse-cpu",
         "--bisect", "always"],
        timeout=900,
    )
    by = {}
    for l in lines:
        by.setdefault(l["line"], []).append(l)
    assert by["check"][0]["correct"] and not by["check"][0]["missed"]
    assert by["repeat"][0]["changed"] == {}
    same = by["same_inputs"][0]
    assert same["programs_differ"] == {} and same["served_equals_engine_program"] == same["positions"]
    # one executable, whatever the pool, the rows or the neighbours
    assert {b["variant"] for b in by["bisect"]} >= {"as_check", "no_samples", "rows_moved", "engine_pool"}
    assert all(b["largest_abs_diff"] == 0.0 for b in by["bisect"])


def test_lowering_hash_lists_every_step_program():
    _, out = _run(["tools/lowering_hash.py"], timeout=600)
    rows = [l.split() for l in out.splitlines() if l.strip()]
    assert len(rows) == 24 and len({tuple(r[:3]) for r in rows}) == 24
    assert all(len(r[3]) == 16 for r in rows)


def test_lowering_hash_for_a_described_v5e_holds_the_mosaic_kernels():
    """``--v5e``: the 3B's three programs with their Mosaic kernels (the K/V
    live decode kernel, the prefill kernel) and the 7B's over four chips,
    which take the XLA attention; skipped where libtpu cannot describe the
    chip, as ``tests/test_tpu_compile.py`` is. The child describes it, not
    this process: libtpu is one process's at a time."""
    import pytest

    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT), "XLA_FLAGS": "",
           "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}
    proc = subprocess.run(
        [sys.executable, "tools/lowering_hash.py", "--v5e"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    if proc.returncode and "cannot describe a v5e:2x2 topology" in proc.stderr:
        pytest.skip(proc.stderr.strip().splitlines()[-1][:300])
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [l.split() for l in proc.stdout.splitlines() if l.startswith("qwen")]
    assert [tuple(r[:3]) for r in rows] == [
        (preset, mesh, program)
        for preset, mesh in (("qwen2.5-3b", "v5e-tp1"), ("qwen2.5-7b", "v5e-tp4"))
        for program in ("decode", "prefill_1x512", "prefill_4x2048")
    ]
    assert [r[5] for r in rows] == ["mosaic"] * 3 + ["no-mosaic"] * 3
    assert all(len(r[3]) == 16 for r in rows)
