"""CPU pre-flight for the hardware-session runbook.

``tools/hardware_session.sh`` exists to be fired on a host with the chip
attached; a typo'd path, flag, or env var in it burns scarce chip
minutes before anyone notices (the round-5 session lost its window
exactly this way). This module parses the script, extracts every
``run <timeout> <name> <cmd...>`` ladder step plus the probe commands,
and executes each one on CPU with tiny shape overrides — proving the
whole ladder is runnable end to end before chip time is spent.

Fast tier (always on): the parser finds the expected steps, every
referenced script/module exists, and the cheap commands (probes, one
bench) actually run. The heavyweight commands
(every bench variant, the profilers, the queue-drain harness) are
``slow``-marked and run in CI's full pass.
"""

from __future__ import annotations

import json
import os
import re
import shlex
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# Overrides applied ON TOP of each step's own env: force CPU, shrink
# every shape knob, and cap runtimes. A step's model/slot choices
# (9B preset, 224 slots, ...) are deliberately clobbered — off-TPU the
# only question is "does the command run", not "what does it measure".
TINY_ENV = {
    "JAX_PLATFORMS": "cpu",
    "LLMQ_BENCH_PRESET": "tiny",
    "LLMQ_BENCH_REQUESTS": "3",
    "LLMQ_BENCH_PROMPT": "8",
    "LLMQ_BENCH_GEN": "6",
    "LLMQ_BENCH_SEQS": "2",
    "LLMQ_BENCH_TRY_QUANT": "0",
    "LLMQ_BENCH_PREFILL_CHUNK": "4",
    "LLMQ_BENCH_DEADLINE": "240",
    "PROF_S": "4",
    "PROF_H": "8",
    "PROF_I": "16",
    "PROF_L": "2",
}

# argv rewrites for performance_benchmark.py-style flagged commands:
# value following the flag is replaced.
TINY_FLAGS = {
    "--samples": "3",
    "--batch-sizes": "2",
    "--max-tokens": "8",
    "--max-model-len": "64",
}


def _joined_lines(text: str):
    """Script lines with backslash continuations folded in."""
    out, acc = [], ""
    for line in text.splitlines():
        if line.rstrip().endswith("\\"):
            acc += line.rstrip()[:-1] + " "
            continue
        out.append(acc + line)
        acc = ""
    if acc:
        out.append(acc)
    return out


def parse_ladder(script: Path):
    """Extract (name, env, argv) for every python command the runbook
    executes: ``run <timeout> <name> [env K=V...] python ...`` steps and
    the inline ``python -c`` probes."""
    steps = []
    probe_n = 0
    for line in _joined_lines(script.read_text()):
        line = line.strip()
        m = re.match(r"run\s+\d+\s+(\S+)\s+(.*)$", line)
        if m:
            name, rest = m.group(1), m.group(2)
        elif re.match(r"(timeout\s+\d+\s+)?python(3?)\s+-c\s", line):
            probe_n += 1
            name, rest = f"probe{probe_n}", line
        else:
            continue
        argv = shlex.split(rest)
        env = {}
        if argv and argv[0] == "timeout":
            argv = argv[2:]
        if argv and argv[0] == "env":
            argv = argv[1:]
            while argv and "=" in argv[0] and not argv[0].startswith("-"):
                key, _, val = argv[0].partition("=")
                env[key] = val
                argv = argv[1:]
        if not argv or not argv[0].startswith("python"):
            continue
        steps.append((f"{script.stem}:{name}", env, argv))
    return steps


def _tiny_step(env, argv):
    """The (env, argv) a step actually runs with in pre-flight mode."""
    env = {**env, **TINY_ENV}
    argv = list(argv)
    for i, tok in enumerate(argv):
        if tok.startswith("preset://"):
            argv[i] = "preset://tiny"
        if tok in TINY_FLAGS and i + 1 < len(argv):
            argv[i + 1] = TINY_FLAGS[tok]
        if tok == "--output" and i + 1 < len(argv):
            argv[i + 1] = "/tmp/preflight_" + Path(argv[i + 1]).name
    return env, argv


def all_steps():
    return parse_ladder(REPO / "tools" / "hardware_session.sh")


def unique_tiny_steps():
    """De-duplicate steps that collapse to the same command once tiny
    overrides clobber their preset/slot env (e.g. the 3B and 9B int8
    benches both become `int8 x tiny`)."""
    seen, out = set(), []
    for name, env, argv in all_steps():
        env, argv = _tiny_step(env, argv)
        key = (tuple(argv), tuple(sorted(env.items())))
        if key in seen:
            continue
        seen.add(key)
        out.append((name, env, argv))
    return out


def _run(env, argv, timeout=400):
    full_env = {**os.environ, "PYTHONPATH": str(REPO), "HOME": "/tmp", **env}
    if argv[0].startswith("python"):
        argv = [sys.executable] + argv[1:]
    return subprocess.run(
        argv, cwd=REPO, env=full_env, capture_output=True, text=True,
        timeout=timeout,
    )


def _assert_ran(name, proc, *, allow_fail=False):
    blob = proc.stdout + proc.stderr
    for marker in (
        "ModuleNotFoundError", "ImportError", "SyntaxError",
        "NameError", "FileNotFoundError", "usage:",
    ):
        assert marker not in blob, f"{name}: {marker} in output:\n{blob[-2000:]}"
    if not allow_fail:
        assert proc.returncode == 0, f"{name}: rc={proc.returncode}\n{blob[-2000:]}"


def _is_probe(name):
    return ":probe" in name


def test_ladders_parse():
    """The runbook yields its full command ladder (a parser that
    silently matches nothing would make every other test vacuous). The
    count covers the steps that came over from the deleted chip poller:
    the driver-style and pinned benches, the fp8-KV and Pallas-int8
    variants, and the queue-drain harness."""
    names = [name for name, _, _ in all_steps()]
    assert sum(n.startswith("hardware_session") for n in names) >= 29
    joined = " ".join(names)
    assert "int4_kernel" in joined and "queue_drain_tpu" in joined
    assert "int8_fusion" in joined and "bench_driver_style" in joined
    assert "metrics_probe" in joined
    assert "fleet_chaos_probe" in joined
    assert "engine_fault_probe" in joined
    assert "integrity_probe" in joined
    assert "sim_probe" in joined
    assert "shardcheck_probe" in joined
    assert "disagg_probe" in joined
    assert "pp_probe" in joined
    assert "serve_probe" in joined


def test_referenced_files_exist():
    """Every script path / -m module named by a ladder step exists."""
    for name, _, argv in all_steps():
        it = iter(argv[1:])
        for tok in it:
            if tok == "-c":
                break
            if tok == "-m":
                mod = next(it)
                path = REPO / (mod.replace(".", "/") + ".py")
                assert path.exists(), f"{name}: module {mod} missing"
                break
            if not tok.startswith("-"):
                assert (REPO / tok).exists(), f"{name}: script {tok} missing"
                break


def test_probes_and_autotune_run():
    """The cheap ladder steps execute on CPU: the device probe, and any
    kernel-autotune child the runbook scripts (a tp-overlap or
    int4-matmul leg would land here; today it scripts none)."""
    ran = 0
    for name, env, argv in unique_tiny_steps():
        if _is_probe(name) or "llmq_tpu.engine.kernel_autotune" in argv:
            proc = _run(env, argv, timeout=240)
            _assert_ran(name, proc, allow_fail=_is_probe(name))
            ran += 1
    assert ran >= 1


def test_bench_tiny_decode_block_runs():
    """One representative bench command runs end to end on CPU with the
    fused decode-block path enabled (K=2), emitting the metric line."""
    proc = _run(
        {**TINY_ENV, "LLMQ_BENCH_DECODE_BLOCK": "2"},
        ["python", "bench.py"],
        timeout=400,
    )
    _assert_ran("bench:tiny", proc)
    assert '"metric"' in proc.stdout
    assert '"decode_block": 2' in proc.stdout


def test_bench_tiny_spec_runs():
    """One representative bench command runs end to end on CPU with
    lossless speculative decoding pinned on (2 draft tokens), and the
    metric line reports both the draft length and the measured
    acceptance rate."""
    proc = _run(
        {**TINY_ENV, "LLMQ_BENCH_SPEC_TOKENS": "2"},
        ["python", "bench.py"],
        timeout=400,
    )
    _assert_ran("bench:tiny-spec", proc)
    assert '"metric"' in proc.stdout
    assert '"spec_tokens": 2' in proc.stdout
    assert '"acceptance_rate"' in proc.stdout


def test_bench_tiny_mixed_step_runs():
    """One representative bench command runs end to end on CPU with the
    piggyback mixed-step dispatch pinned on; the metric line reports the
    mode plus nonzero fused-dispatch counters (a mixed run that never
    piggybacked a prefill token silently fell back to the split path)."""
    proc = _run(
        {
            **TINY_ENV,
            "LLMQ_MIXED_STEP": "on",
            "LLMQ_BENCH_PREFILL_CHUNK": "4",
        },
        ["python", "bench.py"],
        timeout=400,
    )
    _assert_ran("bench:tiny-mixed", proc)
    assert '"metric"' in proc.stdout
    payload = json.loads(
        [ln for ln in proc.stdout.splitlines() if ln.startswith("{")][-1]
    )
    assert payload["mixed_step"] == "on"
    assert payload["mixed_steps"] > 0
    assert payload["mixed_prefill_tokens"] > 0


def test_metrics_probe_runs():
    """The observability rung runs end to end on CPU: the probe builds a
    tiny engine, starts the exporter on an ephemeral port, scrapes its
    own /metrics (validating the Prometheus text format and the core
    series), and round-trips a traced job through a memory broker."""
    proc = _run(
        {**TINY_ENV, "LLMQ_METRICS_PORT": "0"},
        ["python", "tools/metrics_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:metrics_probe", proc)
    assert "scrape leg ok" in proc.stdout
    assert "trace leg ok" in proc.stdout
    assert "metric: obs_probe_ok" in proc.stdout


def test_snapshot_probe_runs():
    """The durable-state rung runs end to end on CPU: snapshot
    extract→b64→insert with a bit-identical continuation, swap-preempt
    parity with recompute under a tight pool, and a seeded kill-resume
    mini-chaos on the memory broker with exactly one result per job."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/snapshot_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:snapshot_probe", proc)
    assert "roundtrip leg ok" in proc.stdout
    assert "swap leg ok" in proc.stdout
    assert "kill-resume leg ok" in proc.stdout
    assert "metric: snapshot_probe_ok" in proc.stdout


def test_prefix_cache_probe_runs():
    """The fleet prefix-cache rung runs end to end on CPU: intra-engine
    reuse with cache-free parity, host-tier demote→promote with
    cold-prefill parity, and a two-worker page ship over the memory
    broker with cross-worker token parity."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/prefix_cache_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:prefix_cache_probe", proc)
    assert "reuse leg ok" in proc.stdout
    assert "host-tier leg ok" in proc.stdout
    assert "ship leg ok" in proc.stdout
    assert "metric: prefix_cache_probe_ok" in proc.stdout


def test_fleet_chaos_probe_runs():
    """The fleet self-healing rung runs end to end on CPU: orphaned
    affinity queues reclaimed exactly once, an unmeetable deadline shed
    at submit as an explicit dead-letter, and the host-memory governor's
    degradation ladder engaging its rungs in order."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/fleet_chaos_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:fleet_chaos_probe", proc)
    assert "reclaim leg ok" in proc.stdout
    assert "shed leg ok" in proc.stdout
    assert "governor leg ok" in proc.stdout
    assert "metric: fleet_chaos_probe_ok" in proc.stdout


def test_engine_fault_probe_runs():
    """The device-fault containment rung runs end to end on CPU: a
    wedged dispatch trips the watchdog and rebuilds the engine
    in-process with token parity, the HBM-OOM ladder absorbs a first
    fault without a rebuild (and degrades in order when driven dry),
    and a classified XLA error recovers every request from snapshots."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/engine_fault_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:engine_fault_probe", proc)
    assert "hang leg ok" in proc.stdout
    assert "oom-ladder leg ok" in proc.stdout
    assert "xla-error leg ok" in proc.stdout
    assert "metric: engine_fault_probe_ok" in proc.stdout


def test_integrity_probe_runs():
    """The silent-data-corruption rung runs end to end on CPU: a NaN
    logit flip trips the on-device guard and recovers with token
    parity, a finite weight flip is named by the digest audit while
    the KV spot-check stays clean, and the golden-prompt canary passes
    clean then catches a corrupted replay."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/integrity_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:integrity_probe", proc)
    assert "guard-trip leg ok" in proc.stdout
    assert "weight-audit leg ok" in proc.stdout
    assert "canary leg ok" in proc.stdout
    assert "metric: integrity_probe_ok" in proc.stdout


@pytest.mark.slow
def test_disagg_probe_runs():
    """The disaggregated-serving rung runs end to end on CPU: prompt KV
    ships over the adoption handshake with unified-fleet token parity,
    the same jobs take the snapshot fallback with parity when no decode
    peer is alive, and the auto-role controller flips
    prefill→decode→prefill under synthetic depth skew."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/disagg_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:disagg_probe", proc)
    assert "handoff leg ok" in proc.stdout
    assert "fallback leg ok" in proc.stdout
    assert "autoswitch leg ok" in proc.stdout
    assert "metric: disagg_probe_ok" in proc.stdout


def test_sim_probe_runs():
    """The fleet-twin rung runs end to end on CPU: a seeded fault-heavy
    scenario completes with every invariant holding, a rerun is
    event-identical (replay digest), and one policy regression passes
    its recorded baseline while its documented detune breaks it."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/sim_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:sim_probe", proc)
    assert "invariants leg ok" in proc.stdout
    assert "replay leg ok" in proc.stdout
    assert "regression leg ok" in proc.stdout
    assert "metric: sim_probe_ok" in proc.stdout


@pytest.mark.slow
def test_bench_tiny_pp_rung_runs():
    """The bench's pipeline-parallel rung runs on 2 CPU devices and the
    metric line carries the staged-engine diagnostics: stage count,
    GPipe bubble fraction, and stage-boundary activation bytes/token.
    The deadline is lifted (TINY_ENV's 240 s budget trims the pp rung
    first by design) and the other diagnostic rungs are opted out to
    keep the run cheap."""
    proc = _run(
        {
            **TINY_ENV,
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "LLMQ_BENCH_DEADLINE": "100000",
            "LLMQ_BENCH_TRY_PREFIX": "0",
            "LLMQ_BENCH_TRY_DISAGG": "0",
        },
        ["python", "bench.py"],
        timeout=580,
    )
    _assert_ran("bench:tiny-pp", proc)
    assert '"pp_stages": 2' in proc.stdout
    assert '"pp_vs_unified"' in proc.stdout
    assert '"pp_bubble_fraction"' in proc.stdout
    assert '"pp_boundary_bytes_per_token"' in proc.stdout


@pytest.mark.slow
def test_pp_probe_runs():
    """The pipeline-parallel rung runs end to end on CPU (8 virtual
    devices): pp=2 staged-engine token parity on every row, the two-tier
    pp-outer x tp-inner mesh, and the stage-boundary wire-codec leg."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/pp_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:pp_probe", proc)
    assert "parity leg ok" in proc.stdout
    assert "two-tier leg ok" in proc.stdout
    assert "wire leg ok" in proc.stdout
    assert "metric: pp_probe_ok legs=3" in proc.stdout


@pytest.mark.slow
def test_shardcheck_probe_runs():
    """The sharding-analysis rung runs end to end on CPU: the AST sweep
    is clean, the lowered-HLO gate's engine-step signatures on the probe
    mesh match the committed baseline, and the MoE token-pin detune
    fails the gate naming the program/mesh and nearest op."""
    proc = _run(
        {**TINY_ENV},
        ["python", "tools/shardcheck_probe.py"],
        timeout=400,
    )
    _assert_ran("tools:shardcheck_probe", proc)
    assert "ast leg ok" in proc.stdout
    assert "spmd-diff leg ok" in proc.stdout
    assert "detune leg ok" in proc.stdout
    assert "metric: shardcheck_probe_ok" in proc.stdout


@pytest.mark.slow
def test_spmd_gate_record_and_diff_legs(tmp_path):
    """The gate's record/diff cycle works against a scratch baseline on
    a subset mesh/program (CPU, 8 virtual devices): record writes the
    signature file, an immediate diff against it is clean."""
    env = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "LLMQ_SPMD_MESHES": "2x2x2",
        "LLMQ_SPMD_PROGRAMS": "prefill1",
        "LLMQ_SPMD_BASELINE": str(tmp_path / "baseline.json"),
    }
    rec = _run(env, ["python", "-m", "llmq_tpu.analysis.spmd", "--record"],
               timeout=400)
    _assert_ran("spmd:record", rec)
    assert (tmp_path / "baseline.json").exists()
    diff = _run(env, ["python", "-m", "llmq_tpu.analysis.spmd"], timeout=400)
    _assert_ran("spmd:diff", diff)
    assert "spmd: clean" in diff.stdout


def test_bench_tiny_int4_runs():
    """One representative bench command runs end to end on CPU with the
    int4 group-quantized weight ladder, emitting the metric line with
    the dtype recorded."""
    proc = _run(
        {**TINY_ENV, "LLMQ_BENCH_DTYPE": "int4"},
        ["python", "bench.py"],
        timeout=400,
    )
    _assert_ran("bench:tiny-int4", proc)
    assert '"metric"' in proc.stdout
    assert '"dtype": "int4"' in proc.stdout


@pytest.mark.slow
@pytest.mark.parametrize(
    "name,env,argv",
    [pytest.param(*step, id=step[0]) for step in unique_tiny_steps()],
)
def test_every_ladder_command_runs_tiny(name, env, argv):
    """The full pre-flight: EVERY de-duplicated runbook command executes
    on CPU in tiny mode. Catches rotted flags, renamed scripts, and env
    knobs the tools no longer accept — before a chip is rented."""
    proc = _run(env, argv, timeout=500)
    _assert_ran(name, proc, allow_fail=_is_probe(name))


@pytest.mark.slow
def test_bench_command_count_not_shrunk():
    """The tiny-mode dedup still leaves a spread of bench variants
    (int8, fp8 KV, pallas matmul, auto-layout must stay distinguishable
    — they differ in env that tiny mode does NOT clobber)."""
    benches = [
        tuple(sorted(env.items()))
        for _, env, argv in unique_tiny_steps()
        if argv[-1].endswith("bench.py")
    ]
    assert len(set(benches)) >= 5
