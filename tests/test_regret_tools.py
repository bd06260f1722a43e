"""The tools PR 40's, PR 47's, PR 51's and PR 55's records rest on run end
to end off the chip: the latent kernel's layout check, the KDA kernel's
bench, the expert products' bench and the latent prefill attention's
bench (interpreted, tiny sizes), the served-regret
probe (the benchmark's CPU
rehearsal) and the lowering hashes. What they read on the chip is in PERF.md; here they only have to
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


def test_kda_kernel_bench_runs_interpreted():
    """``tools/decode_kernel_bench.py --case kda`` at a tiny shape: the XLA
    form, then the kernel and a plain copy at each heads a block; the
    kernel's outputs and new state are the XLA form's, and an interpreted
    run gives no time."""
    lines, _ = _run(
        ["tools/decode_kernel_bench.py", "--case", "kda", "--interpret", "--rows", "3",
         "--kda-layers", "2", "--heads", "4", "--heads-a-block", "1,4", "--iters", "1"],
        timeout=600,
    )
    assert [(l["kernel"], l["heads_a_block"]) for l in lines] == [
        ("xla", None), ("inplace", 1), ("copy", 1), ("inplace", 4), ("copy", 4),
    ]
    for l in lines:
        assert l["ms_per_step"] is None and (l["rows"], l["layers"], l["heads"]) == (3, 2, 4)
        if l["kernel"] == "inplace":
            assert l["max_abs_out_vs_xla"] < 1e-5 and l["max_abs_state_vs_xla"] < 1e-5


def test_expert_matmul_bench_runs_interpreted():
    """``tools/expert_matmul_bench.py`` at a tiny shape: the dense form,
    ``ragged_dot`` on a layer's own matrices and under a scan, then the
    kernel on the whole stack at its own tile and at another; the kernel's
    sum is ``ragged_dot``'s to bf16 rounding, and an interpreted run gives
    no time."""
    lines, _ = _run(
        ["tools/expert_matmul_bench.py", "--interpret", "--shapes", "tiny", "--rows", "300",
         "--tile-rows", "256", "--iters", "1"],
        timeout=600,
    )
    assert [(l["form"], l["tile_rows"]) for l in lines] == [
        ("dense", None), ("ragged", None), ("ragged_scan", None), ("stacked", 128),
        ("stacked", 256),
    ]
    for l in lines:
        assert l["ms_a_layer"] is None and (l["shape"], l["rows"]) == ("tiny", 300)
        assert 0 < l["assignments_here"] <= 600 and l["experts_hit"] == 4
        if l["form"] != "dense":
            assert l["max_diff_over_spread"] < 0.05


def test_mla_prefill_bench_runs_interpreted():
    """``tools/mla_prefill_bench.py`` at tiny shapes: the XLA form and the
    flash kernel at two pairs of blocks over a full and a ragged prompt,
    then a tiny preset's whole prefill under both settings of the
    threshold (its heads are no lane tiles: XLA's form twice); the two
    forms agree to bf16 rounding, and an interpreted run gives no time."""
    lines, _ = _run(
        ["tools/mla_prefill_bench.py", "--interpret", "--heads", "2", "--tokens", "96",
         "--blocks", "32x32,64x32", "--iters", "1", "--programs", "openpangu-ultra-moe-tiny:64",
         "--out", os.devnull],
        timeout=600,
    )
    full, ragged, program = lines
    for l, length in ((full, 96), (ragged, 72)):
        assert (l["line"], l["heads"], l["tokens"], l["length"], l["head_tokens"]) == (
            "attention", 2, 96, length, 192)
        assert l["xla_ms"] is None and l["flash_32x32_ms"] is None
        assert l["flash_32x32_max_diff"] < 0.05 and l["flash_64x32_max_diff"] < 0.05
    assert (program["line"], program["tokens"], program["xla_plan"], program["flash_plan"]) == (
        "program", 64, "xla", "xla")
    assert program["xla_ms"] is None and "saved_pct" not in program
    # what each plan's program took to trace and lower, and to compile
    assert all(program[f"{plan}_{what}_s"] > 0 for plan in ("xla", "flash") for what in ("trace", "compile"))


def test_kda_decode_ab_runs_on_the_cpu():
    """``tools/kda_decode_ab.py --tiny``: a ling-shaped decode step by the
    XLA form on a run, by the plan's form (the kernel, interpreted) and by
    the XLA form on an array of rows; the two XLA forms agree to the bit,
    the kernel to float32 rounding (the bit is the chip's to show)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT), "XLA_FLAGS": "",
           "LLMQ_ATTN_BACKEND": "pallas"}
    proc = subprocess.run(
        [sys.executable, "tools/kda_decode_ab.py", "--tiny"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines() if l.startswith("{")]
    forms = [l for l in lines if "form" in l]
    assert [(l["mask"], l["form"], l["plan"]) for l in forms] == [
        (mask, form, plan) for mask in ("all", "some")
        for form, plan in (("xla_run", "xla"), ("plan", "inplace"), ("xla_array", "xla"))
    ]
    for l in forms:
        assert l["logits_max_abs"] < 1e-4
        if l["form"] != "plan":
            assert l["logits_max_abs"] == 0.0 and not any(l["state_values_that_differ_a_layer"])
    assert lines[-1]["line"] == "summary"


def test_lowering_hash_lists_every_step_program():
    _, out = _run(["tools/lowering_hash.py"], timeout=900)
    rows = [l.split() for l in out.splitlines() if l.strip()]
    # seven presets x two backends x (decode, 1 x 512, 4 x 2,048), and for
    # the two patterns with latent layers every other (rows, bucket) their
    # cells warm: ling 6 a backend, openpangu 5
    assert len(rows) == 42 + 2 * (6 + 5) and len({tuple(r[:3]) for r in rows}) == len(rows)
    assert all(len(r[3]) == 16 for r in rows)
    ling, pangu = "ling-3.0-flash-ep4", "openpangu-ultra-moe-718b-ep16"
    programs = lambda preset, backend="pallas": [
        r[2] for r in rows if r[0] == preset and r[1] == backend
    ]
    assert programs(ling)[:3] == programs(pangu)[:3] == ["decode", "prefill_1x512", "prefill_4x2048"]
    assert programs(ling)[3:] == [
        f"prefill_{b}x{t}" for t in (256, 512, 1024, 2048) for b in (1, 4)
        if (b, t) not in ((1, 512), (4, 2048))
    ]
    assert programs(pangu)[3:] == [
        f"prefill_{b}x{t}" for t in (1024, 2048, 4096) for b in (1, 4) if (b, t) != (4, 2048)
    ]
    assert programs(ling, "xla") == programs(ling) and programs(pangu, "xla") == programs(pangu)
    marked = lambda kernel: [tuple(r[:3]) for r in rows if kernel in r[5:]]
    # the one-pass KDA state update is in ling's pallas decode step alone
    assert marked("kda_step_inplace") == [(ling, "pallas", "decode")]
    assert [r[5:] for r in rows if tuple(r[:3]) == (ling, "pallas", "decode")] == [["kda_step_inplace"]]
    # the grouped matmul on a group's stack is in every pallas prefill of
    # the four patterns with routed experts but ling's 1 x 256 (as many
    # rows as the dense form takes), and in no decode step
    assert marked("grouped_matmul_stacked") == [
        (r[0], "pallas", r[2]) for r in rows
        if r[0] in (ling, pangu, "lfm2-24b-a2b-pp5", "laguna-s-2.1-ep4")
        and r[1] == "pallas" and r[2] not in ("decode", "prefill_1x256")
    ]
    # the flash prefill of expanded latent attention is in openpangu's
    # pallas prefills from 1,024 positions (128 heads x 1,024 = 2**17), in
    # none of the shapes ling's cell warms, and in no other line
    assert marked("mla_flash_prefill_attention") == [
        (pangu, "pallas", p) for p in programs(pangu) if p not in ("decode", "prefill_1x512")
    ]
    assert sum(len(r) == 5 for r in rows) == len(rows) - len(marked("grouped_matmul_stacked")) - 1


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
