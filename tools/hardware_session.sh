#!/usr/bin/env bash
# The hardware measurement ladder in one command, for a host that has
# the chip attached (JAX_PLATFORMS unset; JAX takes the TPU by itself).
# Every step is its own process and owns the chip from start to exit —
# one process per chip, so run nothing else that needs it meanwhile.
# Results land under PERF_RESULTS/:
#
#   1. int8 matmul fusion check (decides whether int8 helps DECODE)
#   2. the per-plane probes (snapshot, prefix, disagg, faults, ...)
#   3. headline bench, bf16 (224->192 slot ladder built in),
#      driver-style and pinned variants
#   4. int8 / fp8-KV / int4 benches at 3B, int8 9B on ONE 16 GB chip
#   5. param auto-layout, speculative decoding, mixed-step A/Bs
#   6. the queue-drain harness (broker -> worker -> results) at 3B
#
# The decode-attention schedules are timed through the chip tool
# (tools/decode_kernel_bench.py), not here.
#
# The quickest proof that the system starts on the chip at all is
# `python chip_smoke.py` at the repo root; run that first.
#
# Each step has its own timeout so one hang doesn't eat the session.
set -u
cd "$(dirname "$0")/.."
# tools/*.py insert the repo root themselves, but belt-and-braces for
# anything invoked as a bare module path (python -m ...).
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
OUT=PERF_RESULTS
mkdir -p "$OUT"
run() {  # run <timeout-s> <name> <cmd...>
    local t="$1" name="$2"; shift 2
    echo "=== $name ($(date +%H:%M:%S))"
    timeout "$t" "$@" > "$OUT/$name.log" 2>&1
    echo "    rc=$? -> $OUT/$name.log"
    tail -3 "$OUT/$name.log" | sed 's/^/    /'
}

run 60  probe         python -c "import jax; d=jax.devices(); print(len(d), d[0].platform, d[0].device_kind)"
grep -q tpu "$OUT/probe.log" || { echo "no TPU on this host; aborting"; exit 1; }

run 300 int8_fusion   python tools/profile_int8_matmul.py
# ICI microbench: decides whether the tp-overlap ring matmuls pay on
# this slice (single-chip sessions exit immediately with a note).
run 300 collectives   python tools/profile_collectives.py
# Observability plane: /metrics scrape + trace round trip on the real
# device (host-side only, so cheap; ephemeral port avoids collisions).
run 900 metrics_probe env LLMQ_METRICS_PORT=0 python tools/metrics_probe.py
# NB: `VAR=x run ...` would leak past the function call in bash — use
# `env` so each override dies with its step.
# Durable-state plane: snapshot round trip, swap-vs-recompute parity,
# and a seeded kill-resume mini-chaos on the memory broker — proves
# crash-resume holds with device-resident KV, not just on CPU.
run 900 snapshot_probe python tools/snapshot_probe.py
# Disaggregated prefill/decode plane: ship-path KV adoption parity,
# snapshot-fallback parity, and the auto-role depth controller — the
# phase-boundary handoff runs with device-resident prompt KV here.
run 900 disagg_probe python tools/disagg_probe.py
# Fleet-wide prefix-cache plane: intra-engine reuse parity, host-tier
# demote->promote parity, and a two-worker page ship over the memory
# broker — proves the KV gather/scatter paths on the real chip, not
# just CPU.
run 900 prefix_probe python tools/prefix_cache_probe.py
# Fleet self-healing plane: affinity-orphan reclaim exactly-once,
# deadline admission shedding, and the host-memory degradation ladder
# (broker + host-side bookkeeping; cheap, keeps the robustness plane
# honest on the same image the benches run on).
run 900 fleet_chaos_probe python tools/fleet_chaos_probe.py
# Device-fault containment: watchdog hang detection + in-process engine
# rebuild, the HBM-OOM degradation ladder, and classified XLA errors —
# each with token parity against a fault-free run (the dispatch hooks
# run against the real chip here).
run 900 engine_fault_probe python tools/engine_fault_probe.py
# Silent-data-corruption defense: logit-guard trip -> numerical_fault
# rebuild with parity, weight-digest audit naming a flipped shard, and
# the golden-prompt canary round trip — the value-level checks the
# crash-shaped probes above can't see.
run 900 integrity_probe python tools/integrity_probe.py
# Fleet-twin simulation plane: seeded fault-heavy scenario with
# invariants proven, replay determinism, and a policy-regression
# baseline + detune-teeth check (virtual clock, host-side only; keeps
# the policy planes the probes above exercise pinned to their recorded
# baselines on this image).
run 900 sim_probe env JAX_PLATFORMS=cpu python tools/sim_probe.py
# Online-serving plane: gateway SSE round-trip parity over the memory
# broker, interactive-preempts-batch token parity vs a priority-off
# golden run, and cancel-frees-pages — the SLO scheduling path the
# serve bench rung measures (engine legs run on the chip here).
run 900 serve_probe python tools/serve_probe.py
# Sharding-analysis plane: AST sweep + lowered-HLO collective-signature
# diff vs the committed baseline + MoE token-pin detune teeth (runs its
# jax legs in CPU subprocesses; never touches the accelerator).
run 900 shardcheck_probe env JAX_PLATFORMS=cpu python tools/shardcheck_probe.py
# Pipeline-parallel plane: pp=2 staged-engine token parity, the two-tier
# pp-outer x tp-inner mesh, and the stage-boundary wire codec — on the
# real ICI/DCN domains here (single-chip sessions note-and-skip).
run 900 pp_probe python tools/pp_probe.py
# Driver-style run: quant-first attempt + canary, exactly what an
# end-of-round BENCH executes; then the bf16 headline alone and the
# slot-count question (192 vs 224 at the same kernel).
run 3900 bench_driver_style python bench.py
run 1800 bench_bf16   env LLMQ_BENCH_TRY_QUANT=0 python bench.py
run 1200 bench_s192 env LLMQ_BENCH_TRY_QUANT=0 LLMQ_BENCH_SEQS=192 python bench.py
# int8 3B — decode is weight-bound at 3B, KV fits, and prefill
# (compute-bound) is unchanged; then with the Pallas dequant matmul (the
# fusion check said XLA does NOT fuse the convert; this is the
# guaranteed path).
run 1800 bench_int8_3b env LLMQ_BENCH_DTYPE=int8 LLMQ_BENCH_PRESET=qwen2.5-3b python bench.py
run 1800 bench_int8_3b_pallas env LLMQ_BENCH_DTYPE=int8 LLMQ_BENCH_PRESET=qwen2.5-3b LLMQ_INT8_MATMUL=pallas python bench.py
# fp8 KV cache at 3B, alone and with int8 weights.
run 1800 bench_fp8kv_3b env LLMQ_BENCH_KV_DTYPE=fp8 LLMQ_BENCH_PRESET=qwen2.5-3b python bench.py
run 1800 bench_int8_fp8kv_3b env LLMQ_BENCH_DTYPE=int8 LLMQ_BENCH_KV_DTYPE=fp8 LLMQ_BENCH_PRESET=qwen2.5-3b python bench.py
# int8 9B north star (chunked init): measurable on one chip. Slots
# capped to what the KV pool can hold (~5 GB after 9.4 GB int8 weights);
# fp8 KV doubles that, so the fp8 variant gets more slots.
run 1800 bench_int8_9b env LLMQ_BENCH_DTYPE=int8 LLMQ_BENCH_PRESET=tower-plus-9b LLMQ_BENCH_SEQS=48 python bench.py
run 1800 bench_int8_fp8kv_9b env LLMQ_BENCH_DTYPE=int8 LLMQ_BENCH_KV_DTYPE=fp8 LLMQ_BENCH_PRESET=tower-plus-9b LLMQ_BENCH_SEQS=96 python bench.py
run 1800 bench_autolayout env LLMQ_PARAM_AUTO_LAYOUT=1 python bench.py
run 1800 bench_spec3   env LLMQ_BENCH_TRY_QUANT=0 \
    LLMQ_BENCH_SPEC_TOKENS=3 python bench.py
# int4 ladder: quarter weight bytes; kernel A/B first (XLA dequant vs
# the dequant-in-VMEM Pallas kernel at the decode MLP shape), then the
# headline — int4's fidelity cost means only a clear tok/s win counts.
run 600  int4_kernel   python tools/profile_kernel_v2.py --int4
run 1800 bench_int4_3b env LLMQ_BENCH_DTYPE=int4 python bench.py
# piggyback mixed dispatch: prefill chunks ride the decode step's idle
# MXU (PERF_NOTES round 9) — compare against bench_bf16's wall split.
run 1800 bench_mixed   env LLMQ_BENCH_TRY_QUANT=0 LLMQ_MIXED_STEP=on \
    LLMQ_BENCH_PREFILL_CHUNK=256 python bench.py
# Queue-drain artifact on the real engine: the end-to-end
# broker->worker->results harness at a TPU preset.
run 1800 queue_drain_tpu python performance_benchmark.py \
    --model preset://qwen2.5-3b --samples 192 --batch-sizes 64 \
    --max-tokens 64 --output benchmarks/queue_drain_tpu_3b.json

echo "=== summary"
grep -h '"metric"' "$OUT"/bench_*.log 2>/dev/null
echo "Next: compare bench_autolayout vs bench_bf16; if auto-layout holds,"
echo "compare bench_spec3 vs bench_bf16 and record the acceptance rate;"
echo "default LLMQ_PARAM_AUTO_LAYOUT=1 on TPU in engine.py; record the"
echo "best line in PERF_NOTES."
