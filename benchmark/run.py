#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name from ``BENCHMARK.json``: its
configuration (``benchmark/configs/<config>.json``), that configuration's
architecture (``benchmark/architectures/<architecture>.py``: weight tree and
plain reference) and limits (``benchmark/limits/<config>.json``, else
``benchmark/limits.json``), its traffic
(``benchmark/traffic/<traffic>.json``) and the per-layer metrics that list
it (``benchmark/layer_metrics/<metric>.json`` naming a reader under
``benchmark/readers/``). See ``benchmark/README.md``.

The last line of standard output is the result; earlier lines say where
the set-up time went, what the traffic was, every percentile with its
sample count, how late the generator ran, and each number of the
correctness comparison beside its limit (those are also the last lines of
standard error). Logs go to standard error.
Without a TPU holding the chips the cell asks for, the command exits
non-zero and prints no result (``--rehearse-cpu`` with ``JAX_PLATFORMS=cpu``
is the one switch; see ``rehearsal.json``).
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))


def say(line: str, **facts) -> None:
    """An earlier line: one JSON object, never the last line."""
    print(json.dumps({"line": line, **facts}, default=float), flush=True)


from benchmark.run_helpers import (  # noqa: E402
    HERE, apply_rehearsal, device_facts, load_cell, memory_peak, warm_shapes,
)


def read_layer_metrics(cell, ctx) -> dict:
    out = {}
    for metric in cell.per_layer:
        spec = json.loads((HERE / "layer_metrics" / f"{metric['name']}.json").read_text())
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


async def run(args, cell, traffic, device) -> tuple:
    from benchmark import correct, loadgen, metrics, schedule, trace_reduce
    from benchmark.kernel_cost import peaks_for
    from benchmark.system import System
    from llmq_tpu.utils.platform import CompileMeter

    marks = {"process": T_PROCESS, "imports": time.monotonic()}
    meter = CompileMeter()
    peaks = None if args.rehearse_cpu else peaks_for(device["kind"])
    system = System(cell.config, cell.name)
    await system.start()
    marks["engine_ready"] = time.monotonic()
    compile_ready = meter.snapshot()
    await asyncio.to_thread(system.serve_weights_from_seed, args.seed)
    marks["weights"] = time.monotonic()

    requests = schedule.make_schedule(traffic, args.seconds)
    say("traffic", generator=traffic["generator"], **schedule.describe(requests))
    warmed = await warm_shapes(system, requests)
    marks["shapes_warm"] = time.monotonic()
    say("warmed", **warmed)

    checked = await correct.check_cell(system, cell.config, traffic["check_lengths"], args.seed)
    limits, limits_file = correct.load_limits(cell.config_name)
    check = correct.verdict(checked, limits)
    del checked  # the logits it keeps for calibrate.py
    marks["reference"] = time.monotonic()
    say("correct", limits_file=limits_file, **check)
    system.timings.clear()
    first_dispatch = len(system.prefill_log)

    trace_dir = None
    if args.trace and not args.rehearse_cpu:
        trace_dir = str(ROOT / "chiprun_out" / "trace" / f"{cell.name}-{args.seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = loadgen.Tracer(trace_dir, float(traffic.get("trace_seconds", 4)), system)
    drive = await loadgen.GENERATORS[traffic["generator"]](
        system, traffic, requests, args.seed, args.seconds, meter, tracer
    )
    t0, t1 = drive.window
    setup_s = t0 - T_PROCESS
    say(
        "setup",
        setup_s=setup_s,
        imports_and_tpu_runtime_s=marks["imports"] - marks["process"],
        device_engine_and_compile_s=marks["engine_ready"] - marks["imports"],
        weights_from_seed_s=marks["weights"] - marks["engine_ready"],
        warm_shapes_s=marks["shapes_warm"] - marks["weights"],
        reference_check_s=marks["reference"] - marks["shapes_warm"],
        warm_traffic_s=t0 - marks["reference"],
        compile_until_ready=compile_ready,
        compile_at_window_start=drive.compile0,
    )

    records = metrics.Records(system, drive)
    gen = traffic["generator"]
    say("percentiles", **metrics.earlier_lines(gen, records))
    in_window = [
        (rows_, batch, bucket)
        for t, rows_, batch, bucket in system.prefill_log[first_dispatch:]
        if t0 <= t < t1
    ]
    shapes: dict = {}
    for _, batch, bucket in in_window:
        shapes[f"{batch}x{bucket}"] = shapes.get(f"{batch}x{bucket}", 0) + 1
    unwarmed = sorted(
        {(b, k) for _, b, k in in_window} - {tuple(w) for w in warmed["warmed"]}
    )
    compiles = {
        k: drive.compile1[k] - drive.compile0[k]
        for k in ("cache_requests", "cache_hits", "cache_misses", "compile_seconds")
    }
    dead = await system.dead_letters()
    say(
        "window",
        seconds=t1 - t0,
        prefill_dispatches=len(in_window),
        prefill_shapes=shapes,
        unwarmed_shapes=unwarmed,
        compiles_in_window=compiles,
        preemptions=drive.stats1.get("preemptions", 0) - drive.stats0.get("preemptions", 0),
        generated_tokens=drive.stats1["generated_tokens"] - drive.stats0["generated_tokens"],
        prefills=drive.stats1["prefills"] - drive.stats0["prefills"],
        decode_steps=drive.stats1["decode_steps"] - drive.stats0["decode_steps"],
        num_pages=drive.stats1.get("num_pages"),
        dead_letters=dead,
    )
    if gen == "closed_loop":
        say("slowest_tpot", requests=loadgen.slowest_tpot(records, system.prefill_log))

    counts = metrics.failures(gen, records)
    counts["failed"] += sum(dead.values())
    values = metrics.end_to_end(gen, records, setup_s)
    device["memory_peak_bytes"] = memory_peak(cell.chips)
    result = {
        "correct": check["correct"],
        "attempted": counts["attempted"],
        "failed": counts["failed"],
    }
    if args.trace:
        events = None
        if tracer.span is not None:
            xplane = trace_reduce.find_xplane(trace_dir)
            events = trace_reduce.load_xplane(xplane)
            device["busy_s"] = trace_reduce.busy_seconds(events)
            device["window_s"] = trace_reduce.traced_seconds(events)
            result["breakdown"] = trace_reduce.breakdown(events)
            xplane.unlink()  # tens of megabytes; the numbers are out
        ctx = SimpleNamespace(
            records=records, trace=events, live_kv=tracer.live_kv,
            model=cell.config, peaks=peaks, traffic=traffic,
            prefill_log=system.prefill_log[first_dispatch:],
        )
        result["metrics"] = read_layer_metrics(cell, ctx)
    else:
        missing = [m["name"] for m in cell.end_to_end if values.get(m["name"]) is None]
        if missing:
            raise RuntimeError(f"no value for end-to-end metric(s) {missing}")
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end
        }
    result["device"] = device
    compared = {"correct": check["correct"], "limits_file": limits_file,
                "compared": check["compared"]}
    return result, compared


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    cell = load_cell(args.workload)
    from benchmark import schedule

    traffic = schedule.load_traffic(cell.traffic_file)
    if args.rehearse_cpu:
        apply_rehearsal(cell, traffic)
    device = device_facts(cell.chips, args.rehearse_cpu)
    say("device", **device, workload=cell.name, seed=args.seed,
        seconds=args.seconds, trace=args.trace)
    loop = asyncio.new_event_loop()
    result, compared = loop.run_until_complete(run(args, cell, traffic, device))
    # After the loop has stopped, so that no warning of the worker's follows
    # it: the record of a run that is not correct keeps standard error's end.
    print(json.dumps(compared, default=float), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    # The worker's own shutdown waits for requests the run has abandoned
    # and the engine thread is a daemon; this process started no other
    # process, so it ends here.
    os._exit(0)


if __name__ == "__main__":
    main()
