#!/usr/bin/env python3
"""The builder's two one-off measurements, each in one process on the chip.
Neither is part of a benchmark run.

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 [--controls <a>,<b>]
        For each of the seeds: the numbers of ``correct.py`` for the
        served program, a run's worst of each, and on the first
        ``--control-seeds`` of them for each control (the reference of
        the configuration's architecture computed in a lower precision,
        standing in the program's place; without ``--controls``, every one
        in that architecture's ``CONTROLS``). The configuration's limits
        (``limits/<config>.json``, or ``limits.json``) are set from the
        largest of the first and the smallest of the second.

    python3 benchmark/calibrate.py --workload <open-loop cell> --sweep 6,8,10,12,14
        The knee: ascending rates, 20 s each, the backlog (requests sent
        that have no first token yet) in the middle and at the end of
        each stretch, and how late the generator ran. The knee is the
        highest rate at which the backlog at the end is no larger than
        in the middle and the generator ran on time.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

SEEDS = [11, 2147483659, 3000000019, 77, 123456789, 4242424242, 5, 999983,
         2718281828, 31415926, 1618033, 86028157, 1000003, 65537, 2305843,
         19937]


def say(line: str, **facts) -> None:
    print(json.dumps({"line": line, **facts}, default=float), flush=True)


async def correctness(args, cell, traffic, system) -> None:
    from benchmark import architectures, correct

    controls = [c for c in args.controls.split(",") if c] or list(
        architectures.of(cell.config).CONTROLS
    )
    names = ("logit_err", "served_regret", "repeat_diff")

    def worst(rows):
        return {n: max(r[n] for r in rows if n in r) for n in names if any(n in r for r in rows)}

    sound, ctrl = [], {c: [] for c in controls}
    for n, seed in enumerate(SEEDS[: args.seeds]):
        await asyncio.to_thread(system.serve_weights_from_seed, seed)
        checked = await correct.check_cell(system, cell.config, traffic["check_lengths"], seed)
        sound.append(worst(checked["rows"]))
        say("sound", seed=seed, rows=checked["rows"], load=checked["load"])
        for c in controls if n < args.control_seeds else ():
            rows = await asyncio.to_thread(
                system.engine.call_on_engine,
                lambda: correct.control_rows(system.core, cell.config, checked["kept"], c),
                600.0,
            )
            ctrl[c].append(worst(rows))
            say("control", control=c, seed=seed, rows=rows)
    say(
        "readings",
        sound_largest={n: max(r[n] for r in sound) for n in names},
        sound_smallest={n: min(r[n] for r in sound) for n in names},
        control_smallest={
            c: {n: min(r[n] for r in runs) for n in names[:2]} for c, runs in ctrl.items() if runs
        },
        control_largest={
            c: {n: max(r[n] for r in runs) for n in names[:2]} for c, runs in ctrl.items() if runs
        },
        seeds=args.seeds,
        control_seeds=min(args.seeds, args.control_seeds),
    )


async def sweep(args, cell, traffic, system) -> None:
    from benchmark import schedule
    from benchmark.loadgen import sleep_until
    from benchmark.run_helpers import warm_shapes
    from benchmark.stats import percentile

    stretch = 20.0
    base = schedule.make_schedule(dict(traffic, rate_rps=30.0, warm_seconds=0,
                                       tail_seconds=0), stretch)
    await warm_shapes(system, base)
    await asyncio.to_thread(system.serve_weights_from_seed, 1)
    for n, rate in enumerate(float(r) for r in args.sweep.split(",")):
        spec = dict(traffic, rate_rps=rate, warm_seconds=0, tail_seconds=0)
        reqs = schedule.make_schedule(spec, stretch)
        texts = {r.index: schedule.prompt_text(1, r.index, r.prompt_tokens) for r in reqs}
        system.timings.clear()
        start = time.monotonic() + 0.05
        sent, late, backlog = [], [], {}

        def pending() -> int:
            running = system.inflight()
            return sum(
                1 for rid in sent
                if rid not in system.timings
                and not (rid in running and running[rid].t_first_token)
            )

        for req in reqs:
            due = start + req.due_s
            if "mid" not in backlog and due >= start + stretch / 2:
                backlog["mid"] = pending()
            await sleep_until(due)
            rid = f"s{n}-{req.index}"
            t = await system.publish(rid, texts[req.index], req.output_tokens)
            sent.append(rid)
            late.append((t - due) * 1e3)
        await sleep_until(start + stretch)
        backlog["end"] = pending()
        t_end = time.monotonic()
        while len(system.timings) < len(sent) and time.monotonic() < t_end + 120:
            await asyncio.sleep(0.2)
        ttft = [
            (system.timings[r]["first_token"] - system.timings[r]["enqueued"]) * 1e3
            for r in sent if r in system.timings
        ]
        say("sweep", rate_rps=rate, sent=len(sent), backlog_mid=backlog.get("mid"),
            backlog_end=backlog["end"], lateness_p95_ms=percentile(late, 95),
            ttft_p50_ms=percentile(ttft, 50), ttft_p95_ms=percentile(ttft, 95),
            drain_after_s=time.monotonic() - t_end)


async def amain(args) -> None:
    from benchmark import schedule
    from benchmark.run_helpers import apply_rehearsal, device_facts, load_cell
    from benchmark.system import System

    cell = load_cell(args.workload)
    traffic = schedule.load_traffic(cell.traffic_file)
    if args.lengths:
        traffic["check_lengths"] = [int(n) for n in args.lengths.split(",")]
    if args.rehearse_cpu:
        apply_rehearsal(cell, traffic)
    say("device", **device_facts(cell.chips, args.rehearse_cpu), workload=cell.name)
    system = System(cell.config, cell.name)
    await system.start()
    if args.sweep:
        await sweep(args, cell, traffic, system)
    else:
        await correctness(args, cell, traffic, system)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", default="", help="default: the CONTROLS of "
                    "the configuration's architecture")
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="the controls are read on the first this many seeds")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--lengths", default="", help="sample prompt lengths, "
                    "instead of the traffic file's check_lengths")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    loop = asyncio.new_event_loop()
    loop.run_until_complete(amain(args))
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
