#!/usr/bin/env python3
"""How an end-to-end bound is set, and the builder's tool that takes the
sets it is set from. Not part of a run.

    python3 benchmark/bounds.py sets --workload <cell> --seeds a,b,c,d,e,f --label <label>
        One set: a run of ``benchmark/run.py`` a seed, a process a run,
        one after another, ``--trace 0``, at ``BENCHMARK.json``'s
        ``run_seconds``. Written to ``chiprun_out/sets/<label>.json`` as
        ``bounds.json`` keeps a set; copy it there by hand.

    python3 benchmark/bounds.py table
        Every metric of ``bounds.json``: each set's median, its quartile
        distance over the median with and without its farthest run and
        its range without it, how far two sets' medians lay apart, and the
        bound the rule gives beside the one ``BENCHMARK.json`` has.

``bounds.json`` holds the readings and nothing of the rule; the rule is
``bound_of`` below with the constants beside it, and
``tests/test_bounds.py`` holds ``BENCHMARK.json`` to it.
"""

from __future__ import annotations

import argparse
import json
import math
import re
import statistics
import subprocess
import sys
import time
from pathlib import Path
from typing import List, Sequence

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
STEP = 0.005
# The driver's check refuses a bound as too tight where the mean of its two
# sets' spreads, each set's farthest run left out, is over HALF of it: 2 is
# the edge, and the checks of byte-equal chat programs on record read
# 0.027-0.030 all three, so 2 would put the bound on the edge of each. A
# quarter of headroom. The contract's "about five times" gives over 0.1 for
# chat and docqa, which the driver's too-loose test (over eight times the
# wider spread of all the runs) refuses in a quiet hour.
FACTOR = 2.5
FLOOR, CEILING = 0.01, 0.1  # the contract's
STOOD_STILL = re.compile(r"event loop stood still: .*?ran (\d+) ms late \(t_mono ([0-9.]+)\)")


def quartile_spread(values: Sequence[float]) -> float:
    """The distance between the first and the third quartile over the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    the driver's spread of a set."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / abs(statistics.median(values))


def _without_farthest(values: Sequence[float]) -> List[float]:
    mid = statistics.median(values)
    return sorted(values, key=lambda v: abs(v - mid))[:-1]


def trimmed_spread(values: Sequence[float]) -> float:
    """``quartile_spread`` of the set with the run farthest from its median
    left out: what the driver's check takes for tightness, so that one
    far-off run in a set does no harm and two do."""
    return quartile_spread(_without_farthest(values))


def trimmed_range(values: Sequence[float]) -> float:
    """Largest minus smallest over the set's median, the run farthest from
    the median left out."""
    kept = _without_farthest(values)
    return (max(kept) - min(kept)) / abs(statistics.median(values))


def round_up(share: float) -> float:
    """The smallest multiple of ``STEP`` that is not under ``share``."""
    return round(math.ceil(round(share / STEP, 9)) * STEP, 6)


def set_spread(one_set: dict, metric: str) -> float:
    """The spread the rule reads of one set: of its runs where it has them.
    A set of the driver's has none (the ledger keeps no runs) and its
    ``quote``, the driver's reason, states this very statistic in the
    metric's unit: ``spread`` over ``median``, or over ``bound`` /
    ``bound_share`` where the reason gives the bound and not the median."""
    if "runs" in one_set:
        return trimmed_spread([run["metrics"][metric] for run in one_set["runs"]])
    if one_set["metric"] != metric:
        raise KeyError(f"set {one_set['label']} reads {one_set['metric']}, not {metric}")
    median = one_set.get("median") or one_set["bound"] / one_set["bound_share"]
    return one_set["spread"] / median


def stood_still(one_set: dict) -> bool:
    """Whether a run of the set logged a standstill inside its window: the
    worker's warning in a run's standard error; of a set of the driver's,
    whose standard error nobody kept, a late tick of the worker's loop-lag
    mark inside the traced window (``loop_lag_max_ms`` of the ledger)."""
    return bool(one_set.get("loop_lag_in_window_ms")) or any(
        run.get("stood_still_in_window") for run in one_set.get("runs", [])
    )


def counted_checks(book: dict, name: str) -> List[List[dict]]:
    """What a metric's bound is set from: for every cell that lists the
    metric its ``checks``, each the sets (``book["sets"]``, by label) that
    were taken together as the driver takes its two (in one call, or the
    two sides of one check of the driver's; a set taken alone is a check of
    one), without the sets named under the metric's ``left_out`` (one set
    in eight at most, each with a run that logged a standstill inside its
    window). A ledger line kept beside them (``ledger``) is no set: its
    ``spread`` is another statistic (the wider of two sides, no run left
    out), over other numbers of runs."""
    entry = book["metrics"][name]
    left_out = set(entry.get("left_out", []))
    checks = [check for cell in entry["cells"].values() for check in cell.get("checks", [])]
    labels = [label for check in checks for label in check]
    if len(left_out) * 8 > len(labels):
        raise ValueError(f"{len(left_out)} of {len(labels)} sets left out: one in eight at most")
    for label in left_out:
        if label not in labels:
            raise ValueError(f"set {label} left out, and no cell of {name} has it")
        if not stood_still(book["sets"][label]):
            raise ValueError(f"set {label} left out, and no run of it logged a standstill")
    kept = [[book["sets"][label] for label in check if label not in left_out] for check in checks]
    return [check for check in kept if check]


def counted_sets(book: dict, name: str) -> List[dict]:
    return [one for check in counted_checks(book, name) for one in check]


def check_spread(check: List[dict], name: str) -> float:
    """The mean of the spreads of a check's sets: what the driver's check
    holds half the bound against."""
    return statistics.mean(set_spread(one, name) for one in check)


def apart(a: float, b: float) -> float:
    """The distance between two medians over the smaller."""
    return abs(a - b) / min(abs(a), abs(b))


def median_distances(book: dict, name: str) -> List[float]:
    """How far apart the medians of two sets of one cell on the same
    programs lay: every two counted sets of runs of a cell, and the pairs
    kept under the cell's ``median_pairs`` (a ledger line both sides of
    which ran the same programs there). No part of the rule: the driver
    refuses a benchmark whose second set's median differs from its first's
    by more than the bound, so ``tests/test_bounds.py`` holds every bound
    over the widest of these."""
    counted = {one["label"] for one in counted_sets(book, name)}
    out = []
    for cell in book["metrics"][name]["cells"].values():
        medians = [
            statistics.median(run["metrics"][name] for run in book["sets"][label]["runs"])
            for check in cell.get("checks", [])
            for label in check
            if label in counted and "runs" in book["sets"][label]
        ]
        pairs = [(a, b) for i, a in enumerate(medians) for b in medians[i + 1:]]
        pairs += [tuple(pair["medians"]) for pair in cell.get("median_pairs", [])]
        out += [apart(a, b) for a, b in pairs]
    return out


def bound_of(book: dict, name: str) -> float:
    """The rule. ``FACTOR`` times the widest spread that any counted check
    of any cell listing the metric showed (the mean of its sets' spreads),
    up to the next multiple of ``STEP``, held inside ``FLOOR``..``CEILING``
    and never under the bound the metric had (``accepted``). A metric for
    which no set was taken keeps its accepted bound."""
    accepted = float(book["metrics"][name]["accepted"])
    checks = counted_checks(book, name)
    if not checks:
        return accepted
    need = FACTOR * max(check_spread(check, name) for check in checks)
    return max(min(max(round_up(need), FLOOR), CEILING), accepted)


def load() -> dict:
    return json.loads((HERE / "bounds.json").read_text())


# --- the tool ---------------------------------------------------------------


def _lines(text: str) -> List[dict]:
    out = []
    for line in text.splitlines():
        if line.startswith("{"):
            try:
                out.append(json.loads(line))
            except ValueError:
                pass
    return out


def one_run(workload: str, seed: int, seconds: float, trace: int, keep: Path) -> dict:
    """One process, one run; what ``bounds.json`` keeps of it."""
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t_start = time.monotonic()
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True, timeout=1500)
    wall = time.monotonic() - t_start
    keep.mkdir(parents=True, exist_ok=True)
    (keep / f"{seed}.out").write_text(done.stdout[-200_000:])
    (keep / f"{seed}.err").write_text(done.stderr[-200_000:])
    row: dict = {"seed": seed, "rc": done.returncode, "wall_s": round(wall, 1)}
    lines = _lines(done.stdout)
    if done.returncode or not lines or "metrics" not in lines[-1]:
        return row
    last = lines[-1]
    named = {l["line"]: l for l in lines if "line" in l}
    row.update(
        correct=last["correct"], attempted=last["attempted"], failed=last["failed"],
        metrics={k: v["value"] for k, v in last["metrics"].items()},
        platform=last["device"]["platform"],
        memory_peak_bytes=last["device"].get("memory_peak_bytes"),
    )
    window = named.get("window", {})
    for key in ("prefill_dispatches", "decode_steps", "prefills"):
        row[key] = window.get(key)
    row["window_s"] = window.get("seconds")
    # The child's monotonic clock is this process's: the window opened
    # ``setup_s`` after the child started (to the interpreter's start-up).
    setup_s = named.get("setup", {}).get("setup_s")
    stops = [(float(t), float(ms)) for ms, t in STOOD_STILL.findall(done.stderr)]
    row["stood_still"] = [[round(t - t_start, 2), ms] for t, ms in stops]
    if setup_s is not None and row["window_s"] is not None:
        t0 = t_start + setup_s
        row["stood_still_in_window"] = any(
            t0 <= t <= t0 + row["window_s"] + ms / 1e3 for t, ms in stops
        )
    return row


def take_set(args) -> None:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = bench["run_seconds"]
    out_dir = ROOT / "chiprun_out" / "sets"
    runs = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        row = one_run(args.workload, seed, seconds, args.trace, out_dir / args.label)
        runs.append(row)
        print(json.dumps(row), flush=True)
        one = {"label": args.label, "workload": args.workload, "seconds": seconds,
               "trace": args.trace, "taken": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
               "runs": runs}
        (out_dir / f"{args.label}.json").write_text(json.dumps(one, indent=1))


def table(_args) -> None:
    book = load()
    bench = {m["name"]: m["bound"] for m in json.loads((ROOT / "BENCHMARK.json").read_text())["end_to_end"]}
    for name, entry in book["metrics"].items():
        print(f"{name}: the rule gives {bound_of(book, name)}, "
              f"BENCHMARK.json has {bench.get(name)}, accepted before {entry['accepted']}")
        for cell, rows in entry["cells"].items():
            for check in rows.get("checks", []):
                kept = [book["sets"][label] for label in check if label not in entry.get("left_out", [])]
                if kept:
                    print(f"  {cell} check {' + '.join(check)}: mean spread {check_spread(kept, name):.4f}")
            labels = [label for check in rows.get("checks", []) for label in check]
            for label in labels + rows.get("further", []):
                one = book["sets"][label]
                note = " LEFT OUT" if label in entry.get("left_out", []) else ""
                note += " (further: sets no bound)" if label in rows.get("further", []) else ""
                if "runs" not in one:
                    print(f"  {cell} {label}: without the farthest {set_spread(one, name):.4f} "
                          f"(the driver's reason, PR {one['pr']}: {one['quote']}){note}")
                    continue
                vals = [run["metrics"][name] for run in one["runs"]]
                still = sum(bool(run.get("stood_still_in_window")) for run in one["runs"])
                print(f"  {cell} {label} ({one['taken']}): median {statistics.median(vals):.4f} "
                      f"quartiles {quartile_spread(vals):.4f} without the farthest {trimmed_spread(vals):.4f} "
                      f"range without it {trimmed_range(vals):.4f} standstills {still}{note}")
            for line in rows.get("ledger", []):
                print(f"  {cell} {line['source']}: spread {line['spread']} over {line['pairs']} pairs")
            for pair in rows.get("median_pairs", []):
                a, b = pair["medians"]
                print(f"  {cell} {pair['source']}: medians {a} / {b}, {apart(a, b):.4f} apart")
        print(f"  widest distance between two sets' medians: {max(median_distances(book, name), default=0.0):.4f}")
        for cell, why in entry.get("rides", {}).items():
            print(f"  {cell} RIDES on this bound: {why}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    sub = ap.add_subparsers(dest="what", required=True)
    s = sub.add_parser("sets")
    s.add_argument("--workload", required=True)
    s.add_argument("--seeds", required=True)
    s.add_argument("--label", required=True)
    s.add_argument("--trace", type=int, choices=(0, 1), default=0)
    s.set_defaults(go=take_set)
    sub.add_parser("table").set_defaults(go=table)
    args = ap.parse_args()
    args.go(args)


if __name__ == "__main__":
    main()
