"""Every ``bound`` of ``BENCHMARK.json`` is what the rule (``bounds.py``)
gives from the sets kept in ``bounds.json``: a bound typed in by hand, or
a set edited afterwards, fails here."""

import copy
import json
import statistics
from pathlib import Path

import pytest

from benchmark import bounds

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
BOOK = bounds.load()
ENDS = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]
LEDGER = ROOT / "PERF_LEDGER.jsonl"


def made_up(sets, checks, accepted=0.01, **entry):
    """A book of one metric ``m`` of one cell ``c`` over made-up sets."""
    return {
        "sets": {one["label"]: one for one in sets},
        "metrics": {"m": {"accepted": accepted, "cells": {"c": {"checks": checks}}, **entry}},
    }


def six(label, base, step):
    return {"label": label, "runs": [{"metrics": {"m": base + step * i}} for i in range(6)]}


def test_the_arithmetic_by_hand():
    six = [100.0, 101.0, 102.0, 103.0, 104.0, 110.0]
    # statistics.quantiles, n=4, exclusive: q1 = 100.75, q3 = 105.5; median 102.5
    assert bounds.quartile_spread(six) == pytest.approx(4.75 / 102.5)
    assert bounds.quartile_spread(six) == pytest.approx(
        (lambda q: (q[2] - q[0]) / statistics.median(six))(statistics.quantiles(six, n=4))
    )
    # the run farthest from the median (110) left out: 100..104 of a median of 102
    assert bounds.trimmed_spread(six) == pytest.approx(3.0 / 102.0)
    assert bounds.trimmed_range(six) == pytest.approx(4.0 / 102.5)
    assert [bounds.round_up(x) for x in (0.0301, 0.03, 0.0349, 0.035, 0.001)] == [
        0.035, 0.03, 0.035, 0.035, 0.005
    ]


@pytest.mark.parametrize("name", list(ENDS))
def test_each_bound_is_the_rules(name):
    entry = BOOK["metrics"][name]
    assert ENDS[name]["bound"] == bounds.bound_of(BOOK, name)
    # the cells whose sets count are the cells that list the metric
    assert sorted(entry["cells"]) == sorted(ENDS[name].get("workloads", CELLS))
    assert bounds.FLOOR <= ENDS[name]["bound"] <= bounds.CEILING <= 0.1
    assert ENDS[name]["bound"] >= entry["accepted"], "a bound is never lowered"
    # the driver refuses a benchmark whose second set's median lies further
    # from its first's than the bound, on the same code
    assert ENDS[name]["bound"] >= max(bounds.median_distances(BOOK, name), default=0.0)


def test_no_metric_is_missing_or_left_over():
    assert list(BOOK["metrics"]) == list(ENDS)
    assert "rule" not in BOOK, "the rule's numbers are bounds.py's, not data beside the readings"


def test_a_set_is_six_runs_on_the_same_seeds_and_none_is_dropped():
    used = set()
    for name, entry in BOOK["metrics"].items():
        for cell, rows in entry["cells"].items():
            in_checks = [label for check in rows.get("checks", []) for label in check]
            assert len(in_checks) == len(set(in_checks))
            for label in in_checks + rows.get("further", []):
                used.add(label)
                one = BOOK["sets"][label]
                assert one["workload"] == cell
                assert name in (one["runs"][0]["metrics"] if "runs" in one else [one["metric"]]) or name == "setup_s"
            assert not set(in_checks) & set(rows.get("further", []))
            for line in rows.get("ledger", []):
                assert line["source"].startswith("ledger, PR ") and line["pairs"] >= 2
                assert 0 < line["spread"] < 0.1
    assert used == set(BOOK["sets"]), "a set that no metric reads, or a label with no set"
    for label, one in BOOK["sets"].items():
        assert one["label"] == label
        if "runs" not in one:  # one side of a check of the driver's: its own test, below
            assert label not in str(
                [rows.get("further") for e in BOOK["metrics"].values() for rows in e["cells"].values()]
            )
            continue
        assert one["trace"] == 0 and one["seconds"] == BENCH["run_seconds"]
        assert len(one["runs"]) in (3, 6) and (len(one["runs"]) == 6 or label.endswith("further"))
        assert [r["seed"] for r in one["runs"]] == BOOK["seeds"][: len(one["runs"])], label
        for run in one["runs"]:
            assert run["rc"] == 0 and run["correct"] is True and run["failed"] == 0
            assert run["platform"] == "tpu", "a set is taken on the chip"
    for one in BOOK["other_runs"]:
        assert all(run["platform"] == "tpu" for run in one["runs"]), one["label"]


DRIVERS = [label for label, one in BOOK["sets"].items() if "runs" not in one]


@pytest.mark.parametrize("label", DRIVERS)
def test_a_drivers_set_reads_what_its_quoted_reason_states(label):
    """A set of the driver's has no runs: what the rule reads of it is
    worked out from the numbers of the driver's reason, each of which
    stands in the quote kept beside it, and the quote in the ledger where
    the ledger still has that PR."""
    one = BOOK["sets"][label]
    base = [one["median"]] if "median" in one else [one["bound"], one["bound_share"]]
    assert ("median" in one) != ("bound" in one)
    for number in [one["spread"], *base[:1]]:
        assert f" {number!r}" in one["quote"].replace("(", " "), (label, number)
    if "bound" in one:
        assert one["bound_share"] == BOOK["metrics"][one["metric"]]["accepted"]
    median = base[0] if "median" in one else one["bound"] / one["bound_share"]
    assert bounds.set_spread(one, one["metric"]) == pytest.approx(one["spread"] / median)
    assert "spread_ms" not in one and not isinstance(one["spread"], dict), "no share typed in beside the reason"
    with pytest.raises(KeyError):
        bounds.set_spread(one, "another_metric")
    reasons = [
        line.get("reason", "") for line in map(json.loads, LEDGER.read_text().splitlines())
        if line.get("pr") == one["pr"]
    ] if LEDGER.exists() else []
    if any(reasons):
        assert any(one["quote"] in reason for reason in reasons), "the ledger has that PR, and not these words"
        assert one["quote_kept_in"].startswith("ledger, PR ")


def test_a_bound_typed_in_by_hand_or_an_edited_set_fails():
    """The proof the tests above have teeth: a bound one step off is not
    the rule's, and a reading of the widest check edited afterwards, in a
    set of runs or in a quoted reason, gives another bound than
    ``BENCHMARK.json`` has."""
    proved = 0
    for name in ENDS:
        checks = bounds.counted_checks(BOOK, name)
        if not checks or ENDS[name]["bound"] in (BOOK["metrics"][name]["accepted"], bounds.CEILING):
            continue  # a kept bound stands on `accepted`, whatever the sets read under it
        for off in (-bounds.STEP, bounds.STEP):
            assert round(ENDS[name]["bound"] + off, 6) != bounds.bound_of(BOOK, name)
        book = copy.deepcopy(BOOK)
        widest = max(bounds.counted_checks(book, name), key=lambda check: bounds.check_spread(check, name))
        for one in widest:
            if "runs" in one:
                for i, run in enumerate(one["runs"]):
                    run["metrics"][name] *= 1.0 + 0.02 * i
            else:
                one["spread"] *= 1.5
        assert bounds.bound_of(book, name) != ENDS[name]["bound"], name
        proved += 1
    assert proved >= 2


def test_a_set_is_left_out_only_with_a_standstill_and_one_in_eight_at_most():
    labels = [f"s{i}" for i in range(8)]
    sets = [six(label, 100.0, 1.0) for label in labels]
    for one in sets:
        for run in one["runs"]:
            run["stood_still_in_window"] = False
    book = made_up(sets, [[label] for label in labels], left_out=["s3"])
    with pytest.raises(ValueError, match="no run of it logged a standstill"):
        bounds.counted_sets(book, "m")
    book["sets"]["s3"]["runs"][2]["stood_still_in_window"] = True
    assert [one["label"] for one in bounds.counted_sets(book, "m")] == [l for l in labels if l != "s3"]
    book["metrics"]["m"]["left_out"] = ["s3", "s4"]
    with pytest.raises(ValueError, match="one in eight"):
        bounds.counted_sets(book, "m")
    # the rule on these made-up sets: quartiles of 100.5..103.5 of five kept, over 102,
    # FACTOR times, rounded up; never under the accepted bound; no set, the accepted bound
    book["metrics"]["m"]["left_out"] = []
    assert bounds.bound_of(book, "m") == bounds.round_up(bounds.FACTOR * 3.0 / 102.0) == 0.075
    book["metrics"]["m"]["accepted"] = 0.08
    assert bounds.bound_of(book, "m") == 0.08
    book["metrics"]["m"]["cells"]["c"]["checks"] = []
    assert bounds.bound_of(book, "m") == 0.08


def test_a_checks_spread_is_the_mean_of_its_sets_and_the_rule_stays_inside_the_contract():
    """Two sets taken together count as the driver counts its two: by the
    mean of their spreads, not by the wider."""
    book = made_up([six("quiet", 100.0, 0.1), six("wide", 100.0, 1.0)], [["quiet", "wide"]])
    quiet, wide = (bounds.set_spread(book["sets"][k], "m") for k in ("quiet", "wide"))
    assert wide == pytest.approx(3.0 / 102.0) and quiet == pytest.approx(0.3 / 100.2)
    assert bounds.bound_of(book, "m") == bounds.round_up(bounds.FACTOR * (quiet + wide) / 2) == 0.045
    book["metrics"]["m"]["cells"]["c"]["checks"] = [["quiet"], ["wide"]]
    assert bounds.bound_of(book, "m") == bounds.round_up(bounds.FACTOR * wide) == 0.075
    book["metrics"]["m"]["cells"]["c"]["checks"] = [["quiet"]]
    assert bounds.bound_of(book, "m") == bounds.FLOOR
    wild = made_up([six("wild", 100.0, 3.0)], [["wild"]])
    assert bounds.bound_of(wild, "m") == bounds.CEILING


def test_how_far_two_sets_medians_lay_apart_is_read_and_is_no_arm_of_the_rule():
    book = made_up([six("a", 100.0, 0.1), six("b", 104.0, 0.1)], [["a"], ["b"]])
    assert bounds.median_distances(book, "m") == [pytest.approx(4.0 / 100.25)]
    assert bounds.bound_of(book, "m") == bounds.FLOOR  # the spreads are a thousandth
    book["metrics"]["m"]["cells"]["c"]["median_pairs"] = [{"source": "ledger, PR 0", "medians": [417.98, 434.07]}]
    book["metrics"]["m"]["cells"]["c"]["checks"] = [["a"]]
    assert bounds.median_distances(book, "m") == [pytest.approx(16.09 / 417.98)]
    assert bounds.bound_of(book, "m") == bounds.FLOOR


def sets_of_runs(name, cell):
    rows = BOOK["metrics"][name]["cells"][cell]
    labels = [label for check in rows.get("checks", []) for label in check]
    return [BOOK["sets"][label] for label in labels if "runs" in BOOK["sets"][label]]


def test_no_check_reads_over_half_its_bound():
    """The driver refuses a bound as too tight where the mean of a check's
    two spreads (each set's farthest run left out) is over half of it: held
    on every counted check of every metric."""
    for name in BOOK["metrics"]:
        for check in bounds.counted_checks(BOOK, name):
            assert bounds.check_spread(check, name) <= ENDS[name]["bound"] / 2, (name, [one["label"] for one in check])


@pytest.mark.parametrize("name", [n for n, m in ENDS.items() if m["bound"] not in (bounds.FLOOR, BOOK["metrics"][n]["accepted"])])
def test_a_new_bound_is_within_eight_times_each_cells_widest_set_or_the_cell_is_named_as_riding(name):
    """The driver refuses a bound as too loose where it is over eight times
    the wider spread of all the runs it reads (1 % is never too loose; a
    kept bound is not this PR's). Held cell by cell: a cell that lists the
    metric has sets of runs whose widest quartile distance (no run left
    out) is an eighth of the bound or more, or it stands under the metric's
    ``rides`` with its reading, a steady cell on a noisier one's bound; and
    no cell stands there that does not ride."""
    rides = BOOK["metrics"][name].get("rides", {})
    assert set(rides) <= set(BOOK["metrics"][name]["cells"])
    for cell in BOOK["metrics"][name]["cells"]:
        spreads = [bounds.quartile_spread([run["metrics"][name] for run in one["runs"]]) for one in sets_of_runs(name, cell)]
        admitted = bool(spreads) and ENDS[name]["bound"] <= 8 * max(spreads)
        assert admitted != (cell in rides), (name, cell, spreads)
        assert admitted or len(rides[cell]) > 20
    assert len(rides) < len(BOOK["metrics"][name]["cells"]), "some cell's own sets set the bound"
