"""``BENCHMARK.json`` against the files it names and the rules of its contract."""

import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_keys_and_names():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.match(n), n
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(CELLS)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 4)
    for w in BENCH["workloads"] + BENCH["configs"]:
        assert 1 <= len(w["why"]) <= 200


def test_end_to_end_metrics_are_medians_rates_and_setup():
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "ttft_p50_ms", "tpot_p50_ms", "out_tok_s", "job_tok_s", "setup_s"
    ]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    # no tail is judged: each p95 is a per-layer metric under a name of its own
    names = {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    assert not names & {"tpot_p95_ms", "ttft_p95_ms"}
    assert {"tpot_p95_ms.open", "tpot_p95_ms.closed", "ttft_p95_ms.open"} <= names


def test_a_window_rate_and_a_job_rate_are_two_metrics():
    """``out_tok_s`` is a window's tokens over the window (closed loop),
    ``job_tok_s`` a fixed job's tokens over the job: each cell lists the
    one its generator yields, and a per-layer metric of a job cell moves
    ``job_tok_s`` under a name no closed-loop cell lists."""
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    kind = {}
    for w in BENCH["workloads"]:
        traffic = json.loads((ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json").read_text())
        kind[w["name"]] = traffic["generator"]
    assert ends["job_tok_s"]["workloads"] == [c for c in CELLS if kind[c] == "fixed_job"]
    assert ends["out_tok_s"]["workloads"] == [c for c in CELLS if kind[c] == "closed_loop"]
    assert (ends["job_tok_s"]["unit"], ends["job_tok_s"]["better"]) == ("tokens/s", "higher")
    for m in BENCH["per_layer"]:
        kinds = {kind[c] for c in m["workloads"]}
        assert len(kinds) == 1 or kinds == {"open_loop", "closed_loop"}, m["name"]
        assert (m["moves"] == "job_tok_s") == (kinds == {"fixed_job"}), m["name"]
    for name in ("prefill_dev_share_pct", "admit_hold_share_pct", "prefill_rows_mean"):
        job, closed = (
            json.loads((ROOT / "benchmark" / "layer_metrics" / f"{name}.{end}.json").read_text())
            for end in ("job", "closed")
        )
        assert (job["reader"], job.get("args")) == (closed["reader"], closed.get("args"))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_has_its_files_and_metrics(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    cfg = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert (ROOT / cfg["file"]).is_file() and cfg["file"].startswith("benchmark/")
    assert (ROOT / "benchmark" / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = [m for m in BENCH["end_to_end"] if cell in m.get("workloads", CELLS)]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert any(cell in m.get("workloads", CELLS) for m in BENCH["per_layer"])


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_layer_metric_has_a_reader_and_moves_what_its_cells_report(metric):
    spec = json.loads(
        (ROOT / "benchmark" / "layer_metrics" / f"{metric['name']}.json").read_text()
    )
    assert (ROOT / "benchmark" / "readers" / f"{spec['reader']}.py").is_file()
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == metric["moves"])
    for cell in metric["workloads"]:
        assert cell in moved.get("workloads", CELLS), (metric["name"], cell)
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_config_files_hold_the_source_widths():
    three = json.loads((ROOT / "benchmark/configs/qwen2.5-3b-bf16.json").read_text())
    assert (three["hidden_size"], three["num_hidden_layers"], three["intermediate_size"]) == (2048, 36, 11008)
    assert (three["num_attention_heads"], three["num_key_value_heads"], three["vocab_size"]) == (16, 2, 151936)
    seven = json.loads((ROOT / "benchmark/configs/qwen2.5-7b-bf16-tp4.json").read_text())
    assert (seven["hidden_size"], seven["num_hidden_layers"], seven["intermediate_size"]) == (3584, 28, 18944)
    assert (seven["num_attention_heads"], seven["num_key_value_heads"], seven["vocab_size"]) == (28, 4, 152064)
    assert seven["engine"]["tensor_parallel"] == 4


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_has_an_architecture_limits_and_worker_options(config):
    import inspect

    from benchmark import architectures, correct, weights
    from benchmark.system import worker_options
    from llmq_tpu.cli.worker import build_tpu_worker

    cfg = json.loads((ROOT / config["file"]).read_text())
    arch = architectures.of(cfg)
    assert all(hasattr(arch, m) for m in architectures.MEMBERS)
    for group in arch.tree_shapes(cfg).values():
        for name in group if isinstance(group, dict) else ():
            assert arch.init_rule(name) in weights.RULES
    limits, source = correct.load_limits(config["name"])
    assert set(limits) == {"logit_err", "repeat_diff", "served_regret"}
    assert source in (f"benchmark/limits/{config['name']}.json", "benchmark/limits.json")
    options = worker_options(cfg["engine"], inspect.signature(build_tpu_worker))
    assert options and all(not k.endswith("_why") for k in options)
