"""Every configuration and cell of ``BENCHMARK.json`` finds its files by
name, as ``benchmark/run.py`` will look for them: the configuration's
file, its architecture module with the four members the harness asks for,
its limits, the cell's traffic, and a reader for every per-layer metric.
Tier-1 runs ``tests/`` only, so the benchmark's own copy of this proof
(``benchmark/tests/``) guards nothing there."""

import importlib
import json
from pathlib import Path

import jax
import pytest

from benchmark import architectures, correct, run_helpers, weights

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_finds_architecture_and_limits(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"] and cfg["source"] == entry["source"]
    arch = architectures.of(cfg)
    assert arch.__file__.endswith(f"{cfg.get('architecture', architectures.DEFAULT)}.py")
    shapes = arch.tree_shapes(cfg)
    for path, _ in jax.tree.flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )[0]:
        assert arch.init_rule(path[-1].key) in weights.RULES
    assert arch.CONTROLS and callable(arch.forward_logits)
    limits, limits_file = correct.load_limits(entry["name"])
    assert set(limits) == {"logit_err", "repeat_diff", "served_regret"}
    own = ROOT / "benchmark" / "limits" / f"{entry['name']}.json"
    assert limits_file == (
        f"benchmark/limits/{entry['name']}.json" if own.is_file() else "benchmark/limits.json"
    )
    assert cfg["program_model"].startswith("preset://") and "engine" in cfg
    # what the file says was reduced is what BENCHMARK.json says
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_finds_traffic_and_metric_files(cell):
    loaded = run_helpers.load_cell(cell["name"])
    assert loaded.traffic_file.is_file() and loaded.chips == cell["chips"]
    traffic = json.loads(loaded.traffic_file.read_text())
    assert traffic["generator"] in ("open_loop", "closed_loop", "fixed_job")
    assert "setup_s" in {m["name"] for m in loaded.end_to_end} and len(loaded.end_to_end) >= 2
    assert loaded.per_layer
    for metric in loaded.per_layer:
        spec = json.loads(
            (ROOT / "benchmark" / "layer_metrics" / f"{metric['name']}.json").read_text()
        )
        reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
        assert callable(reader.read)


def test_the_hybrid_preset_is_the_tree_its_configuration_describes():
    """The served tree of ``preset://ling-3.0-flash-ep4`` and the tree the
    benchmark makes from the configuration's file have one layout."""
    from llmq_tpu.models import hybrid
    from llmq_tpu.models.presets import get_preset

    cfg = json.loads((ROOT / "benchmark/configs/ling-3.0-flash-ep4.json").read_text())
    assert cfg["program_model"] == "preset://ling-3.0-flash-ep4"
    ours = jax.tree.map(
        tuple, hybrid.param_shapes(get_preset("ling-3.0-flash-ep4")),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    assert ours == architectures.of(cfg).tree_shapes(cfg)
