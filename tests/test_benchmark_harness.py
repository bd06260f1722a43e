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


@pytest.mark.parametrize(
    "name",
    ["ling-3.0-flash-ep4", "openpangu-ultra-moe-718b-ep16", "lfm2-24b-a2b-pp5",
     "evabyte-6.5b-pp4"],
)
def test_the_hybrid_preset_is_the_tree_its_configuration_describes(name):
    """The served tree of ``preset://<name>`` and the tree the benchmark
    makes from the configuration's file have one layout."""
    from llmq_tpu.models import hybrid
    from llmq_tpu.models.presets import get_preset

    cfg = json.loads((ROOT / f"benchmark/configs/{name}.json").read_text())
    assert cfg["program_model"] == f"preset://{name}"
    ours = jax.tree.map(
        tuple, hybrid.param_shapes(get_preset(name)),
        is_leaf=lambda x: isinstance(x, tuple),
    )
    assert ours == architectures.of(cfg).tree_shapes(cfg)


def test_the_openpangu_file_keeps_every_published_key_it_does_not_reduce():
    """Every key of the published config (the worker's preset carries it)
    stands in the benchmark's file at its published value, but for those
    under ``reduced``, which stand beside their published value; no
    reduced key is a width."""
    from llmq_tpu.models.presets import _OPENPANGU_ULTRA_MOE as published

    cfg = json.loads(
        (ROOT / "benchmark/configs/openpangu-ultra-moe-718b-ep16.json").read_text()
    )
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
                       "vocab_size", "num_nextn_predict_layers"}
    for key, value in published.items():
        if key in reduced:
            assert cfg[f"{key}_published"] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert cfg["experts_held"] == [0, cfg["n_routed_experts"]]
    assert set(cfg["assumed"]) >= {"router", "rope", "softmax_scale", "norm_placement",
                                   "shared_expert"}


def test_latent_decode_cost_by_hand_and_a_reader_with_nothing_to_read():
    """``kernel_cost_mla``: one live token and one row of one layer at the
    published widths (the benchmark's own copy of this proof is
    ``benchmark/tests/test_kernel_cost_mla.py``); and the reader leaves
    the metric out where the program gave no spans."""
    from types import SimpleNamespace

    from benchmark import kernel_cost_mla
    from benchmark.readers import decode_mla_roofline

    kw = dict(live_tokens=1, rows=1, layers=1, hidden=7680, heads=128, kv_rank=512,
              nope=128, rope=64, v_dim=128, q_lora=True)
    assert kernel_cost_mla.mla_decode_bytes(**kw) == 1_152 + 42_401_792 + 48_128
    assert kernel_cost_mla.mla_decode_flops(**kw) == 278_528 + 42_401_792
    ctx = SimpleNamespace(_span_join=False, peaks={}, live_kv={"tokens": 1, "sequences": 1})
    assert decode_mla_roofline.read(ctx, program="jit_decode_step", scope="x") is None


def test_the_lfm2_file_keeps_every_published_key_it_does_not_reduce():
    """Every key of the published config (the worker's preset carries it)
    stands in the benchmark's file at its published value, ``layer_types``
    whole, but for the two under ``reduced``, which stand beside their
    published value; no reduced key is a width; the file states what it
    assumed, its deployment and the arithmetic of its 10.36 GB, and the
    file's own mapping gives the preset's pattern."""
    from llmq_tpu.models.config import ModelConfig
    from llmq_tpu.models.presets import _LFM2_24B_A2B as published
    from llmq_tpu.models.presets import get_preset

    cfg = json.loads((ROOT / "benchmark/configs/lfm2-24b-a2b-pp5.json").read_text())
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "num_dense_layers"}
    for key, value in published.items():
        if key in reduced:
            assert cfg[f"{key}_published"] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert len(cfg["layer_types"]) == 40 and len(cfg["kept_layers"]) == cfg["num_hidden_layers"]
    assert [cfg["layer_types"][i] for i in cfg["kept_layers"]] == (
        ["conv"] + ["full_attention", "conv", "conv", "conv"] * 2
    )
    assert set(cfg["assumed"]) >= {"tie_embedding", "head_dim", "router", "qk_norm_order", "rope"}
    assert "5,178 M parameters, 10.36 GB" in cfg["deployment"]
    assert "pipeline stage of five" in cfg["deployment"]
    preset = get_preset("lfm2-24b-a2b-pp5")
    assert ModelConfig.from_hf_config(cfg).layer_pattern == preset.layer_pattern
    traffic = json.loads((ROOT / "benchmark/traffic/decode-agent.json").read_text())
    assert {k: traffic[k] for k in (
        "generator", "clients", "requests_per_client", "stagger_seconds", "warm_seconds",
        "trace_offset_s", "trace_seconds", "check_lengths",
    )} == {
        "generator": "closed_loop", "clients": 128, "requests_per_client": 8,
        "stagger_seconds": 48, "warm_seconds": 60, "trace_offset_s": 5, "trace_seconds": 4,
        "check_lengths": [3072, 1024],
    }
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]) == (2048, 4096)
    assert traffic["output_tokens"]["value"] == 2048 and "rate_rps" not in traffic


def test_the_lfm2_cell_lists_its_own_attention_metrics_and_not_the_uniform_models():
    """``decode_attn_ms`` / ``decode_attn_roofline`` multiply by
    ``num_hidden_layers`` where 2 layers of 9 attend: the cell is in
    neither list, and reads its attention by ``decode_gqa_*`` instead."""
    cell = "lfm2-24b-a2b-pp5.decode-agent"
    lists = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    assert cell not in lists["decode_attn_ms"] and cell not in lists["decode_attn_roofline"]
    for name in ("decode_gqa_ms", "decode_gqa_roofline", "decode_shortconv_ms"):
        assert lists[name] == [cell], name
    for name in ("decode_step_dev_ms", "decode_moe_ms", "decode_moe_roofline",
                 "moe_tokens_per_expert_mean", "decode_live_pages_mean", "tpot_p95_ms.closed"):
        assert cell in lists[name], name


def test_gqa_decode_cost_by_hand_and_a_reader_with_nothing_to_read():
    """``kernel_cost_gqa``: one live token and one row of one attention
    layer at the published widths (the benchmark's own copy of this proof
    is ``benchmark/tests/test_kernel_cost_gqa.py``); the attention layers
    are counted from ``layer_types``; and the reader leaves the metric out
    where the program gave no spans."""
    from types import SimpleNamespace

    from benchmark import kernel_cost_gqa
    from benchmark.readers import decode_gqa_roofline

    kw = dict(live_tokens=1, rows=1, layers=1, hidden=2048, heads=32, kv_heads=8, head_dim=64)
    assert kernel_cost_gqa.gqa_decode_bytes(**kw) == 2_048 + 12_582_912 + 8_192
    assert kernel_cost_gqa.gqa_decode_flops(**kw) == 8_192 + 12_582_912
    cfg = json.loads((ROOT / "benchmark/configs/lfm2-24b-a2b-pp5.json").read_text())
    assert kernel_cost_gqa.attention_layers(cfg) == 2
    ctx = SimpleNamespace(_span_join=False, peaks={}, live_kv={"tokens": 1, "sequences": 1})
    assert decode_gqa_roofline.read(ctx, program="jit_decode_step", scope="x") is None


def test_the_evabyte_file_keeps_every_published_key_it_does_not_reduce():
    """Every key of the published config (the catalog row's, which the
    worker's preset carries in part) stands in the benchmark's file at its
    published value but for the two under ``reduced``, which stand beside
    their published value; no reduced key is a width; the file states what
    it assumed, its deployment and the arithmetic of its 3.24 GB, and the
    file's own mapping gives the preset."""
    from llmq_tpu.models.config import ModelConfig
    from llmq_tpu.models.presets import _EVABYTE as published
    from llmq_tpu.models.presets import get_preset

    cfg = json.loads((ROOT / "benchmark/configs/evabyte-6.5b-pp4.json").read_text())
    reduced = set(cfg["reduced"])
    assert reduced == {"num_hidden_layers", "num_pred_heads"}
    catalog = dict(
        published, fp32_ln=False, init_cutoff_factor=None, init_fn="v2", init_std=0.01275,
        lazy_init=True, max_seq_length=32768, mixedp_attn=True, num_chunks=None,
        rope_scaling=None,
    )
    for key, value in catalog.items():
        if key in reduced:
            assert cfg[f"{key}_published"] == value and cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_pred_heads"]) == (8, 1)
    assert set(cfg["assumed"]) >= {
        "summaries_of_rotated_keys", "summary_scale", "alignment",
        "summaries_only_of_complete_windows", "residual", "rope", "ids", "prediction_heads",
    }
    assert "1,622 M parameters, 3.24 GB" in cfg["deployment"]
    assert "pipeline stage 0 of FOUR" in cfg["deployment"]
    assert cfg["engine"]["max_num_seqs"] == 24 and cfg["engine"]["max_model_len"] == 12288
    assert ModelConfig.from_hf_config(cfg) == get_preset("evabyte-6.5b-pp4")
    traffic = json.loads((ROOT / "benchmark/traffic/decode-bytes.json").read_text())
    assert {k: traffic[k] for k in (
        "generator", "clients", "requests_per_client", "stagger_seconds", "warm_seconds",
        "schedule_seed", "trace_offset_s", "trace_seconds", "check_lengths",
    )} == {
        "generator": "closed_loop", "clients": 24, "requests_per_client": 8,
        "stagger_seconds": 36, "warm_seconds": 48, "schedule_seed": 42050,
        "trace_offset_s": 5, "trace_seconds": 4, "check_lengths": [6140, 2304],
    }
    assert (traffic["prompt_tokens"]["min"], traffic["prompt_tokens"]["max"]) == (4096, 8192)
    assert traffic["output_tokens"]["value"] == 3072 and "rate_rps" not in traffic
    # the first sample's 8 compared positions cross byte 6,144: a window
    # closes INSIDE the comparison
    first = traffic["check_lengths"][0]
    assert first - 1 < 3 * cfg["window_size"] - 1 < first - 1 + correct.K_TOKENS


def test_the_evabyte_cell_lists_its_own_attention_metrics():
    cell = "evabyte-6.5b-pp4.decode-bytes"
    lists = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    for name in ("decode_eva_ms", "decode_eva_roofline", "eva_summary_rows_share_pct"):
        assert lists[name] == [cell], name
    for name in ("decode_step_dev_ms", "decode_mlp_ms", "decode_live_pages_mean",
                 "tpot_p95_ms.closed", "preemptions.closed", "prefill_dev_share_pct.closed",
                 "prefill_rows_mean.closed", "admit_hold_share_pct.closed"):
        assert lists[name][-1] == cell, name
    for name in ("decode_attn_ms", "decode_gqa_ms", "decode_mla_ms", "decode_moe_ms"):
        assert cell not in lists[name], name
    ends = {m["name"]: m.get("workloads") for m in BENCH["end_to_end"]}
    assert ends["tpot_p50_ms"][-1] == cell and ends["out_tok_s"][-1] == cell


def test_eva_decode_cost_by_hand_and_readers_with_nothing_to_read():
    """``kernel_cost_eva``: one attended row and one sequence of one layer
    at the published widths, its own copy of the row arithmetic against
    the program's (the benchmark's own copy of this proof is
    ``benchmark/tests/test_kernel_cost_eva.py``); and the readers leave
    their metrics out where the program gave no spans."""
    from types import SimpleNamespace

    from benchmark import kernel_cost_eva
    from benchmark.readers import decode_eva_roofline, span_field_share
    from llmq_tpu.ops.attention import eva_context

    kw = dict(attended=1, rows=1, layers=1, hidden=4096, heads=32, head_dim=128)
    assert kernel_cost_eva.eva_decode_bytes(**kw) == 16_384 + 100_663_296 + 16_384
    assert kernel_cost_eva.eva_decode_flops(**kw) == 16_384 + 100_663_296
    for n in (0, 1, 2047, 2048, 2049, 6144, 6145, 9200, 12288):
        assert kernel_cost_eva.attended_rows(n, window=2048, chunk=16) == eva_context(n, 2048, 16)
    ctx = SimpleNamespace(_span_join=False, peaks={})
    assert decode_eva_roofline.read(ctx, program="jit_decode_step", scope="x") is None
    assert span_field_share.read(ctx, name="decode_dispatch", field="a", of=["a", "b"]) is None
