"""bench.py orchestration logic (no accelerator needed).

The headline benchmark is the round's reporting artifact, so its
decision logic — quantized-attempt parsing, failure-line fallbacks,
preset picking — gets unit coverage beyond the CPU smoke runs.
"""

import json
import subprocess

import pytest

import bench

pytestmark = pytest.mark.unit


def _completed(stdout: str, stderr: str = "", rc: int = 0):
    return subprocess.CompletedProcess(
        args=["bench"], returncode=rc, stdout=stdout, stderr=stderr
    )


@pytest.fixture(autouse=True)
def _reset_fallback():
    bench._QUANT_FALLBACK = None
    yield
    bench._QUANT_FALLBACK = None


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class TestBackendStamp:
    """A metric line says which device measured it, as JAX reports it;
    and there is no line at all from a run that wanted a chip and did
    not get one (bench.init_devices)."""

    def test_chip_run_is_stamped_with_the_device(self):
        stamp = bench._backend_stamp([_Dev("tpu", "TPU v5 lite")])
        assert stamp == {
            "platform": "tpu", "device_kind": "TPU v5 lite", "count": 1,
        }

    def test_no_chip_exits_nonzero_instead_of_falling_back(self, monkeypatch):
        # JAX came up on the CPU although nobody asked for it (chip absent
        # or held): no CPU number may come out under the bench's name.
        import jax

        from llmq_tpu.utils import platform

        monkeypatch.setattr(platform, "cpu_requested", lambda: False)
        monkeypatch.setattr(jax, "devices", lambda: [_Dev("cpu", "cpu")])
        with pytest.raises(SystemExit) as exit_info:
            bench.init_devices()
        assert exit_info.value.code not in (0, None)
        assert "not a TPU" in str(exit_info.value.code)

    def test_requested_cpu_is_the_cpu_not_a_failure(self):
        # JAX_PLATFORMS=cpu (tests, CI): the rehearsal runs, and is
        # stamped as what it is.
        _jax, devices = bench.init_devices()
        stamp = bench._backend_stamp(devices)
        assert stamp["platform"] == "cpu" and stamp["count"] == len(devices)

    def test_stamp_is_json_serializable(self):
        stamp = bench._backend_stamp([_Dev("tpu", "TPU v5 lite")] * 4)
        assert json.loads(json.dumps(stamp)) == stamp
        assert stamp["count"] == 4


class TestQuantAttemptParsing:
    def _patch_run(self, monkeypatch, proc=None, exc=None):
        def fake_run(*a, **kw):
            if exc is not None:
                raise exc
            return proc

        # bench imports subprocess inside the function, so patching the
        # real module's run is what it sees.
        monkeypatch.setattr(subprocess, "run", fake_run)

    def test_valid_payload_returned(self, monkeypatch):
        payload = {"metric": "m", "value": 5000.0, "vs_baseline": 1.2}
        self._patch_run(
            monkeypatch, _completed("noise\n" + json.dumps(payload) + "\n")
        )
        assert bench._try_quantized_headline() == payload

    def test_error_payload_rejected(self, monkeypatch):
        payload = {"metric": "m", "value": 0.0, "error": "boom"}
        self._patch_run(monkeypatch, _completed(json.dumps(payload)))
        assert bench._try_quantized_headline() is None

    def test_no_json_rejected(self, monkeypatch):
        self._patch_run(monkeypatch, _completed("no json here\n"))
        assert bench._try_quantized_headline() is None

    def test_timeout_rejected(self, monkeypatch):
        self._patch_run(
            monkeypatch,
            exc=subprocess.TimeoutExpired(cmd="bench", timeout=1),
        )
        assert bench._try_quantized_headline() is None

    def test_last_json_line_wins(self, monkeypatch):
        early = {"metric": "m", "value": 1.0, "vs_baseline": 0.1}
        final = {"metric": "m", "value": 2.0, "vs_baseline": 0.2}
        out = json.dumps(early) + "\n" + json.dumps(final) + "\n"
        self._patch_run(monkeypatch, _completed(out))
        assert bench._try_quantized_headline() == final


class TestFailureEmit:
    def test_plain_failure_line(self, capsys):
        bench._emit_failure("failed", "boom")
        line = json.loads(capsys.readouterr().out.strip())
        assert line["value"] == 0.0
        assert line["error"] == "boom"

    def test_failure_prefers_quant_fallback(self, capsys):
        bench._QUANT_FALLBACK = {
            "metric": "decode_tokens_per_sec_per_chip[qwen2.5-3b]",
            "value": 4800.0,
            "vs_baseline": 1.02,
        }
        bench._emit_failure("failed", "RESOURCE_EXHAUSTED")
        line = json.loads(capsys.readouterr().out.strip())
        assert line["value"] == 4800.0
        assert "bf16 run failed" in line["note"]
        assert "error" not in line


class TestPickPreset:
    def test_cpu_is_tiny(self):
        assert bench.pick_preset(None, "cpu") == "tiny"

    def test_16gb_bf16_picks_3b(self):
        assert bench.pick_preset(16 * 2**30, "tpu") == "qwen2.5-3b"

    def test_16gb_int8_picks_9b(self):
        assert bench.pick_preset(16 * 2**30, "tpu", int8=True) == (
            "tower-plus-9b"
        )

    def test_16gb_int4_picks_9b(self):
        assert bench.pick_preset(16 * 2**30, "tpu", int4=True) == (
            "tower-plus-9b"
        )

    def test_8gb_int4_beats_int8_preset(self):
        # Quartered weight bytes admit a larger architecture than int8
        # on the same HBM.
        gb8 = 8 * 2**30
        assert bench.pick_preset(gb8, "tpu", int4=True) == "qwen2.5-7b"
        assert bench.pick_preset(gb8, "tpu", int8=True) == "qwen2.5-3b"


class TestNoBorrowedNumbers:
    """What replaced the CPU-fallback re-emit: the bench prints what it
    measured on the device it ran on, or nothing. No old hardware line is
    re-emitted under a new run, no peak is assumed for a device the
    table does not know, no backend failure turns into a metric line."""

    def test_no_backend_exits_nonzero(self, monkeypatch):
        import jax

        def boom():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(SystemExit) as exit_info:
            bench.init_devices()
        assert "no backend" in str(exit_info.value.code)

    def test_known_device_kinds_have_their_published_peak(self):
        assert bench.peak_flops_per_chip([_Dev("tpu", "TPU v5 lite")]) == 197e12
        assert bench.peak_flops_per_chip([_Dev("tpu", "TPU v5e")]) == 197e12
        assert bench.peak_flops_per_chip([_Dev("tpu", "TPU v4")]) == 275e12

    def test_unknown_device_kind_is_an_error_not_a_default(self):
        for dev in (_Dev("tpu", "TPU v9 mystery"), _Dev("cpu", "cpu")):
            with pytest.raises(ValueError, match="no peak FLOP/s on record"):
                bench.peak_flops_per_chip([dev])
        assert not hasattr(bench, "_last_hardware_metric_line")


class TestTrimPlan:
    """bench.trim_plan: budget-aware phase trimming against the seconds
    left on LLMQ_BENCH_DEADLINE. The proven bf16 headline is reserved
    first and never dropped; speculative phases drop the serve rung
    first (diagnostic only — it prices the latency plane, never the
    headline), then the pp rung
    (diagnostic only — the model fits one host here), then the disagg
    rung (diagnostic, most builds per datapoint), then the prefix
    rung (also diagnostic — it never replaces the headline), then the
    int4 attempt, then the tp-overlap rung, then quant, then the
    spec-decode rung, then the mixed-step rung, then the extra ladder
    rungs."""

    KW = dict(quant_s=1500.0, ladder_extra_s=720.0,
              spec_s=360.0, tp_overlap_s=240.0, proven_s=300.0,
              int4_s=1500.0, mixed_s=300.0, prefix_s=240.0,
              disagg_s=420.0, pp_s=300.0, serve_s=240.0)
    ALL = {"quant": True, "full_ladder": True,
           "spec_ladder": True, "tp_overlap": True, "int4_ladder": True,
           "mixed_step": True, "prefix_rung": True, "disagg_rung": True,
           "pp_rung": True, "serve_rung": True}
    # Remaining-seconds sweep covering every drop boundary (phase sums
    # + the 300 s proven floor): see the per-test comments.
    SWEEP = (300.0, 350.0, 1000.0, 1020.0, 1080.0, 1320.0, 1480.0,
             1680.0, 2080.0, 3180.0, 3280.0, 3420.0, 3580.0, 4920.0,
             4980.0, 5160.0, 5400.0, 5580.0, 5880.0, 6120.0, 6180.0)

    def test_no_deadline_runs_everything(self):
        assert bench.trim_plan(None, **self.KW) == self.ALL

    def test_roomy_budget_runs_everything(self):
        # 300 (proven) + 240 (serve) + 300 (pp) + 420 (disagg)
        # + 240 (prefix) + 1500 (int4) + 240 + 1500 + 360 + 300 + 720
        # = 6120 fits.
        assert bench.trim_plan(6120.0, **self.KW) == self.ALL

    def test_serve_rung_dropped_first(self):
        # Everything but the serve rung fits (5580 after the floor),
        # + 240 does not.
        plan = bench.trim_plan(5880.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False}

    def test_pp_rung_dropped_second(self):
        # After shedding the serve rung, everything but the pp rung
        # fits (5280 after the floor), + 300 does not.
        plan = bench.trim_plan(5580.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False}

    def test_disagg_rung_dropped_third(self):
        # After shedding the serve + pp rungs, everything but the
        # disagg rung fits (4860 after the floor), + 420 does not.
        plan = bench.trim_plan(5400.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False}

    def test_prefix_rung_dropped_fourth(self):
        # After shedding the serve + pp + disagg rungs, everything but
        # the prefix rung fits (4620 after the floor), + 240 does not.
        plan = bench.trim_plan(4980.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False,
                        "disagg_rung": False, "prefix_rung": False}

    def test_int4_dropped_fifth(self):
        # Everything through the ladder fits (3120 after the floor),
        # + 1500 (int4) does not.
        plan = bench.trim_plan(3580.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False,
                        "prefix_rung": False, "int4_ladder": False}

    def test_tp_overlap_dropped_sixth(self):
        plan = bench.trim_plan(3280.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False,
                        "prefix_rung": False, "int4_ladder": False,
                        "tp_overlap": False}

    def test_quant_dropped_seventh(self):
        # 300 (proven) + 720 + 360 + 300 fits, + 1500 does not.
        plan = bench.trim_plan(2080.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False,
                        "prefix_rung": False, "int4_ladder": False,
                        "tp_overlap": False, "quant": False}

    def test_spec_rung_dropped_eighth(self):
        # 300 + 720 + 300 fits, + 360 (spec rung) does not.
        plan = bench.trim_plan(1480.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False,
                        "prefix_rung": False, "int4_ladder": False,
                        "tp_overlap": False, "quant": False,
                        "spec_ladder": False}

    def test_mixed_rung_dropped_ninth(self):
        # 300 + 720 fits, + 300 (mixed rung) does not.
        plan = bench.trim_plan(1080.0, **self.KW)
        assert plan == {**self.ALL, "serve_rung": False,
                        "pp_rung": False, "disagg_rung": False,
                        "prefix_rung": False, "int4_ladder": False,
                        "tp_overlap": False, "quant": False,
                        "spec_ladder": False, "mixed_step": False}

    def test_ladder_dropped_tenth(self):
        # The last phase to go: 300 + 720 does not fit.
        plan = bench.trim_plan(1000.0, **self.KW)
        assert plan == {k: False for k in self.ALL}

    def test_everything_but_proven_dropped(self):
        plan = bench.trim_plan(350.0, **self.KW)
        assert plan == {k: False for k in self.ALL}

    def test_proven_floor_reserved_before_phases(self):
        # Exactly the full phase sum of budget but NO room for the
        # proven floor on top -> the floor wins, the serve rung goes.
        plan = bench.trim_plan(5820.0, **self.KW)
        assert plan["serve_rung"] is False

    def test_boundaries_inclusive(self):
        assert bench.trim_plan(6120.0, **self.KW)["serve_rung"] is True
        assert bench.trim_plan(5880.0, **self.KW)["pp_rung"] is True
        assert bench.trim_plan(5580.0, **self.KW)["disagg_rung"] is True
        assert bench.trim_plan(5160.0, **self.KW)["prefix_rung"] is True
        assert bench.trim_plan(4920.0, **self.KW)["int4_ladder"] is True
        assert bench.trim_plan(3420.0, **self.KW)["tp_overlap"] is True
        assert bench.trim_plan(3180.0, **self.KW)["quant"] is True
        assert bench.trim_plan(1680.0, **self.KW)["spec_ladder"] is True
        assert bench.trim_plan(1320.0, **self.KW)["mixed_step"] is True
        assert bench.trim_plan(1020.0, **self.KW)["full_ladder"] is True

    def test_drop_order_invariants(self):
        # A more speculative phase never survives a less speculative
        # one's drop, at any budget.
        order = ("serve_rung", "pp_rung", "disagg_rung", "prefix_rung",
                 "int4_ladder",
                 "tp_overlap", "quant", "spec_ladder", "mixed_step",
                 "full_ladder")
        for remaining in self.SWEEP:
            plan = bench.trim_plan(remaining, **self.KW)
            for earlier, later in zip(order, order[1:]):
                assert not (plan[earlier] and not plan[later]), (
                    remaining, earlier, later, plan
                )

    def test_legacy_defaults_omit_new_rungs_free(self):
        # Callers that never pass int4_s/mixed_s/prefix_s/disagg_s/
        # pp_s/serve_s get them at zero cost: the keys exist but never
        # consume budget.
        kw = dict(quant_s=1500.0, ladder_extra_s=720.0,
                  spec_s=360.0, tp_overlap_s=240.0, proven_s=300.0)
        plan = bench.trim_plan(3120.0, **kw)
        assert plan["tp_overlap"] is True and plan["int4_ladder"] is True
        assert plan["prefix_rung"] is True
        assert plan["disagg_rung"] is True
        assert plan["pp_rung"] is True
        assert plan["serve_rung"] is True
