"""Autotune driver logic (``engine/kernel_autotune.py``): gating, the
subprocess contract, and the child-side per-chip cache, through the
tp-overlap probe. The measured A/B itself is hardware-only; here
children/measurers are mocked."""

import json
import subprocess
import types

from llmq_tpu.engine import kernel_autotune as ka

SHAPES = dict(hidden_size=64, intermediate_size=128)
VALID = ("on", "off")
_DETAIL = "kernel-autotune: tp-overlap A/B gspmd=9.0us ring=7.0us per layer -> on"


def _key(identity):
    return ka._tp_overlap_cache_key(64, 128, 192, 4, "bfloat16", identity)


def _fake_run(choice="on", rc=0, detail=_DETAIL):
    def run(argv, timeout, capture_output, text):
        return types.SimpleNamespace(
            returncode=rc, stdout=choice + "\n", stderr=detail + "\n"
        )

    return run


def test_skips_on_cpu_pin(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert ka.autotune_tp_overlap(**SHAPES) is None


def test_disabled_by_flag(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("LLMQ_KERNEL_AUTOTUNE", "0")
    assert ka.autotune_tp_overlap(**SHAPES) is None


def _probe_applies(monkeypatch, *, holds_chip=False):
    """Pretend a TPU host whose calling process has (not) touched JAX."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("LLMQ_KERNEL_AUTOTUNE", raising=False)
    monkeypatch.setattr(
        ka, "_probe_blocked", lambda: "holds the chip" if holds_chip else None
    )


def test_probe_choice_from_child(monkeypatch):
    _probe_applies(monkeypatch)
    monkeypatch.setattr(subprocess, "run", _fake_run("on"))
    assert ka.autotune_tp_overlap(**SHAPES) == "on"


def test_child_failure_is_loud_and_starts_on_default(monkeypatch, capsys):
    """A failed probe is an ERROR with the child's output, never a quiet
    win, and the answer is the default mode: non-zero exit (a candidate
    did not compile), junk answer, timeout."""
    _probe_applies(monkeypatch)
    monkeypatch.setattr(
        subprocess, "run",
        _fake_run("junk", rc=3, detail="MosaicError: kernel refused"),
    )
    assert ka.autotune_tp_overlap(**SHAPES) == "off"
    err = capsys.readouterr().err
    assert "probe FAILED" in err and "exit 3" in err
    assert "MosaicError: kernel refused" in err

    def boom(*a, **k):
        raise subprocess.TimeoutExpired(cmd="x", timeout=1)

    monkeypatch.setattr(subprocess, "run", boom)
    assert ka.autotune_tp_overlap(**SHAPES) == "off"
    assert "probe FAILED: no answer" in capsys.readouterr().err


def test_no_child_from_a_process_that_holds_the_chip(monkeypatch, capsys):
    """One process per chip: once the caller has initialised JAX a child
    that needs the chip can only fail or hang, so none is started."""
    _probe_applies(monkeypatch, holds_chip=True)

    def never(*a, **k):
        raise AssertionError("spawned a child that needs the chip")

    monkeypatch.setattr(subprocess, "run", never)
    assert ka.autotune_tp_overlap(**SHAPES) is None
    assert capsys.readouterr().err.count("probe NOT RUN") == 1


def test_probe_blocked_tracks_backend_init():
    """The real check: this test process has initialised JAX (conftest
    asks for devices), so a child would be refused."""
    import jax

    jax.devices()
    assert "holds the chip" in ka._probe_blocked()


class TestChildCache:
    """resolve_choice: the child-side cache keyed by shapes AND the
    measuring chip/toolchain identity (a checkout may be NFS-shared
    across a fleet mixing chip generations)."""

    def test_default_path_is_inside_the_checkout(self, monkeypatch):
        from pathlib import Path

        monkeypatch.delenv("LLMQ_AUTOTUNE_CACHE", raising=False)
        monkeypatch.setenv("HOME", "/nonexistent-home")
        path = ka.cache_path_from_env()
        repo = Path(ka.__file__).resolve().parents[2]
        assert repo in path.parents and "nonexistent-home" not in str(path)

    def test_measure_then_cache_roundtrip(self, monkeypatch, tmp_path):
        cache = tmp_path / "autotune.json"
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", str(cache))
        calls = []

        def measure():
            calls.append(1)
            return "on", True

        def resolve(identity):
            return ka.resolve_choice(measure, key=_key(identity), valid=VALID)

        assert resolve("TPU_v5e/jax0.9") == "on" and len(calls) == 1
        (key,) = json.loads(cache.read_text()).keys()
        assert key.startswith("tpovl:h64:i128:s192:tp4:bfloat16")
        assert key.endswith("TPU_v5e/jax0.9")

        # Same shapes + same identity: served from cache, no re-measure.
        assert resolve("TPU_v5e/jax0.9") == "on" and len(calls) == 1

        # Same shapes, DIFFERENT chip: cache miss, measured again.
        assert resolve("TPU_v4/jax0.9") == "on" and len(calls) == 2
        assert len(json.loads(cache.read_text())) == 2

        # Toolchain upgrade: also a miss.
        resolve("TPU_v5e/jax0.10")
        assert len(calls) == 3

    def test_unmeasured_fallback_not_cached(self, monkeypatch, tmp_path):
        cache = tmp_path / "autotune.json"
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", str(cache))
        got = ka.resolve_choice(
            lambda: ("off", False), key=_key("TPU_v5e/jax0.9"), valid=VALID
        )
        assert got == "off"
        assert not cache.exists()

    def test_disabled_cache_always_measures(self, monkeypatch):
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", "0")
        calls = []

        def measure():
            calls.append(1)
            return "on", True

        for _ in range(2):
            got = ka.resolve_choice(measure, key=_key("x/y"), valid=VALID)
            assert got == "on"
        assert len(calls) == 2

    def test_corrupt_cache_re_measures(self, monkeypatch, tmp_path):
        cache = tmp_path / "autotune.json"
        cache.write_text("{not json")
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", str(cache))
        got = ka.resolve_choice(
            lambda: ("on", True), key=_key("TPU_v5e/jax0.9"), valid=VALID
        )
        assert got == "on"
        assert json.loads(cache.read_text())  # rewritten valid
