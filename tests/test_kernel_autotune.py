"""Autotune driver logic (``engine/kernel_autotune.py``): gating, the
subprocess contract, and the child-side per-chip cache. The measured A/B
itself is hardware-only; here children/measurers are mocked."""

import json
import subprocess
import types

import pytest

from llmq_tpu.engine import kernel_autotune as ka

SHAPES = dict(num_heads=8, num_kv_heads=2, head_dim=64, num_layers=4)
SHAPE_TUPLE = (8, 2, 64, 4, 192, 128)
_DETAIL = "kernel-autotune: decode A/B v1=1ms v2=0.5ms v3=0.6ms per layer -> v2"


def _fake_run(choice="v2", rc=0, detail=_DETAIL):
    def run(argv, timeout, capture_output, text):
        return types.SimpleNamespace(
            returncode=rc, stdout=choice + "\n", stderr=detail + "\n"
        )

    return run


def test_respects_explicit_env(monkeypatch):
    monkeypatch.setenv("LLMQ_DECODE_KERNEL", "v3")
    assert ka.autotune_decode_kernel(**SHAPES) is None


def test_skips_on_cpu_pin(monkeypatch):
    monkeypatch.delenv("LLMQ_DECODE_KERNEL", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert ka.autotune_decode_kernel(**SHAPES) is None


def test_disabled_by_flag(monkeypatch):
    monkeypatch.delenv("LLMQ_DECODE_KERNEL", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("LLMQ_KERNEL_AUTOTUNE", "0")
    assert ka.autotune_decode_kernel(**SHAPES) is None


def _probe_applies(monkeypatch, *, holds_chip=False):
    """Pretend a TPU host whose calling process has (not) touched JAX."""
    monkeypatch.delenv("LLMQ_DECODE_KERNEL", raising=False)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.delenv("LLMQ_KERNEL_AUTOTUNE", raising=False)
    monkeypatch.setattr(
        ka, "_probe_blocked", lambda: "holds the chip" if holds_chip else None
    )


def test_probe_choice_from_child(monkeypatch):
    _probe_applies(monkeypatch)
    monkeypatch.setattr(subprocess, "run", _fake_run("v2"))
    assert ka.autotune_decode_kernel(**SHAPES) == "v2"


def test_child_failure_is_loud_and_starts_on_default(monkeypatch, capsys):
    """A failed probe is an ERROR with the child's output, never a quiet
    win, and the answer is the default kernel: non-zero exit (a kernel
    did not compile), junk answer, timeout."""
    _probe_applies(monkeypatch)
    monkeypatch.setattr(
        subprocess, "run",
        _fake_run("junk", rc=3, detail="MosaicError: kernel refused"),
    )
    assert ka.autotune_decode_kernel(**SHAPES) == "live"
    err = capsys.readouterr().err
    assert "probe FAILED" in err and "exit 3" in err
    assert "MosaicError: kernel refused" in err

    def boom(*a, **k):
        raise subprocess.TimeoutExpired(cmd="x", timeout=1)

    monkeypatch.setattr(subprocess, "run", boom)
    assert ka.autotune_decode_kernel(**SHAPES) == "live"
    assert "probe FAILED: no answer" in capsys.readouterr().err


def test_no_child_from_a_process_that_holds_the_chip(monkeypatch, capsys):
    """One process per chip: once the caller has initialised JAX a child
    that needs the chip can only fail or hang, so none is started."""
    _probe_applies(monkeypatch, holds_chip=True)

    def never(*a, **k):
        raise AssertionError("spawned a child that needs the chip")

    monkeypatch.setattr(subprocess, "run", never)
    assert ka.autotune_decode_kernel(**SHAPES) is None
    assert ka.autotune_tp_overlap(
        hidden_size=64, intermediate_size=128
    ) is None
    assert capsys.readouterr().err.count("probe NOT RUN") == 2


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
            return "v2", True

        got = ka.resolve_choice(SHAPE_TUPLE, "TPU_v5e/jax0.9", measure)
        assert got == "v2" and len(calls) == 1
        (key,) = json.loads(cache.read_text()).keys()
        assert key.startswith("decode:h8:kv2:d64:l4:s192:p128")
        assert key.endswith("TPU_v5e/jax0.9")

        # Same shapes + same identity: served from cache, no re-measure.
        got = ka.resolve_choice(SHAPE_TUPLE, "TPU_v5e/jax0.9", measure)
        assert got == "v2" and len(calls) == 1

        # Same shapes, DIFFERENT chip: cache miss, measured again.
        got = ka.resolve_choice(SHAPE_TUPLE, "TPU_v4/jax0.9", measure)
        assert got == "v2" and len(calls) == 2
        assert len(json.loads(cache.read_text())) == 2

        # Toolchain upgrade: also a miss.
        ka.resolve_choice(SHAPE_TUPLE, "TPU_v5e/jax0.10", measure)
        assert len(calls) == 3

    def test_unmeasured_fallback_not_cached(self, monkeypatch, tmp_path):
        cache = tmp_path / "autotune.json"
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", str(cache))
        got = ka.resolve_choice(
            SHAPE_TUPLE, "TPU_v5e/jax0.9", lambda: ("v1", False)
        )
        assert got == "v1"
        assert not cache.exists()

    def test_disabled_cache_always_measures(self, monkeypatch):
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", "0")
        calls = []

        def measure():
            calls.append(1)
            return "v3", True

        assert ka.resolve_choice(SHAPE_TUPLE, "x/y", measure) == "v3"
        assert ka.resolve_choice(SHAPE_TUPLE, "x/y", measure) == "v3"
        assert len(calls) == 2

    def test_corrupt_cache_re_measures(self, monkeypatch, tmp_path):
        cache = tmp_path / "autotune.json"
        cache.write_text("{not json")
        monkeypatch.setenv("LLMQ_AUTOTUNE_CACHE", str(cache))
        got = ka.resolve_choice(
            SHAPE_TUPLE, "TPU_v5e/jax0.9", lambda: ("v2", True)
        )
        assert got == "v2"
        assert json.loads(cache.read_text())  # rewritten valid


def test_run_ab_off_tpu_is_unmeasured():
    """On the CPU backend run_ab must report measured=False so the child
    never caches the unmeasured default."""
    pytest.importorskip("jax")
    choice, measured = ka.run_ab(
        num_heads=4, num_kv_heads=2, head_dim=8, num_layers=1,
        max_seqs=2, page_size=8,
    )
    assert choice == "live" and measured is False
