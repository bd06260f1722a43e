"""End-to-end probe of the pipeline-parallel serving plane.

Three legs, each printing a ``probe: <leg> ok`` line:

1. **parity** — a pp=2 staged engine (per-stage executables over ICI
   submeshes, chained by host stage hops) is TOKEN-IDENTICAL to pp=1
   for every row — greedy, seeded stochastic, and filtered sampling —
   and the boundary counters show real stage traffic.
2. **two-tier** — the DCN-shaped mesh (pp outer over hosts, tp inner
   per host): pp=2 x tp=2 holds greedy parity. Skipped with a note when
   fewer than 4 devices answer (single-chip sessions).
3. **wire** — ``LLMQ_PP_WIRE=1`` routes every stage-boundary activation
   through the snapshot wire codec (serialize -> frame -> digest check
   -> decode), the in-process stand-in for the tcp:// hop between stage
   hosts; parity must stay exact and the engine must report the codec
   path was taken.

Runs on real devices in the hardware-session ladders; on CPU (preflight)
it forces 8 virtual devices so the staged meshes exist.

    python tools/pp_probe.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Preflight runs this off-accelerator; the staged meshes need >1 device,
# so give the CPU platform virtual devices BEFORE jax initializes.
if os.environ.get("JAX_PLATFORMS") == "cpu" and (
    "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", "")
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from __graft_entry__ import _engine_run  # noqa: E402
from llmq_tpu.parallel.pipeline import (  # noqa: E402
    boundary_bytes_per_token,
    bubble_fraction,
)


def _assert_rows(ref, got, what):
    for rid in ref:
        assert got[rid] == ref[rid], (
            f"{what} diverged for {rid!r}: {ref[rid]} -> {got[rid]}"
        )


def run_parity_leg(ref):
    got, _ = _engine_run(1, 1, 1, pp=2)
    st = _engine_run.engine_stats
    assert st["pp_stages"] == 2, st
    assert st["pp_boundary_transfers"] > 0, "no stage-boundary traffic"
    assert st["pp_boundary_bytes"] > 0
    assert st["pp_wire"] == "device", st["pp_wire"]
    _assert_rows(ref, got, "pp=2")
    print(
        f"probe: parity leg ok — pp=2 token-identical to pp=1 on all "
        f"rows (greedy+seeded), {st['pp_boundary_transfers']} boundary "
        f"hops / {st['pp_boundary_bytes']} bytes, bubble fraction "
        f"{st['pp_bubble_fraction']:.3f} "
        f"(GPipe (pp-1)/(m+pp-1); {boundary_bytes_per_token(64)} "
        f"activation bytes/token at the tiny width)"
    )


def run_two_tier_leg(ref):
    if len(jax.devices()) < 4:
        print(
            "probe: two-tier leg skipped — "
            f"{len(jax.devices())} device(s), pp=2 x tp=2 needs 4"
        )
        return False
    got, _ = _engine_run(1, 1, 2, pp=2)
    for rid in ("a", "long"):
        assert got[rid] == ref[rid], (
            f"pp=2 x tp=2 diverged for {rid!r}: {ref[rid]} -> {got[rid]}"
        )
    print(
        "probe: two-tier leg ok — pp=2 outer x tp=2 inner (the "
        "DCN-over-hosts shape) holds greedy parity"
    )
    return True


def run_wire_leg(ref):
    os.environ["LLMQ_PP_WIRE"] = "1"
    try:
        got, _ = _engine_run(1, 1, 1, pp=2)
    finally:
        del os.environ["LLMQ_PP_WIRE"]
    st = _engine_run.engine_stats
    assert st["pp_wire"] == "codec", st["pp_wire"]
    assert st["pp_boundary_transfers"] > 0
    _assert_rows(ref, got, "pp=2 wire codec")
    print(
        f"probe: wire leg ok — {st['pp_boundary_transfers']} boundary "
        f"activations round-tripped the snapshot wire codec "
        f"(frame+digest), parity exact"
    )


def main():
    from llmq_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()  # before the first compile
    assert bubble_fraction(4, 2) == 1 / 5  # host-side math sanity
    if len(jax.devices()) < 2:
        print(
            "pp_probe: single-device session — staged meshes need >= 2 "
            "devices; skipping (run preflight's CPU leg for the parity "
            "proof)"
        )
        print("metric: pp_probe_ok legs=0")
        return
    ref, _ = _engine_run(1, 1, 1)
    run_parity_leg(ref)
    two_tier = run_two_tier_leg(ref)
    run_wire_leg(ref)
    print(f"metric: pp_probe_ok legs={2 + int(two_tier)}")


if __name__ == "__main__":
    main()
