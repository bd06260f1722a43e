"""Tier-B SPMD repartition diff gate: lowered-HLO collective signatures.

The AST rules (``sharding-axis``, ``unconstrained-repartition``) catch
the *source shape* of the MoE mixed-mesh bug; this module catches the
*compiled consequence*. GSPMD decides the actual partitioning only at
lowering time, so a regression that re-introduces a silent repartition —
deleting a ``with_sharding_constraint`` pin, adding an op whose free
layout choice back-propagates — shows up as **new collectives** in the
partitioned HLO long before it shows up as wrong tokens.

The gate lowers the **engine's own jitted steps** (``prefill`` /
``prefill1`` / ``decode`` / ``mixed`` / ``verify``) for the tiny MoE
preset across the
measured mesh matrix, extracts a canonical collective signature from
the *compiled* HLO (post-partitioning — the pre-partitioning StableHLO
has no collectives), and diffs it against the recorded baseline in
``spmd_baseline.json``. Lowering the engine's jits rather than bare
model calls is load-bearing: the MoE mixed-mesh repartition only
materializes inside the engine's composition (sampling fused into the
step, donated KV, decode-state out_shardings) — a standalone
``model.prefill`` jit lowers to the same collectives with and without
the token-axis pins, i.e. a model-level gate has no teeth. Signature:

- per program and mesh, counts of ``all-reduce`` / ``all-gather`` /
  ``all-to-all`` / ``collective-permute`` / ``reduce-scatter`` keyed by
  the mesh axes the collective moves data over (recovered from
  ``replica_groups`` / ``source_target_pairs`` device coordinates);
- any *new* collective kind/axis key, or a count increase, fails the
  gate and names the nearest op via HLO ``op_name`` metadata (which
  carries the jax source path, e.g. ``...transformer.py:271``);
- count *decreases* pass with a note (fewer collectives is an
  improvement — re-record to ratify it).

Runs on CPU with 8 virtual devices (``run_gate_subprocess`` forces the
environment in a fresh interpreter, because ``XLA_FLAGS`` must be set
before jax initializes). Exposed as ``llmq-tpu lint --spmd`` /
``--spmd-record`` and as legs of ``tools/shardcheck_probe.py``.

Subset knobs for time-bounded callers (probe legs, unit tests):
``LLMQ_SPMD_MESHES="2x2x2,1x2x4"`` and
``LLMQ_SPMD_PROGRAMS="prefill,decode"`` (or the equivalent CLI flags).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

#: The measured mesh matrix from tests/test_moe_mixed_mesh.py: the three
#: known-good meshes plus the five that diverged before the token-axis
#: pins landed (PR 17). Shapes are ``(dp, sp, tp)`` or — for the
#: pipeline rows — ``(dp, sp, tp, pp)``; pp rows lower every per-stage
#: executable plus the head program and merge the counts, and the axis
#: attribution asserts no collective ever carries a ``pp`` label (stage
#: boundaries move data by explicit host transfer, never a collective).
MESH_MATRIX: Tuple[Tuple[int, ...], ...] = (
    (2, 1, 1),
    (1, 2, 1),
    (2, 1, 4),
    (1, 2, 4),
    (2, 2, 1),
    (2, 2, 2),
    (2, 4, 1),
    (4, 2, 1),
    (1, 1, 1, 2),
    (1, 1, 2, 2),
)

#: ``prefill`` is the batched executable (B = max_prefill_batch);
#: ``prefill1`` is the single-row one the engine compiles separately
#: (``_prefill_chunk`` pads to {1, max_prefill_batch} rows). They
#: partition differently — the MoE mixed-mesh repartition only appears
#: in the B=1 long-prompt module — so the gate signs both.
PROGRAMS: Tuple[str, ...] = (
    "prefill", "prefill1", "decode", "mixed", "verify"
)

BASELINE_PATH = Path(__file__).with_name("spmd_baseline.json")

# Engine dims mirror the dryrun MoE mixed-mesh leg (__graft_entry__):
# 64-position prefill bucket so the sp-sharded ring pass spans multiple
# KV pages per shard, 8-token mixed chunks, 2-candidate speculation for
# the verify program.
_MAX_MODEL_LEN = 64
_PAGE_SIZE = 8
_NUM_PAGES = 64
_MIN_PREFILL_BUCKET = 16
_MIXED_CHUNK = 8
_SPEC_TOKENS = 2

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|collective-permute|"
    r"reduce-scatter|collective-broadcast)(?:-start)?\("
)
_BRACE_GROUPS_RE = re.compile(r"replica_groups=(\{\{[0-9,{} ]*\}\})")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?"
)
_PAIRS_RE = re.compile(r"source_target_pairs=(\{\{[0-9,{} ]*\}\})")
_OP_NAME_RE = re.compile(r'op_name="([^"]+)"')
_SOURCE_RE = re.compile(r'source_file="([^"]+)"[^"]*source_line=(\d+)')


def mesh_key(shape: Tuple[int, ...]) -> str:
    return "x".join(str(n) for n in shape)


def parse_mesh_key(key: str) -> Tuple[int, ...]:
    """``"2x2x2"`` → (dp, sp, tp); ``"1x1x2x2"`` → (dp, sp, tp, pp)."""
    parts = tuple(int(part) for part in key.split("x"))
    if len(parts) not in (3, 4):
        raise ValueError(f"mesh key {key!r} must have 3 or 4 components")
    return parts


def mesh_pp_degree(shape: Tuple[int, ...]) -> int:
    return shape[3] if len(shape) > 3 else 1


def programs_for_shape(
    shape: Tuple[int, ...], programs: Sequence[str]
) -> List[str]:
    """Speculative verify is gated off under pp (the engine raises), so
    pp rows sign every program except ``verify``."""
    if mesh_pp_degree(shape) > 1:
        return [p for p in programs if p != "verify"]
    return list(programs)


def program_key(program: str, shape: Tuple[int, ...]) -> str:
    return f"{program}@{mesh_key(shape)}"


# ---------------------------------------------------------------------------
# HLO parsing → collective signature
# ---------------------------------------------------------------------------


def _parse_brace_groups(text: str) -> List[List[int]]:
    return [
        [int(n) for n in grp.split(",") if n.strip()]
        for grp in re.findall(r"\{([0-9, ]+)\}", text)
    ]


def _expand_iota_groups(
    g: int, s: int, dims: List[int], perm: Optional[List[int]]
) -> List[List[int]]:
    """Expand the iota replica-group form ``[G,S]<=[dims]T(perm)``:
    arange(prod(dims)) reshaped to ``dims``, transposed by ``perm``,
    reshaped to G rows of S."""
    total = 1
    for d in dims:
        total *= d
    ids = list(range(total))
    if perm is not None and perm != list(range(len(dims))):
        # Compute the transposed flat order without numpy: element at
        # multi-index m (in transposed dims) comes from source index
        # with coordinates m permuted back.
        tdims = [dims[p] for p in perm]
        strides = [0] * len(dims)
        acc = 1
        for i in range(len(dims) - 1, -1, -1):
            strides[i] = acc
            acc *= dims[i]
        out = []
        idx = [0] * len(tdims)
        for _ in range(total):
            src = sum(strides[perm[i]] * idx[i] for i in range(len(tdims)))
            out.append(src)
            for i in range(len(tdims) - 1, -1, -1):
                idx[i] += 1
                if idx[i] < tdims[i]:
                    break
                idx[i] = 0
        ids = out
    return [ids[i * s : (i + 1) * s] for i in range(g)]


def _axes_label(groups: List[List[int]], shape: Tuple[int, ...]) -> str:
    """Mesh axes a set of device groups moves data over.

    Device ids follow ``make_mesh``'s (dp, sp, tp) row-major grid, so a
    group's coordinates vary exactly on the axes the collective spans:
    tp groups are stride-1 runs, sp groups stride tp, dp groups stride
    sp*tp, and multi-axis collectives vary several coordinates. Under
    pp the per-stage executables are compiled over 3-axis submeshes
    whose participant ids live in [0, dp*sp*tp) — an id at or beyond
    that range means a group straddles a stage boundary, which labels
    the collective ``pp`` and fails the gate (stage-to-stage data moves
    by explicit host transfer, never by collective).
    """
    from llmq_tpu.parallel.mesh import AXIS_NAMES  # (dp, sp, tp, pp)

    dp, sp, tp = shape[:3]
    inner = dp * sp * tp
    varying = set()
    for group in groups:
        coords = [
            ((i % inner) // (sp * tp), ((i % inner) // tp) % sp,
             (i % inner) % tp, i // inner)
            for i in group
        ]
        for axis_idx, name in enumerate(AXIS_NAMES):
            if len({c[axis_idx] for c in coords}) > 1:
                varying.add(name)
    label = "+".join(name for name in AXIS_NAMES if name in varying)
    return label or "self"


def _groups_from_line(line: str) -> Optional[List[List[int]]]:
    m = _BRACE_GROUPS_RE.search(line)
    if m:
        return _parse_brace_groups(m.group(1))
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        g, s = int(m.group(1)), int(m.group(2))
        dims = [int(n) for n in m.group(3).split(",")]
        perm = (
            [int(n) for n in m.group(4).split(",")] if m.group(4) else None
        )
        return _expand_iota_groups(g, s, dims, perm)
    m = _PAIRS_RE.search(line)
    if m:
        # collective-permute: treat each (src, tgt) pair as a 2-group so
        # the axis attribution sees which coordinate the hop crosses.
        return _parse_brace_groups(m.group(1))
    return None


def signature_from_hlo(
    hlo_text: str, shape: Tuple[int, int, int]
) -> Tuple[Dict[str, int], Dict[str, str]]:
    """(collective counts keyed ``kind@axes``, example nearest-op per key)."""
    counts: Dict[str, int] = {}
    ops: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        kind = m.group(1)
        groups = _groups_from_line(line)
        axes = _axes_label(groups, shape) if groups else "unattributed"
        if axes == "self":
            continue  # degenerate single-device groups move nothing
        key = f"{kind}@{axes}"
        counts[key] = counts.get(key, 0) + 1
        if key not in ops:
            name = _OP_NAME_RE.search(line)
            src = _SOURCE_RE.search(line)
            where = (
                f"{Path(src.group(1)).name}:{src.group(2)}" if src else "?"
            )
            ops[key] = f"{name.group(1) if name else '?'} ({where})"
    return counts, ops


# ---------------------------------------------------------------------------
# Program construction and lowering
# ---------------------------------------------------------------------------


def tiny_moe_config():
    """The dryrun tiny MoE preset (qwen2_moe family): grouped-matmul
    expert path + shared expert — the exact config the mixed-mesh parity
    matrix is measured on."""
    from llmq_tpu.models.config import ModelConfig

    return ModelConfig.tiny(
        vocab_size=512,
        hidden_size=128,
        num_layers=2,
        num_heads=8,
        num_kv_heads=4,
        intermediate_size=256,
        attention_bias=True,
        model_type="qwen2_moe",
        num_experts=8,
        num_experts_per_tok=2,
        moe_intermediate_size=64,
        shared_expert_intermediate_size=96,
    )


#: Engine-config overrides per program. ``prefill``/``decode`` share the
#: plain engine; ``verify`` needs the speculative verify scan compiled
#: in (spec_tokens swaps the decode executable); ``mixed`` needs the
#: piggyback mixedfill jit (mirrors the dryrun leg: prefill_chunk=8).
_VARIANTS: Dict[str, Tuple[Tuple[str, object], ...]] = {
    "prefill": (),
    "prefill1": (),
    "decode": (),
    "verify": (("spec_tokens", _SPEC_TOKENS),),
    "mixed": (("prefill_chunk_size", _MIXED_CHUNK), ("mixed_step", "on")),
}


def _build_core(shape: Tuple[int, ...], overrides=()):
    """A tiny-MoE EngineCore on the given mesh. ``__init__`` runs
    ``_resync`` so ``_dev_state`` is live and every jit is buildable."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.engine.engine import EngineConfig, EngineCore
    from llmq_tpu.engine.tokenizer import ByteTokenizer
    from llmq_tpu.models.transformer import init_params
    from llmq_tpu.parallel.mesh import make_mesh

    dp, sp, tp = shape[:3]
    mesh = make_mesh(
        data_parallel=dp, sequence_parallel=sp, tensor_parallel=tp,
        pipeline_parallel=mesh_pp_degree(shape),
    )
    config = tiny_moe_config()
    params = init_params(config, jax.random.key(0), dtype=jnp.float32)
    return EngineCore(
        config,
        params,
        ByteTokenizer(),
        mesh=mesh,
        engine_config=EngineConfig(
            max_num_seqs=max(4, dp * 2),  # dp-divisible slot axis
            max_model_len=_MAX_MODEL_LEN,
            page_size=_PAGE_SIZE,
            num_pages=_NUM_PAGES,
            min_prefill_bucket=_MIN_PREFILL_BUCKET,
            **dict(overrides),
        ),
    )


def _lower_engine_hlo(core, program: str) -> str:
    """Compiled (post-partitioning) HLO for one engine step program.

    Mirrors ``EngineCore._optimize_param_layouts``: lower the jit the
    engine actually dispatches with ShapeDtypeStructs shaped like the
    live device state — nothing executes, but GSPMD partitions exactly
    the programs production runs.
    """
    import jax
    import numpy as np

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    if core.pp > 1:
        return _lower_engine_pp_hlo(core, program)

    params = jax.tree.map(sds, core.params)
    kp, vp = sds(core.k_pages), sds(core.v_pages)
    st = jax.tree.map(sds, core._dev_state)
    i32 = np.int32
    if program in ("decode", "verify"):
        # With spec_tokens > 0 the "decode" jit IS the fused verify scan.
        lowered = core._decode_jits["greedy"].lower(params, kp, vp, st)
    elif program in ("prefill", "prefill1"):
        # The full-length bucket: with sp-sharded ring attention each
        # shard holds multiple KV pages, the regime the mixed-mesh bug
        # bit in. B=1 is the single-row executable the long prompt
        # dispatches — the one whose GSPMD propagation actually takes
        # the token-sharded ragged_dot path when the pins are off.
        batch = 1 if program == "prefill1" else core.cfg.max_prefill_batch
        bucket = core.cfg.max_model_len
        rows = tuple(sds(r) for r in core._pack_sampling_rows([], batch))
        lowered = core._prefill_jits["greedy"].lower(
            params, kp, vp,
            jax.ShapeDtypeStruct((batch, bucket), i32),
            jax.ShapeDtypeStruct((batch,), i32),
            jax.ShapeDtypeStruct((batch, core._pages_per_seq), i32),
            *rows, st,
        )
    elif program == "mixed":
        k_iters = core.cfg.decode_block
        chunk = core.cfg.prefill_chunk_size
        rows = tuple(sds(r) for r in core._pack_sampling_rows([], 1))
        lowered = core._mixedfill_jits["greedy"].lower(
            params, kp, vp,
            jax.ShapeDtypeStruct((k_iters, chunk), i32),
            jax.ShapeDtypeStruct((k_iters, chunk), i32),
            jax.ShapeDtypeStruct((k_iters,), np.bool_),
            jax.ShapeDtypeStruct((k_iters,), i32),
            jax.ShapeDtypeStruct((1, core._pages_per_seq), i32),
            jax.ShapeDtypeStruct((1,), i32),
            *rows, st,
        )
    else:
        raise ValueError(f"unknown program {program!r}")
    return lowered.compile().as_text()


def _lower_engine_pp_hlo(core, program: str) -> str:
    """Concatenated compiled HLO of every per-stage executable plus the
    head program (pp > 1 engines compile one module per stage, chained
    by the host drivers). Concatenation is the right merge for the
    signature: counts are per-line, so the sum over stages falls out —
    and each stage's replica ids live in [0, dp*sp*tp), which is what
    lets ``_axes_label`` certify no collective crosses a stage boundary.
    """
    import jax
    import numpy as np

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    i32 = np.int32
    pp = core.pp
    st = jax.tree.map(sds, core._dev_state)
    stage_params = [
        jax.tree.map(sds, tree) for tree in core.params["stages"]
    ]
    stage_kv = [sds(kp) for kp in core.k_pages]
    pps = core._pages_per_seq
    texts: List[str] = []

    def run_chain(stage_jits, head_jit, stage_data, head_extra):
        """Lower stage 0..pp-2 then the head, threading the hidden-grid
        ShapeDtypeStruct exactly as the host drivers thread the array."""
        h = None
        for s in range(pp - 1):
            args = (stage_params[s], stage_kv[s], stage_kv[s])
            args += stage_data + ((h,) if s > 0 else ())
            lowered = stage_jits[s].lower(*args)
            texts.append(lowered.compile().as_text())
            h = jax.eval_shape(stage_jits[s], *args)[0]
        lowered = head_jit.lower(
            stage_params[-1], stage_kv[-1], stage_kv[-1], h, *head_extra
        )
        texts.append(lowered.compile().as_text())

    if program == "decode":
        # Driver ships (st[0] tokens, st[1] ctx, st[2] bt, st[3] active).
        run_chain(
            core._pp_decode_stage,
            core._pp_decode_head["greedy"],
            (st[0], st[1], st[2], st[3]),
            (st,),
        )
    elif program in ("prefill", "prefill1"):
        batch = 1 if program == "prefill1" else core.cfg.max_prefill_batch
        bucket = core.cfg.max_model_len
        tok = jax.ShapeDtypeStruct((batch, bucket), i32)
        lens = jax.ShapeDtypeStruct((batch,), i32)
        bt = jax.ShapeDtypeStruct((batch, pps), i32)
        rows = tuple(sds(r) for r in core._pack_sampling_rows([], batch))
        run_chain(
            core._pp_prefill_stage,
            core._pp_prefill_head["greedy"],
            (tok, lens, bt),
            (tok, lens, bt) + rows + (st,),
        )
    elif program == "mixed":
        chunk = core.cfg.prefill_chunk_size
        seg_t = jax.ShapeDtypeStruct((chunk,), i32)
        seg_p = jax.ShapeDtypeStruct((chunk,), i32)
        seg_f = jax.ShapeDtypeStruct((), np.bool_)
        seg_l = jax.ShapeDtypeStruct((), i32)
        m_bt = jax.ShapeDtypeStruct((1, pps), i32)
        m_lens = jax.ShapeDtypeStruct((1,), i32)
        rows = tuple(sds(r) for r in core._pack_sampling_rows([], 1))
        run_chain(
            core._pp_mixed_stage,
            core._pp_mixed_head["greedy"],
            (st[0], st[1], st[3], st[2], seg_t, seg_p, seg_l, m_bt,
             rows[0]),
            (seg_t, seg_p, seg_f, seg_l, m_bt, m_lens) + rows + (st,),
        )
    else:
        raise ValueError(f"program {program!r} not lowered under pp")
    return "\n".join(texts)


def lower_program_hlo(program: str, shape: Tuple[int, ...]) -> str:
    """One-shot convenience: build the right engine variant and lower."""
    core = _build_core(shape, _VARIANTS[program])
    try:
        return _lower_engine_hlo(core, program)
    finally:
        core.stop_watchdog()


def collect_signatures(
    meshes: Sequence[Tuple[int, int, int]],
    programs: Sequence[str],
    log=print,
) -> Dict[str, Dict[str, object]]:
    """``program@mesh`` → {"collectives": counts, "ops": examples}.

    Builds one engine per (mesh, config-variant) and lowers every
    program that shares it, so prefill and decode reuse a core.
    """
    out: Dict[str, Dict[str, object]] = {}
    for shape in meshes:
        by_variant: Dict[Tuple, List[str]] = {}
        for program in programs_for_shape(shape, programs):
            by_variant.setdefault(_VARIANTS[program], []).append(program)
        for overrides, group in by_variant.items():
            core = _build_core(shape, overrides)
            try:
                for program in group:
                    key = program_key(program, shape)
                    hlo = _lower_engine_hlo(core, program)
                    counts, ops = signature_from_hlo(hlo, shape)
                    out[key] = {"collectives": counts, "ops": ops}
                    log(
                        f"spmd: lowered {key}: "
                        + (
                            ", ".join(
                                f"{k}x{v}" for k, v in sorted(counts.items())
                            )
                            or "no collectives"
                        )
                    )
            finally:
                core.stop_watchdog()
    return out


# ---------------------------------------------------------------------------
# Baseline record / diff
# ---------------------------------------------------------------------------


def diff_signatures(
    current: Dict[str, Dict[str, object]],
    baseline: Dict[str, Dict[str, int]],
) -> Tuple[List[str], List[str]]:
    """(failures, notes). A failure is a new collective key or a count
    increase vs. baseline — i.e. a resharding XLA inserted that the
    recorded programs did not have — or a program/mesh with no recorded
    baseline at all."""
    failures: List[str] = []
    notes: List[str] = []
    for key in sorted(current):
        cur = current[key]
        counts: Dict[str, int] = cur["collectives"]  # type: ignore[assignment]
        ops: Dict[str, str] = cur["ops"]  # type: ignore[assignment]
        base = baseline.get(key)
        if base is None:
            failures.append(
                f"{key}: no recorded baseline (run `llmq-tpu lint "
                f"--spmd-record` to record)"
            )
            continue
        for ckey in sorted(set(counts) | set(base)):
            now, then = counts.get(ckey, 0), base.get(ckey, 0)
            axes = ckey.split("@", 1)[1] if "@" in ckey else ""
            if now > 0 and "pp" in axes.split("+"):
                failures.append(
                    f"{key}: collective crosses a pipeline-stage "
                    f"boundary: {ckey} (x{now}) — nearest op: "
                    f"{ops.get(ckey, '?')}"
                )
                continue
            if now > then:
                failures.append(
                    f"{key}: NEW resharding collective {ckey} "
                    f"(x{now}, baseline x{then}) — nearest op: "
                    f"{ops.get(ckey, '?')}"
                )
            elif now < then:
                notes.append(
                    f"{key}: {ckey} decreased x{then} -> x{now} "
                    "(improvement; re-record to ratify)"
                )
    return failures, notes


def load_baseline(path: Path) -> Dict[str, Dict[str, int]]:
    payload = json.loads(path.read_text(encoding="utf-8"))
    return payload["signatures"]


def save_baseline(
    path: Path, signatures: Dict[str, Dict[str, object]]
) -> None:
    payload = {
        "comment": (
            "Collective signatures of the tiny-MoE ENGINE step programs "
            "(the jits EngineCore dispatches), recorded on CPU with 8 "
            "virtual devices. Diffed by `llmq-tpu lint --spmd`; "
            "re-record with --spmd-record after intentional sharding "
            "changes."
        ),
        "dims": {
            "max_model_len": _MAX_MODEL_LEN,
            "page_size": _PAGE_SIZE,
            "num_pages": _NUM_PAGES,
            "min_prefill_bucket": _MIN_PREFILL_BUCKET,
            "mixed_chunk": _MIXED_CHUNK,
            "spec_tokens_verify": _SPEC_TOKENS,
        },
        "signatures": {
            key: value["collectives"] for key, value in signatures.items()
        },
    }
    path.write_text(json.dumps(payload, indent=2) + "\n", encoding="utf-8")


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _selected(args) -> Tuple[List[Tuple[int, int, int]], List[str]]:
    raw_meshes = args.meshes or os.environ.get("LLMQ_SPMD_MESHES") or ""
    raw_programs = (
        args.programs or os.environ.get("LLMQ_SPMD_PROGRAMS") or ""
    )
    meshes = (
        [parse_mesh_key(part) for part in raw_meshes.split(",") if part]
        if raw_meshes
        else list(MESH_MATRIX)
    )
    programs = (
        [part for part in raw_programs.split(",") if part]
        if raw_programs
        else list(PROGRAMS)
    )
    for program in programs:
        if program not in PROGRAMS:
            raise SystemExit(f"unknown program {program!r}")
    return meshes, programs


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m llmq_tpu.analysis.spmd",
        description="SPMD repartition diff gate (collective signatures).",
    )
    parser.add_argument("--record", action="store_true")
    parser.add_argument("--baseline", default=None)
    parser.add_argument(
        "--meshes", default=None, help='e.g. "2x2x2,1x2x4"'
    )
    parser.add_argument(
        "--programs", default=None, help='e.g. "prefill,decode"'
    )
    args = parser.parse_args(argv)

    # XLA_FLAGS must precede jax initialization — callers that cannot
    # guarantee a fresh interpreter go through run_gate_subprocess.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    meshes, programs = _selected(args)
    needed = max(math.prod(shape) for shape in meshes)
    have = len(jax.devices())
    if have < needed:
        print(
            f"spmd: FAIL — {needed} devices needed for the mesh matrix, "
            f"{have} visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8 before jax loads)"
        )
        return 1

    baseline_path = Path(
        args.baseline
        or os.environ.get("LLMQ_SPMD_BASELINE")
        or BASELINE_PATH
    )
    signatures = collect_signatures(meshes, programs)

    if args.record:
        save_baseline(baseline_path, signatures)
        print(
            f"spmd: recorded {len(signatures)} signature(s) -> "
            f"{baseline_path}"
        )
        return 0

    if not baseline_path.exists():
        print(f"spmd: FAIL — baseline {baseline_path} missing; run --record")
        return 1
    failures, notes = diff_signatures(signatures, load_baseline(baseline_path))
    for note in notes:
        print(f"spmd: note: {note}")
    if failures:
        for failure in failures:
            print(f"spmd: FAIL: {failure}")
        return 1
    print(
        f"spmd: clean — {len(signatures)} program/mesh signature(s) match "
        "baseline"
    )
    return 0


def run_gate_subprocess(
    record: bool = False,
    extra_env: Optional[Dict[str, str]] = None,
    timeout: float = 1800.0,
) -> int:
    """Run the gate in a fresh interpreter with 8 virtual CPU devices.

    A subprocess is mandatory, not a convenience: the calling process has
    usually initialized jax already (with however many devices the
    session happened to have), and XLA's virtual device count cannot be
    changed after initialization.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "llmq_tpu.analysis.spmd"]
    if record:
        cmd.append("--record")
    try:
        proc = subprocess.run(cmd, env=env, timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"spmd: FAIL — gate subprocess exceeded {timeout:.0f}s")
        return 1
    return proc.returncode


if __name__ == "__main__":
    sys.exit(main())
