#!/usr/bin/env python3
"""Where a nonzero ``served_regret`` comes from, seed by seed, in one
process on the chip (the worker is built once; the weights are swapped).

    python3 tools/served_regret_probe.py --workload <cell> --seeds 7,11,... \
        [--repeats 1] [--check-only] [--out chiprun_out/regret.jsonl]

``benchmark/correct.py`` scores the tokens the engine served under load by
the logits of a second compilation of the same model (``jax.jit`` of
``model.decode``), so a nonzero reading has three possible sources, and
this tool tells them apart for each seed:

1. ``check``: ``correct.check_cell`` as a benchmark run makes it, with
   every position where the served token is not the direct path's best.
2. ``repeat``: the same requests served under load again (other slots,
   other pages, another interleaving of the samples' prefills with the
   fillers' decode steps). A served token that CHANGES between two
   servings whose prefill dispatches had the same shapes is something the
   load changed: a race in a kernel, a page mixed up.
3. ``same_inputs``: on the idle engine, in the engine's own pool, every
   filler position is computed by BOTH executables one after the other on
   the same cache and the same token: the engine's decode step program
   (``core._decode_jits``, the executable that served) and the direct
   path's. Tokens that differ here differ between two compilations given
   the same inputs, with no load at all.

A tool, not code a cell runs; it reads the benchmark's files and edits
none. Works at the parent of PR 40 too (copy it there).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import numpy as np  # noqa: E402


def say(line: str, out=None, **facts) -> None:
    text = json.dumps({"line": line, **facts}, default=float)
    print(text, flush=True)
    if out is not None:
        with open(out, "a") as f:
            f.write(text + "\n")


def misses(logits, tokens, correct) -> list:
    """Positions where ``tokens`` are not the best of ``logits``:
    (position, served token, best token, regret in spreads)."""
    x = np.asarray(logits, np.float64)[: len(tokens)]
    s = correct.spread(x)
    return [
        (j, int(t), int(x[j].argmax()), float(x[j].max() - x[j, int(t)]) / s)
        for j, t in enumerate(tokens) if int(x[j].argmax()) != int(t)
    ]


def same_inputs(core, fillers, dispatches, offset, correct, hlo_to=None, scratch_pages=0):
    """Both executables over the fillers in the engine's own pool, token by
    token: the rows a prefill of the direct path wrote, then a step of the
    engine's program and a step of the direct path's on the same cache,
    each fed the served token. The direct path runs second, so the history
    both read is the direct path's own. Returns, for each filler, the
    engine program's tokens and the direct path's logits."""
    import jax

    page, pps, S = core.cfg.page_size, core._pages_per_seq, core.cfg.max_num_seqs
    bts = np.zeros((S, pps), np.int32)
    base = 1
    for r, (ids, served) in enumerate(fillers):
        n = -(-(len(ids) + len(served)) // page)
        bts[r, :n] = np.arange(base, base + n)
        base += n
    prefill, decode = correct._JITS[id(core.model)]
    k, v = core.k_pages, core.v_pages
    first = [None] * len(fillers)
    for members, bucket in dispatches:
        members = [None if i is None or i < offset else i - offset for i in members]
        if all(i is None for i in members):
            continue
        tokens = np.zeros((len(members), bucket), np.int32)
        lengths = np.zeros((len(members),), np.int32)
        bt = np.zeros((len(members), pps), np.int32)
        for row, i in enumerate(members):
            if i is not None and first[i] is None:
                ids = fillers[i][0]
                tokens[row, : len(ids)], lengths[row], bt[row] = ids, len(ids), bts[i]
        logits, k, v = prefill(core.params, tokens, lengths, k, v, bt)
        for row, i in enumerate(members):
            if lengths[row]:
                first[i] = np.asarray(logits[row], np.float32)
    assert all(x is not None for x in first), "a filler no dispatch names"
    host = {
        "keys": np.zeros_like(core._h_keys), "steps": np.zeros_like(core._h_steps),
        "temp": np.zeros_like(core._h_temp), "topk": np.zeros_like(core._h_topk),
        "topp": np.ones_like(core._h_topp),
        "limits": np.full_like(core._h_limits, 1 << 20),
        "mins": np.zeros_like(core._h_mins), "stop": np.full_like(core._h_stopids, -1),
    }
    eng = [[] for _ in fillers]
    rows = [[x] for x in first]
    for j in range(max(len(t) for _, t in fillers) - 1):
        toks = np.zeros((S,), np.int32)
        ctx = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for r, (ids, served) in enumerate(fillers):
            if j < len(served) - 1:
                toks[r], ctx[r], active[r] = served[j], len(ids) + j, True
        st = jax.device_put(
            (toks, ctx, bts, active, host["keys"], host["steps"], host["temp"],
             host["topk"], host["topp"], host["limits"], host["mins"], host["stop"]),
            core._st_shardings,
        )
        out, k, v, _ = core._decode_jits["greedy"](core.params, k, v, st)
        out, _ = core._split_guard(out)
        out = np.asarray(out[0] if core._hybrid else out).reshape(-1, S)[0]
        logits, k, v = decode(core.params, toks, ctx, k, v, bts, active)
        for r in np.flatnonzero(active):
            eng[r].append(int(out[r]))
            rows[r].append(np.asarray(logits[r], np.float32))
    core.k_pages, core.v_pages = k, v
    if hlo_to:  # both executables' compiled text, to be read side by side
        prog = core._decode_jits["greedy"]
        Path(f"{hlo_to}.engine.hlo.txt").write_text(
            prog.lower(*prog.variants[""]).compile().as_text()
        )
        Path(f"{hlo_to}.direct.hlo.txt").write_text(
            decode.lower(core.params, toks, ctx, k, v, bts, active).compile().as_text()
        )
        # ... and the direct path's at a scratch pool of the check's size,
        # which is the executable ``correct.py`` scores the tokens by.
        from llmq_tpu.models.transformer import make_kv_pages

        small = make_kv_pages(core.model_config, scratch_pages, page,
                              dtype=core.cfg.kv_dtype, placement=core._kv_format)
        Path(f"{hlo_to}.direct_scratch.hlo.txt").write_text(
            decode.lower(core.params, toks, ctx, *small, bts, active).compile().as_text()
        )
    return eng, [np.stack(x) for x in rows]


def direct_logits(core, jits, seqs, rows_at, dispatches, pool, present=None):
    """``correct.program_logits`` with what it fixes left open: which
    sequences are there at all (``present``; the others keep their pages
    and their row, unused), which row of the decode batch each takes
    (``rows_at``), and the pool (``"engine"``: the engine's own, in
    place; else a scratch pool of that many pages). The direct path's
    programs (``jits``) only."""
    import jax

    from llmq_tpu.models.transformer import make_kv_pages

    page, pps, S = core.cfg.page_size, core._pages_per_seq, core.cfg.max_num_seqs
    present = set(range(len(seqs))) if present is None else set(present)
    bts = np.zeros((S, pps), np.int32)
    base = 1
    for i, (ids, served) in enumerate(seqs):
        n = -(-(len(ids) + len(served)) // page)
        bts[rows_at[i], :n] = np.arange(base, base + n)
        base += n
    if pool == "engine":
        k, v = core.k_pages, core.v_pages
    else:
        assert pool >= base
        k, v = make_kv_pages(core.model_config, pool, page, dtype=core.cfg.kv_dtype,
                             placement=core._kv_format)
    prefill, decode = jits
    rows = [[] for _ in seqs]
    for members, bucket in dispatches:
        members = [i if i in present else None for i in members]
        if all(i is None for i in members):
            continue
        tokens = np.zeros((len(members), bucket), np.int32)
        lengths = np.zeros((len(members),), np.int32)
        bt = np.zeros((len(members), pps), np.int32)
        for row, i in enumerate(members):
            if i is not None and not rows[i]:
                ids = seqs[i][0]
                tokens[row, : len(ids)], lengths[row], bt[row] = ids, len(ids), bts[rows_at[i]]
        logits, k, v = prefill(core.params, tokens, lengths, k, v, bt)
        for row, i in enumerate(members):
            if lengths[row]:
                rows[i].append(np.asarray(logits[row], np.float32))
    for j in range(max(len(t) for _, t in seqs) - 1):
        toks = np.zeros((S,), np.int32)
        ctx = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for i, (ids, served) in enumerate(seqs):
            if i in present and j < len(served) - 1:
                r = rows_at[i]
                toks[r], ctx[r], active[r] = served[j], len(ids) + j, True
        logits, k, v = decode(core.params, toks, ctx, k, v, bts, active)
        for i in present:
            if active[rows_at[i]]:
                rows[i].append(np.asarray(logits[rows_at[i]], np.float32))
    if pool == "engine":
        core.k_pages, core.v_pages = k, v
    return {i: np.stack(rows[i]) for i in sorted(present)}


def bisect(core, samples, fillers, dispatches, correct, out, seed):
    """One factor at a time, for a seed whose check missed: the direct
    path's logits of the fillers as the check computes them, then without
    the samples in the batch, then in other rows, then in the engine's
    pool; and the same with the XLA loop in the kernel's place."""
    import jax
    import jax.numpy as jnp

    from llmq_tpu.ops import dispatch

    seqs = samples + fillers
    n, m = len(samples), len(fillers)
    fill = list(range(n, n + m))
    same = list(range(n + m))
    page = core.cfg.page_size
    pages = 1 + sum(-(-(len(i) + len(t)) // page) for i, t in seqs)

    def jits():  # new functions, so that jit traces them anew
        return (jax.jit(lambda *a: core.model.prefill(*a), donate_argnums=(3, 4)),
                jax.jit(lambda *a: core.model.decode(*a), donate_argnums=(3, 4)))

    done = {}

    def run(tag, pair, **kw):
        """One variant, said at once: against ``as_check``, or the XLA
        loop's variants against ``xla_as_check``."""
        force["xla"] = tag.startswith("xla_")  # read when a shape is traced
        got = direct_logits(core, pair, seqs, kw.pop("rows_at", same), dispatches, **kw)
        done[tag] = got
        against = "xla_as_check" if tag.startswith("xla_") else "as_check"
        base = done[against]
        differ = {
            i - n: [int(j) for j in np.flatnonzero(np.abs(got[i] - base[i]).max(axis=1) > 0)]
            for i in fill
        }
        say("bisect", out, seed=seed, variant=tag, against=against,
            positions_whose_logits_differ={i: d for i, d in differ.items() if d},
            largest_abs_diff=max(float(np.abs(got[i] - base[i]).max()) for i in fill),
            missed={i - n: mm for i, mm in ((i, misses(got[i], seqs[i][1], correct)) for i in fill) if mm})

    def refill(pool, key, scale):
        """The pool filled in place: zeros (``scale`` 0), or what a used
        pool holds past a row's context: finite rows, zeros in the lanes
        beyond the row's width. A layer at a time, so that nothing the
        size of the pool lies beside it."""
        def layer(a, l, key):
            x = jax.random.normal(jax.random.fold_in(key, l), a.shape[1:], jnp.float32) * scale
            return a.at[l].set((x * (jnp.arange(a.shape[-1]) < 576)).astype(a.dtype))

        layer = jax.jit(layer, donate_argnums=0)

        def one(a):
            if a.ndim < 2 or not a.size:
                return a
            for l in range(a.shape[0]):
                a = layer(a, l, key)
            return a

        return jax.tree.map(one, pool)

    def finite(pool):
        return all(bool(jnp.isfinite(a[l]).all()) for a in jax.tree.leaves(pool)
                   if a.ndim >= 2 and a.size for l in range(a.shape[0]))

    say("pool", out, seed=seed, engine_pool_finite=finite(core.k_pages),
        shape=[list(a.shape) for a in jax.tree.leaves(core.k_pages)])

    def kernel_and_loop(tag, **kw):
        run(tag, kernel, **kw)
        if loop:
            run("xla_" + tag, loop, **kw)

    kernel, loop, force = jits(), None, {"xla": False}
    plan = getattr(dispatch, "latent_decode_kernel_plan", None)
    if plan is not None and core.stats().get("decode_kernel") != "xla":
        dispatch.latent_decode_kernel_plan = (
            lambda *a, **k: "xla" if force["xla"] else plan(*a, **k)
        )
        loop = jits()
    key = jax.random.key(seed % 1000)
    try:
        kernel_and_loop("as_check", pool=pages)
        run("no_samples", kernel, pool=pages, present=fill)
        run("rows_moved", kernel, pool=pages, present=fill,
            rows_at=[core.cfg.max_num_seqs - 1 - i for i in range(n)] + list(range(m)))
        kernel_and_loop("engine_pool", pool="engine")
        core.k_pages = refill(core.k_pages, key, 0.0)
        kernel_and_loop("engine_pool_zeroed", pool="engine")
        core.k_pages = refill(core.k_pages, key, 0.5)
        kernel_and_loop("engine_pool_noise", pool="engine")
        core.k_pages = refill(core.k_pages, key, 0.0)
    finally:
        if plan is not None:
            dispatch.latent_decode_kernel_plan = plan


async def one_seed(args, cell, traffic, system, seed: int) -> None:
    from benchmark import correct

    core = system.core
    await asyncio.to_thread(system.serve_weights_from_seed, seed)
    checked = await correct.check_cell(system, cell.config, traffic["check_lengths"], seed)
    kept = checked["kept"]
    limits, _ = correct.load_limits(cell.config_name)
    verdict = correct.verdict(checked, limits)
    seqs = kept["samples"] + kept["fillers"]
    direct = list(kept["direct"]) + list(kept["direct_fill"])
    missed = {
        i: misses(d, toks, correct) for i, ((_, toks), d) in enumerate(zip(seqs, direct))
    }
    say("check", args.out, seed=seed, correct=verdict["correct"],
        compared=verdict["compared"], load=checked["load"],
        positions=sum(len(t) for _, t in seqs),
        missed={i: m for i, m in missed.items() if m})

    if args.check_only:
        return
    prompts = [ids for ids, _ in kept["samples"]]
    for n in range(max(1, args.repeats)):
        served, fillers, dispatches, load = await correct.serve_under_load(system, seed, prompts)
        again = [list(t) for t in served] + [list(t) for _, t in fillers]
        changed = {
            i: [j for j, (a, b) in enumerate(zip(seqs[i][1], t)) if a != b]
            for i, t in enumerate(again)
        }
        say("repeat", args.out, seed=seed, n=n,
            same_prefill_shapes=load["prefill_shapes"] == checked["load"]["prefill_shapes"],
            prefill_shapes=load["prefill_shapes"],
            changed={i: c for i, c in changed.items() if c})
    # ``kept`` does not hold its serving's dispatches, so the comparison
    # uses the last serving's own tokens and dispatches.
    eng, logits = await asyncio.to_thread(
        system.engine.call_on_engine,
        lambda: same_inputs(
            core, fillers, dispatches, len(prompts), correct, args.dump_hlo,
            1 + sum(-(-(len(i) + len(t)) // core.cfg.page_size) for i, t in seqs),
        ),
        900.0,
    )
    differ, served_as_engine = {}, {}
    for i, ((_, toks), e, x) in enumerate(zip(fillers, eng, logits)):
        # x[j] chose token j; the engine's program emitted e[j - 1] for it.
        best = x.argmax(axis=1)
        differ[i] = [
            (j, e[j - 1], int(best[j]),
             float(x[j].max() - x[j, e[j - 1]]) / correct.spread(x))
            for j in range(1, len(toks)) if e[j - 1] != int(best[j])
        ]
        served_as_engine[i] = sum(e[j - 1] == toks[j] for j in range(1, len(toks)))
    args.dump_hlo = None  # once
    if args.bisect == "always" or (args.bisect and any(missed.values())):
        await asyncio.to_thread(
            system.engine.call_on_engine,
            lambda: bisect(core, kept["samples"], fillers, dispatches, correct, args.out, seed),
            1800.0,
        )
    say("same_inputs", args.out, seed=seed,
        positions=sum(len(t) - 1 for _, t in fillers),
        programs_differ={i: d for i, d in differ.items() if d},
        served_equals_engine_program=sum(served_as_engine.values()),
        served_missed_direct={
            i: m for i, m in (
                (i, misses(x, toks, correct)) for i, ((_, toks), x) in enumerate(zip(fillers, logits))
            ) if m
        })


async def amain(args) -> None:
    from benchmark import schedule
    from benchmark.run_helpers import apply_rehearsal, device_facts, load_cell
    from benchmark.system import System

    cell = load_cell(args.workload)
    traffic = schedule.load_traffic(cell.traffic_file)
    if args.rehearse_cpu:
        apply_rehearsal(cell, traffic)
    say("device", args.out, **device_facts(cell.chips, args.rehearse_cpu), workload=cell.name)
    system = System(cell.config, cell.name)
    await system.start()
    say("engine", args.out, decode_kernel=system.stats().get("decode_kernel"))
    for seed in (int(s) for s in args.seeds.split(",")):
        await one_seed(args, cell, traffic, system, seed)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--repeats", type=int, default=1)
    ap.add_argument("--check-only", action="store_true", help="the ``check`` line alone: for "
                    "a change to a prefill, which the other two legs do not run")
    ap.add_argument("--out", default=None, help="append the lines to this file too")
    ap.add_argument("--dump-hlo", default=None, metavar="PREFIX", help="write the two "
                    "executables' compiled text to PREFIX.{engine,direct}.hlo.txt")
    ap.add_argument("--bisect", nargs="?", const="missed", default=None,
                    choices=("missed", "always"), help="for a seed whose check missed (or "
                    "always), the direct path again with one thing changed at a time")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    loop = asyncio.new_event_loop()
    loop.run_until_complete(amain(args))
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
