"""How ``correct`` is decided: the served program against the plain
reference, on a seeded sample of sequences at the cell's own lengths,
served while other sequences are live.

For each run, from ``--seed``:

1. ``N_FILL`` other requests ("fillers", ``FILL_PROMPT`` tokens each) are
   sent to the engine the worker runs. Once each has its first token, the
   sample prompts (one per sample length) are sent together, greedy,
   ``K_TOKENS`` tokens: they are prefilled and decoded by the engine's own
   step programs, in its own pool, in batches with the live fillers.
2. Each sample prompt is then served twice more, one after the other on
   the idle engine: the two answers must be the same tokens
   (``repeat_diff``, limit 0).
3. Every sequence of step 1 is run through the program's model directly:
   prefill of the prompts in the batch shapes the engine dispatched them
   in, then one decode step a token, through a scratch paged cache, fed
   the tokens that step 1 served: logits of each served position.
4. The sample prompts are run through the plain reference of the cell's
   configuration, ``forward_logits`` of ``architectures/<architecture>.py``
   (float32, highest precision, no cache), over prompt + served tokens:
   logits of the same positions.

Three numbers are compared with limits:

- ``served_regret`` ties the served tokens to the directly computed
  logits: how far below its row's best logit the direct path scores the
  token the engine served under load, worst over all positions of the
  sample prompts and the fillers (some 200), in units of the logits'
  standard deviation over the vocabulary. An engine whose step programs,
  pool or batching compute something else than the model does (a fused
  block, speculation, a page mixed up under load, a lower precision on the
  served path alone) serves tokens the direct path scores lower.
- ``logit_err`` ties the direct logits to the reference: root mean square
  of program minus reference, over the K positions and the whole
  vocabulary, relative to the standard deviation of the reference's
  logits. Steady from seed to seed (it averages over a million logits),
  and it separates bf16 from int8 weights or an fp8 cache.
- ``repeat_diff``: tokens that differ between the two idle answers.

The limits are the configuration's own, ``limits/<config>.json``, where
that file exists, else those of ``limits.json``; either file holds the
readings its limits were set from.
"""

from __future__ import annotations

import asyncio
import json
import math
import time
from pathlib import Path
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

K_TOKENS = 8  # served tokens of a sample prompt
N_FILL = 12  # other sequences live while the sample prompts are served
FILL_PROMPT = 160  # their prompt length (one prefill bucket)
FILL_TOKENS = 48  # their output: they outlast the samples' prefill and K tokens
FILL_TIED = 16  # of which the first are judged against the direct logits

HERE = Path(__file__).resolve().parent
Seq = Tuple[List[int], List[int]]  # prompt ids, served tokens
_JITS: Dict[int, tuple] = {}  # the direct path's two programs, traced once a model


def load_limits(config: str, directory: Path = HERE) -> Tuple[Dict[str, float], str]:
    """The limits a configuration is held to, and the file they are from:
    ``limits/<config>.json`` where it exists, else ``limits.json``."""
    directory = Path(directory)
    path = directory / "limits" / f"{config}.json"
    if not path.is_file():
        path = directory / "limits.json"
    return json.loads(path.read_text())["limits"], str(path.relative_to(directory.parent))


def spread(logits: np.ndarray) -> float:
    """Standard deviation of the logits over the vocabulary, all rows."""
    x = np.asarray(logits, np.float64)
    return float(np.sqrt(np.mean((x - x.mean(axis=1, keepdims=True)) ** 2)))


def logit_err(program: np.ndarray, ref: np.ndarray) -> float:
    diff = np.asarray(program, np.float64) - np.asarray(ref, np.float64)
    return float(np.sqrt(np.mean(diff**2))) / spread(ref)


def regret(logits: np.ndarray, tokens: Sequence[int]) -> float:
    """How far below its row's best logit ``logits`` scores each of
    ``tokens``, worst row, in units of the logits' spread."""
    x = np.asarray(logits, np.float64)[: len(tokens)]
    worst = max(float(x[j].max() - x[j, int(t)]) for j, t in enumerate(tokens))
    return worst / spread(x)


def prompt_ids(seed: int, index: int, n: int) -> List[int]:
    from .schedule import prompt_text

    # The byte tokenizer's ids of the traffic's own alphabet.
    return [b + 1 for b in prompt_text(seed, 10_000_000 + index, n).encode()]


def program_logits(core, seqs: Sequence[Seq], dispatches: Sequence[tuple]) -> List[np.ndarray]:
    """For each (prompt ids, served tokens): logits [len(served), V] of the
    program's model at the served positions. The prompts are prefilled
    into a scratch paged cache laid out as the engine's, in the shapes the
    engine dispatched them in (``dispatches``: for each prefill, the
    sequence index or None of every row of its padded batch, and its
    bucket; a batch shape is a program of its own, with its own rounding);
    then decode steps at the engine's slot count, one row a sequence, each
    fed the token served before."""
    import jax

    from llmq_tpu.models.transformer import make_kv_pages

    page = core.cfg.page_size
    pps = core._pages_per_seq
    S = core.cfg.max_num_seqs
    assert len(seqs) <= S
    bts = np.zeros((S, pps), np.int32)
    base = 1  # page 0 is the scratch page
    for r, (ids, served) in enumerate(seqs):
        n = -(-(len(ids) + len(served)) // page)
        bts[r, :n] = np.arange(base, base + n)
        base += n
    k, v = make_kv_pages(
        core.model_config, base, page, dtype=core.cfg.kv_dtype,
        placement=core._kv_format,
    )
    if id(core.model) not in _JITS:
        _JITS[id(core.model)] = (
            jax.jit(core.model.prefill, donate_argnums=(3, 4)),
            jax.jit(core.model.decode, donate_argnums=(3, 4)),
        )
    prefill, decode = _JITS[id(core.model)]
    rows: List[List[np.ndarray]] = [[] for _ in seqs]
    seen = {i for members, _ in dispatches for i in members if i is not None}
    alone = [  # a sequence no dispatch names: one row at its own bucket
        ([i], next(b for b in core._buckets if b >= len(seqs[i][0])))
        for i in range(len(seqs)) if i not in seen
    ]
    for members, bucket in list(dispatches) + alone:
        tokens = np.zeros((len(members), bucket), np.int32)
        lengths = np.zeros((len(members),), np.int32)
        bt = np.zeros((len(members), pps), np.int32)
        for row, i in enumerate(members):
            if i is not None and not rows[i]:
                ids = seqs[i][0]
                tokens[row, : len(ids)], lengths[row], bt[row] = ids, len(ids), bts[i]
        logits, k, v = prefill(core.params, tokens, lengths, k, v, bt)
        for row, i in enumerate(members):
            if lengths[row]:
                rows[i].append(np.asarray(logits[row], np.float32))
    for j in range(max(len(served) for _, served in seqs) - 1):
        toks = np.zeros((S,), np.int32)
        ctx = np.zeros((S,), np.int32)
        active = np.zeros((S,), bool)
        for r, (ids, served) in enumerate(seqs):
            if j < len(served) - 1:
                toks[r], ctx[r], active[r] = served[j], len(ids) + j, True
        logits, k, v = decode(core.params, toks, ctx, k, v, bts, active)
        for r in np.flatnonzero(active):
            rows[r].append(np.asarray(logits[r], np.float32))
    del k, v
    return [np.stack(x) for x in rows]


async def serve_greedy(engine, rid: str, ids: Sequence[int], n: int) -> List[int]:
    from llmq_tpu.engine.sampling import SamplingParams

    out = await engine.generate(
        rid=rid,
        prompt_ids=list(ids),
        params=SamplingParams(temperature=0.0, max_tokens=n, ignore_eos=True),
    )
    return list(out.token_ids)


async def serve_under_load(system, seed: int, prompts: Sequence[List[int]]):
    """Step 1: the sample prompts served together while fillers are live.
    Returns the samples' tokens, the fillers as (ids, served), the prefill
    dispatches of all of them (rows as indices into samples + fillers) and
    how many fillers were live when the samples were sent and when they
    ended."""
    core, engine = system.core, system.engine
    mark = len(system.prefill_log)
    n_fill = max(1, min(N_FILL, core.cfg.max_num_seqs - len(prompts) - 1))
    fill_len = min(FILL_PROMPT, core.cfg.max_model_len // 4)
    fillers = [prompt_ids(seed, 1000 + i, fill_len) for i in range(n_fill)]
    tasks = [
        asyncio.ensure_future(serve_greedy(engine, f"fill{i}", ids, FILL_TOKENS))
        for i, ids in enumerate(fillers)
    ]

    def live() -> int:
        return sum(
            1 for rid, seq in system.inflight().items()
            if rid.startswith("fill") and seq.t_first_token
        )

    deadline = time.monotonic() + 120.0
    while live() < n_fill and not all(t.done() for t in tasks):
        if time.monotonic() > deadline:
            raise RuntimeError("the fillers of the correctness sample never started")
        await asyncio.sleep(0.005)
    at_submit = live()
    served = await asyncio.gather(
        *(serve_greedy(engine, f"check{i}", ids, K_TOKENS) for i, ids in enumerate(prompts))
    )
    at_end = live()
    filled = await asyncio.gather(*tasks)
    index = {f"check{i}": i for i in range(len(prompts))}
    index.update({f"fill{i}": len(prompts) + i for i in range(n_fill)})
    dispatches, shapes = [], {}
    for (_, _, batch, bucket), rids in zip(
        system.prefill_log[mark:], system.prefill_rids[mark:]
    ):
        members = [index.get(rid) for rid in rids] + [None] * (batch - len(rids))
        dispatches.append((members, bucket))
        shapes[f"{batch}x{bucket}"] = shapes.get(f"{batch}x{bucket}", 0) + 1
    load = {"fillers": n_fill, "live_at_submit": at_submit, "live_at_end": at_end,
            "prefill_shapes": shapes}
    fillers = [(f, t[:FILL_TIED]) for f, t in zip(fillers, filled)]
    return served, fillers, dispatches, load


async def check_cell(system, cfg: Dict[str, Any], lengths: Sequence[int],
                     seed: int) -> Dict[str, Any]:
    """The numbers of one run: a row per sample prompt and one for the
    fillers. ``kept`` holds the sequences and their direct logits, for
    ``calibrate.py`` to put the controls in the program's place."""
    from . import architectures

    reference = architectures.of(cfg)
    core = system.core
    prompts = [
        prompt_ids(seed, i, min(int(want), core.cfg.max_model_len - K_TOKENS - 1))
        for i, want in enumerate(lengths)
    ]
    served, fillers, dispatches, load = await serve_under_load(system, seed, prompts)
    rows = []
    for i, ids in enumerate(prompts):
        a = await serve_greedy(system.engine, f"check{i}a", ids, K_TOKENS)
        b = await serve_greedy(system.engine, f"check{i}b", ids, K_TOKENS)
        rows.append({
            "prompt_tokens": len(ids),
            "repeat_diff": float(sum(x != y for x, y in zip(a, b)) + abs(len(a) - len(b))),
        })
    samples = list(zip(prompts, served))

    def direct_and_reference():
        logits = program_logits(core, samples + fillers, dispatches)
        direct, direct_fill = logits[: len(samples)], logits[len(samples) :]
        refs = [
            np.asarray(reference.forward_logits(
                core.params, cfg, ids + toks[:-1], positions_of(ids, toks)
            ))
            for ids, toks in samples
        ]
        return direct, direct_fill, refs

    direct, direct_fill, refs = await asyncio.to_thread(
        system.engine.call_on_engine, direct_and_reference, 600.0
    )
    for row, (_, toks), prog, ref in zip(rows, samples, direct, refs):
        row["logit_err"] = logit_err(prog, ref)
        row["served_regret"] = regret(prog, toks)
    rows.append({
        "fillers": len(fillers),
        "positions": sum(len(t) for _, t in fillers),
        "served_regret": max(regret(p, t) for p, (_, t) in zip(direct_fill, fillers)),
    })
    kept = {"samples": samples, "fillers": fillers, "direct": direct,
            "direct_fill": direct_fill, "refs": refs}
    return {"rows": rows, "load": load, "kept": kept}


def control_rows(core, cfg: Dict[str, Any], kept: Dict[str, Any], control: str):
    """The same rows with a control in the program's place: the reference
    computed in a lower precision. Its logits against the reference
    (``logit_err``), and the tokens it would serve, its best at each
    position, judged by the program's direct logits (``served_regret``).
    Run by ``calibrate.py``, never by a benchmark run."""
    from . import architectures

    reference = architectures.of(cfg)

    def ctrl_logits(ids, toks):
        return np.asarray(reference.forward_logits(
            core.params, cfg, ids + toks[:-1], positions_of(ids, toks), control=control
        ))

    rows = []
    for (ids, toks), prog, ref in zip(kept["samples"], kept["direct"], kept["refs"]):
        ctrl = ctrl_logits(ids, toks)
        rows.append({
            "prompt_tokens": len(ids),
            "logit_err": logit_err(ctrl, ref),
            "served_regret": regret(prog, ctrl.argmax(axis=1)),
        })
    rows.append({
        "fillers": len(kept["fillers"]),
        "positions": sum(len(t) for _, t in kept["fillers"]),
        "served_regret": max(
            regret(prog, ctrl_logits(ids, toks).argmax(axis=1))
            for (ids, toks), prog in zip(kept["fillers"], kept["direct_fill"])
        ),
    })
    return rows


def positions_of(ids: Sequence[int], served: Sequence[int]) -> List[int]:
    """Positions of prompt + served[:-1] whose logits chose ``served``."""
    return list(range(len(ids) - 1, len(ids) - 1 + len(served)))


def verdict(check: Dict[str, Any], limits: Dict[str, float]) -> Dict[str, Any]:
    """Each number compared beside its limit; correct when all are within."""
    rows = check["rows"]
    compared = {}
    ok = True
    for name, limit in limits.items():
        worst = max(r[name] for r in rows if name in r)
        compared[name] = {"value": worst, "limit": limit}
        ok = ok and (worst <= limit) and math.isfinite(worst)
    return {"correct": bool(ok), "compared": compared, "samples": rows,
            "load": check.get("load")}
