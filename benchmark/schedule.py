"""The one general traffic generator: a traffic file of parameters in, a
list of requests with their sizes and due instants out.

Everything that decides how much work a run does (how many requests, their
prompt and output lengths, their order, their due instants) comes from the
traffic file and its ``schedule_seed``; ``--seed`` never enters here. It
draws only the token ids (see ``prompt_text``) and the weights. So every
run of a cell sends the same requests at the same instants.

Lengths are *stratified*: the n lengths of a schedule are the quantiles
(i + 0.5) / n of the stated distribution, clipped to its limits, then put
in an order drawn from ``schedule_seed``. Their median and spread are
therefore those of the distribution whatever n is, with no sampling noise.
Exponential gaps of an open loop are stratified the same way, so the
realised rate is the stated rate.
"""

from __future__ import annotations

import dataclasses
import json
import math
import random
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional

GENERATORS = ("open_loop", "closed_loop", "fixed_job")


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    prompt_tokens: int
    output_tokens: int
    #: Seconds after the traffic's start at which the request is due
    #: (open loop); 0 for a fixed job; the client's start offset for the
    #: first request of a closed-loop client, None for its later ones.
    due_s: Optional[float]
    client: int = 0


def load_traffic(path: Path) -> dict:
    spec = json.loads(Path(path).read_text())
    if spec.get("generator") not in GENERATORS:
        raise ValueError(
            f"{path}: generator {spec.get('generator')!r}, want one of {GENERATORS}"
        )
    return spec


def stratified(dist: dict, n: int) -> List[int]:
    """n whole-number lengths at the quantiles (i + 0.5) / n of ``dist``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if kind == "lognormal":
            x = float(dist["median"]) * math.exp(
                float(dist["sigma"]) * NormalDist().inv_cdf(u)
            )
        elif kind == "uniform":
            x = lo + (hi - lo) * u
        elif kind == "fixed":
            x = float(dist["value"])
        else:
            raise ValueError(f"unknown length distribution {kind!r}")
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def _lengths(spec: dict, n: int, rng: random.Random) -> List[tuple]:
    prompts = stratified(spec["prompt_tokens"], n)
    outputs = stratified(spec["output_tokens"], n)
    # Independent orders: prompt and output length are uncorrelated.
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    return list(zip(prompts, outputs))


def make_schedule(spec: dict, seconds: float) -> List[Request]:
    """The requests of one run that measures for ``seconds``."""
    rng = random.Random(int(spec["schedule_seed"]))
    gen = spec["generator"]
    if gen == "open_loop":
        rate = float(spec["rate_rps"])
        span = float(spec["warm_seconds"]) + seconds + float(spec["tail_seconds"])
        n = max(1, int(math.ceil(rate * span)))
        sizes = _lengths(spec, n, rng)
        # Stratified exponential gaps in a drawn order: mean 1 / rate.
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        rng.shuffle(gaps)
        t, out = 0.0, []
        for i, ((p, o), g) in enumerate(zip(sizes, gaps)):
            t += g
            out.append(Request(i, p, o, t))
        return out
    if gen == "closed_loop":
        clients = int(spec["clients"])
        per_client = int(spec["requests_per_client"])
        sizes = _lengths(spec, clients * per_client, rng)
        stagger = float(spec["stagger_seconds"])
        out = []
        for i, (p, o) in enumerate(sizes):
            c, k = i % clients, i // clients
            out.append(
                Request(i, p, o, stagger * c / clients if k == 0 else None, c)
            )
        return out
    n = max(1, int(round(float(spec["jobs_per_second"]) * seconds)))
    return [Request(i, p, o, 0.0) for i, (p, o) in enumerate(_lengths(spec, n, rng))]


_ALPHABET = (
    "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 .,;:-_"
)


def prompt_text(seed: int, index: int, n_tokens: int) -> str:
    """A prompt of exactly ``n_tokens`` byte-tokens drawn from ``--seed``
    (the preset models use the byte tokenizer: one ASCII character is one
    token). No braces, which the job template would read as fields. Two
    requests never share a prefix beyond chance."""
    rng = random.Random(f"{seed}:{index}")
    return "".join(rng.choices(_ALPHABET, k=n_tokens))


def describe(requests: List[Request]) -> Dict[str, float]:
    from statistics import median

    return {
        "n": len(requests),
        "prompt_median": median(r.prompt_tokens for r in requests),
        "prompt_min": min(r.prompt_tokens for r in requests),
        "prompt_max": max(r.prompt_tokens for r in requests),
        "output_median": median(r.output_tokens for r in requests),
        "prompt_tokens_total": sum(r.prompt_tokens for r in requests),
        "output_tokens_total": sum(r.output_tokens for r in requests),
    }
