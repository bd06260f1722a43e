"""Token sampling: batched, per-slot parameters, jit-compiled.

The reference hardcoded ``SamplingParams(temperature=0.7)`` and delegated
the actual sampling to vLLM (``vllm_worker.py:161-165``). Here sampling is
native and *per-job overridable* (SURVEY.md §5 config plan): every slot in
the continuous batch carries its own temperature/top-k/top-p/seed, shipped
to the device as arrays so one compiled sampler serves any mix of greedy
and stochastic requests.

TPU notes: the sampler works on ``[S, V]`` logits. Top-k/top-p use one
descending sort of the vocab axis (XLA sorts are fast and fuse with the
masking); the Gumbel-max trick turns sampling into an argmax — no host
round-trip, no dynamic shapes.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling configuration (reference default temp 0.7)."""

    temperature: float = 0.7
    top_p: float = 1.0
    top_k: int = 0  # 0 disables top-k
    max_tokens: int = 8192
    min_tokens: int = 0  # suppress EOS/stop until this many tokens emitted
    stop: Tuple[str, ...] = ()
    stop_token_ids: Tuple[int, ...] = ()
    seed: Optional[int] = None
    ignore_eos: bool = False

    @classmethod
    def from_job_extras(
        cls, extras: dict, *, default_max_tokens: int
    ) -> "SamplingParams":
        """Per-job overrides from Job extra fields (``extra="allow"``)."""

        def _tuple(value) -> Tuple[str, ...]:
            if value is None:
                return ()
            if isinstance(value, str):
                return (value,)
            return tuple(value)

        return cls(
            temperature=float(extras.get("temperature", 0.7)),
            top_p=float(extras.get("top_p", 1.0)),
            top_k=int(extras.get("top_k", 0)),
            max_tokens=int(extras.get("max_tokens", default_max_tokens)),
            min_tokens=int(extras.get("min_tokens", 0)),
            stop=_tuple(extras.get("stop")),
            stop_token_ids=tuple(int(t) for t in _tuple(extras.get("stop_token_ids"))),
            seed=(int(extras["seed"]) if extras.get("seed") is not None else None),
            ignore_eos=bool(extras.get("ignore_eos", False)),
        )


def pack_sampling_arrays(
    params: Sequence[Optional[SamplingParams]],
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Stack per-slot params into (temperature [S], top_k [S], top_p [S]).

    Empty slots (None) pack as greedy — they are masked out by ``active``
    anyway, greedy just keeps their lanes NaN-free.
    """
    temps = jnp.asarray(
        [p.temperature if p else 0.0 for p in params], dtype=jnp.float32
    )
    top_ks = jnp.asarray([p.top_k if p else 0 for p in params], dtype=jnp.int32)
    top_ps = jnp.asarray(
        [p.top_p if p else 1.0 for p in params], dtype=jnp.float32
    )
    return temps, top_ks, top_ps


def required_mode(params: "SamplingParams") -> str:
    """Cheapest sampler variant able to serve this request exactly."""
    if params.temperature <= 0.0:
        return "greedy"
    if params.top_k <= 0 and params.top_p >= 1.0:
        return "stochastic"
    return "filtered"


_MODE_ORDER = ("greedy", "stochastic", "filtered")


def join_modes(modes) -> str:
    """The cheapest variant exact for every request in the batch."""
    best = 0
    for m in modes:
        best = max(best, _MODE_ORDER.index(m))
    return _MODE_ORDER[best]


def fold_step_keys(key_data, steps):
    """Device-side sampling key chain: per-slot step keys derived as
    ``fold_in(base_key, step)``.

    This is the invariant that makes fused multi-step decode blocks
    (``EngineConfig.decode_block``) exact: the host builds each slot's
    base key ONCE, at admission/resync (``make_base_key``), and every
    subsequent step key is a pure function of (base key, step counter) —
    both of which live in the device decode-state carry, with
    ``advance_state`` incrementing the counter on device. K fused
    iterations inside one ``lax.scan`` therefore draw the exact same
    key sequence as K host round trips, with no per-step host key
    rebuilds to replace.
    """
    base_keys = jax.random.wrap_key_data(key_data)
    return jax.vmap(jax.random.fold_in)(base_keys, steps)


def _step_gumbel(key_data, steps, shape) -> jnp.ndarray:
    step_keys = fold_step_keys(key_data, steps)
    return jax.vmap(
        lambda key: jax.random.gumbel(key, shape[1:], dtype=jnp.float32)
    )(step_keys)


@jax.named_scope("llmq.sample")
def sample_tokens(
    logits: jnp.ndarray,  # [S, V] float32
    key_data: jnp.ndarray,  # [S, ...] per-slot PRNG key data (see make_base_key)
    steps: jnp.ndarray,  # [S] int32 — per-slot generation step, folded into keys
    temperature: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S] int32, 0 = off
    top_p: jnp.ndarray,  # [S] float32, 1.0 = off
    *,
    mode: str = "filtered",
) -> jnp.ndarray:
    """Sample one token per slot; temperature <= 0 means greedy.

    ``mode`` is *static* — the engine compiles one decode executable per
    variant actually used and picks per step (a greedy batch must not pay
    a [S, V] vocab sort — on a 150k vocab that sort dwarfs the model step):

    - ``greedy``      argmax only;
    - ``stochastic``  Gumbel-max (exact sampling, no sort) — valid when no
                      slot filters by top-k/top-p;
    - ``filtered``    one descending vocab sort; per-slot *dynamic* k/p as
                      rank masks and cumulative-probability masks on the
                      sorted axis, then Gumbel argmax, un-sorted back.

    The step counter is folded into slot keys on device, so the host never
    touches PRNG state in the hot loop. Greedy lanes inside stochastic/
    filtered batches are handled by the final ``where``.
    """
    S, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)
    if mode == "greedy":
        return greedy

    safe_temp = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / safe_temp

    if mode == "stochastic":
        gumbel = _step_gumbel(key_data, steps, (S, V))
        sampled = jnp.argmax(scaled + gumbel, axis=-1)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    # Descending sort once; all filters become rank masks.
    sort_idx = jnp.argsort(-scaled, axis=-1)  # [S, V]
    sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
    ranks = jnp.arange(V)[None, :]

    # top-k: keep ranks < k (k==0 → keep all).
    k = jnp.where(top_k > 0, top_k, V)[:, None]
    keep = ranks < k

    # top-p: keep the smallest prefix with cumulative prob >= p. The
    # standard formulation keeps entries whose *preceding* cumulative mass
    # is < p, which always retains rank 0.
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum_before = jnp.cumsum(probs, axis=-1) - probs
    keep &= cum_before < top_p[:, None]

    masked = jnp.where(keep, sorted_logits, NEG_INF)
    # Gumbel noise is drawn in *token* space and permuted through the same
    # sort, so an unfiltered slot samples bit-identically to `stochastic`
    # mode — a seeded request's stream can't change when an unrelated
    # filtered request joins the batch and switches the variant.
    gumbel = _step_gumbel(key_data, steps, (S, V))
    gumbel_sorted = jnp.take_along_axis(gumbel, sort_idx, axis=-1)
    choice_rank = jnp.argmax(masked + gumbel_sorted, axis=-1)  # [S]
    sampled = jnp.take_along_axis(sort_idx, choice_rank[:, None], axis=-1)[:, 0]

    return jnp.where(temperature <= 0.0, greedy, sampled)


@jax.named_scope("llmq.sample")
def spec_verify_tokens(
    logits: jnp.ndarray,  # [S, Q, V] float32 — Q = spec_tokens + 1 positions
    drafts: jnp.ndarray,  # [S, Q-1] int32 — proposed tokens (-1 = no draft)
    key_data: jnp.ndarray,  # [S, ...] per-slot PRNG key data
    steps: jnp.ndarray,  # [S] int32 — generation step at position 0
    temperature: jnp.ndarray,  # [S]
    top_k: jnp.ndarray,  # [S] int32, 0 = off
    top_p: jnp.ndarray,  # [S] float32, 1.0 = off
    *,
    mode: str = "filtered",
) -> jnp.ndarray:
    """Speculative-verify sampling: the token the model emits at each of
    Q candidate positions, assuming every earlier position accepted its
    draft. ``emit[s, i] == drafts[s, i]`` means position i's draft is
    accepted and position i+1 is reached; the first mismatch is the
    corrected token and the chain stops there (the engine computes the
    accepted prefix from exactly this equality). Position Q-1 carries no
    draft — it is the bonus token sampled when every draft is accepted.

    Losslessness:

    - ``greedy`` — emit is the plain argmax per position, so an accepted
      prefix is *bit-identical* to what Q sequential decode steps would
      have produced (each position's logits condition only on accepted
      tokens).
    - sampled — standard rejection sampling against a deterministic
      (point-mass) draft: accept draft d with probability p(d) under the
      slot's temperature/top-k/top-p-filtered distribution; on rejection
      sample from the residual — p with d removed and renormalized —
      which makes the marginal of ``emit`` exactly p at every position.
      Per-position randomness comes from the same device-side key chain
      as normal decode (``fold_in(base_key, step + i)``, split into an
      accept-uniform and a resample-Gumbel), so the scheme needs no host
      RNG state; seeded streams legitimately differ from the non-spec
      engine (lossless in distribution, not per-token).
    """
    S, Q, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)  # [S, Q]
    if mode == "greedy":
        return greedy

    R = S * Q
    flat = logits.reshape(R, V)
    steps_q = (steps[:, None] + jnp.arange(Q)[None, :]).reshape(R)
    safe_temp = jnp.maximum(jnp.repeat(temperature, Q), 1e-6)[:, None]
    scaled = flat / safe_temp

    if mode == "filtered":
        # Same one-sort filter machinery as sample_tokens, but the keep
        # mask is scattered back to token space: rejection sampling needs
        # the filtered distribution itself (accept prob + residual), not
        # just one draw from it.
        topk_q = jnp.repeat(top_k, Q)
        topp_q = jnp.repeat(top_p, Q)
        sort_idx = jnp.argsort(-scaled, axis=-1)
        sorted_logits = jnp.take_along_axis(scaled, sort_idx, axis=-1)
        ranks = jnp.arange(V)[None, :]
        k = jnp.where(topk_q > 0, topk_q, V)[:, None]
        keep = ranks < k
        probs_sorted = jax.nn.softmax(sorted_logits, axis=-1)
        cum_before = jnp.cumsum(probs_sorted, axis=-1) - probs_sorted
        keep &= cum_before < topp_q[:, None]
        rows = jnp.broadcast_to(jnp.arange(R)[:, None], (R, V))
        keep_tok = jnp.zeros((R, V), bool).at[rows, sort_idx].set(keep)
        masked = jnp.where(keep_tok, scaled, NEG_INF)
    else:
        masked = scaled

    # Drafts flattened with a -1 sentinel at the bonus position: p(d)=0
    # there, so the "reject" branch below is a plain sample from p.
    d = jnp.concatenate(
        [drafts, jnp.full((S, 1), -1, drafts.dtype)], axis=1
    ).reshape(R)
    step_keys = fold_step_keys(jnp.repeat(key_data, Q, axis=0), steps_q)
    pairs = jax.vmap(lambda key: jax.random.split(key, 2))(step_keys)
    u = jax.vmap(lambda key: jax.random.uniform(key, ()))(pairs[:, 0])
    gumbel = jax.vmap(
        lambda key: jax.random.gumbel(key, (V,), dtype=jnp.float32)
    )(pairs[:, 1])

    probs = jax.nn.softmax(masked, axis=-1)
    p_d = jnp.take_along_axis(
        probs, jnp.clip(d, 0, V - 1)[:, None], axis=-1
    )[:, 0]
    p_d = jnp.where(d >= 0, p_d, 0.0)
    accept = u < p_d
    # Residual for a point-mass draft: p with d zeroed, renormalized —
    # Gumbel-argmax over the masked logits with d dropped samples it
    # exactly (d = -1 routes out of range: nothing dropped, full p).
    d_oob = jnp.where(d >= 0, d, V)
    residual = masked.at[jnp.arange(R), d_oob].set(NEG_INF, mode="drop")
    resample = jnp.argmax(residual + gumbel, axis=-1)
    emit = jnp.where(accept, d, resample).reshape(S, Q)
    return jnp.where((temperature <= 0.0)[:, None], greedy, emit)


@functools.lru_cache(maxsize=8192)
def _key_data_host(eff_seed: int) -> "np.ndarray":
    """Key data for ``eff_seed``, computed on the host CPU backend.

    This runs per admitted request on the engine's hot path. Letting the
    eager ops land on the default accelerator serializes them behind
    everything already dispatched: the ``np.asarray`` sync waits for the
    whole run-ahead queue (~300 ms per prefill chunk, measured round 2
    on a host with a slow link to its chip). Pinning to the CPU backend
    makes it microseconds; the cache makes repeat slots/seeds free.
    """
    import numpy as np

    try:
        dev = jax.local_devices(backend="cpu")[0]
        with jax.default_device(dev):
            return np.asarray(jax.random.key_data(jax.random.key(eff_seed)))
    except RuntimeError:  # no cpu backend registered (unusual)
        return np.asarray(jax.random.key_data(jax.random.key(eff_seed)))


def make_base_key(seed: Optional[int], request_tag: int) -> "np.ndarray":
    """Key data for one request, computed once at admission (host-side).

    Seeded requests derive from the seed alone and are reproducible
    across runs. Unseeded ones derive from ``request_tag`` — a stable
    per-request integer (the engine passes a CRC of the request id), so
    a recompute-preempted request re-admitted into a *different* slot
    continues the same stream; keys never depend on slot placement.
    """
    return _key_data_host(seed if seed is not None else 0x5EED ^ request_tag)


def request_tag(rid: str) -> int:
    """Stable integer stream tag for an unseeded request id."""
    import zlib

    return zlib.crc32(rid.encode("utf-8", "surrogatepass"))


