"""The ``bailing_hybrid`` architecture (Ling-3.0): its served tree, how
each leaf is made, its plain reference and that reference's controls
(see ``__init__.py`` for what the harness asks of an architecture).

The tree: top-level ``embed``, ``final_norm``, ``lm_head``, and one group
``stack<i>`` for every run of consecutive kept layers of one kind, stacked
on a leading layer axis. A layer's kind comes from its *published* index
``i`` (``kept_layers`` of the configuration's file): MLA attention where
``(i + 1) % layer_group_size == 0``, else KDA; a dense MLP where
``i < first_k_dense_replace``, else routed experts. Of the
``num_experts_published`` experts the router scores, the tree holds
``experts_held = [first, count]``.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, nothing imported from the program. One sequence, one layer at a
time, each layer's weights raised from the bf16 tree as they are used and
the experts a block at a time, so that a layer's float32 copy fits beside
the served tree. It follows the block as ISSUE 33 writes it down:

- pre-norm residual layers, RMSNorm, final RMSNorm, untied head;
- KDA: SiLU of a causal depth-wise convolution (kernel 4, zeros before
  the sequence) of the q, k and v projections; q and k L2-normalised per
  head, q scaled by d^-1/2; per-channel gate ``g = kda_lower_bound *
  sigmoid(exp(A_log) * (W_f x + dt_bias))``, ``alpha = exp(g)``; ``beta =
  sigmoid(W_b x)`` a head; the delta rule TOKEN BY TOKEN (``lax.scan``):
  ``S' = Diag(alpha) S``, ``S = S' + beta k (v - S'^T k)^T``, ``o = S^T
  q``; per-head RMSNorm of ``o`` times ``sigmoid(W_g x)``, then ``W_o``;
  no rotary;
- MLA, expanded: latent ``c = RMSNorm(W_kva x)[:512]``, shared rotary key
  ``r``, per-head keys and values ``W_kvb c``, scores over 192 values a
  head, causal softmax, a head-wise sigmoid gate, ``W_o``. Rotary pairs
  are (0, 1), (2, 3), ... (``rope_interleave``), rotated in place: the
  program brings them to halves first, which permutes q^R and r alike;
- routed experts: sigmoid scores over all published experts in float32,
  a selection bias, 8 groups of which the 4 with the largest top-2 sums
  stay, the 8 largest biased scores among them chosen, weights ``2.5 *
  s / (sum of the 8 chosen s + 1e-20)``; only the chosen experts that are
  HELD are computed and added, plus the shared expert. What the other
  chips' experts would add is left out, here as in the program.

``control`` puts something else in the reference's place, which the
comparison in ``correct.py`` has to refuse (``CONTROLS``). Two tempting
lower precisions:

- ``"int8w"``: every matrix (projections, experts, router, embedding,
  head) rounded to int8 with one scale per output channel;
- ``"bf16state"``: the KDA state rounded to bfloat16 after every token.

Three planted faults of this block's own machinery, what a cache manager
or a step program that is wrong would compute (``positions[0]`` is the
last position of the prompt; what follows it was decoded):

- ``"kda_reset"``: every token of a KDA layer starts from a zero state;
- ``"conv_tail"``: from the first decoded token on, the short
  convolutions see zeros in place of the three inputs before (the tails
  are not carried from the prefill, nor from step to step);
- ``"moe_drop"``, ``"moe_drop_first"``: the routed experts of the last, or
  of the first, kept layer that has any are left out (its shared expert
  stays). The last layer's reach the logits alone; the first's also move
  every later layer's state, attention and choice of experts.

And two that are no controls but a diagnosis (``DIAGNOSES``; PERF.md, PR
33): ``"bf16act"`` rounds the residual stream, every normed input and
every attention and MLP output to bfloat16, about what the program's
arithmetic does; ``"bf16act_routed"`` does the same but chooses each
token's experts as the float32 pass chose them (weights from its own
scores). The distance between the two is what expert choices flipped by
rounding cost.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("int8w", "bf16state", "kda_reset", "conv_tail", "moe_drop", "moe_drop_first")
DIAGNOSES = ("bf16act", "bf16act_routed")
_Q_CHUNK = 512  # query rows per attention block
_E_BLOCK = 16  # experts raised to float32 at a time

_NORMS = ("ln1", "ln2", "final_norm", "kda_o_norm", "mla_kv_norm")
_BIASES = ("router_bias", "kda_a_log", "kda_dt_bias")


def _kinds(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    group = int(cfg["layer_group_size"])
    kept = cfg.get("kept_layers") or range(int(cfg["num_hidden_layers"]))
    # The published rule on published indices; the file's own
    # first_k_dense_replace counts the dense layers that are kept.
    dense = int(cfg.get("first_k_dense_replace_published", cfg["first_k_dense_replace"]))
    return [
        ("mla" if (i + 1) % group == 0 else "kda", "dense" if i < dense else "moe")
        for i in kept
    ]


def _groups(cfg: Dict[str, Any]) -> List[Tuple[str, str, str, int]]:
    """(name, attention, mlp, layers) of each run of equal kept layers."""
    out: List[Tuple[str, str, str, int]] = []
    for attn, mlp in _kinds(cfg):
        if out and out[-1][1:3] == (attn, mlp):
            out[-1] = (*out[-1][:3], out[-1][3] + 1)
        else:
            out.append((f"stack{len(out)}", attn, mlp, 1))
    return out


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    n = int(cfg["num_attention_heads"])
    d = int(cfg.get("head_dim") or int(cfg["hidden_size"]) // n)
    return dict(
        H=int(cfg["hidden_size"]), n=n, d=d, D=n * d,
        I=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
        K=int(cfg["short_conv_kernel_size"]),
        rank=int(cfg["kv_lora_rank"]), nope=int(cfg["qk_nope_head_dim"]),
        rope=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
        E=int(cfg.get("num_experts_published", cfg["num_experts"])),
        held=int(cfg["num_experts"]), Im=int(cfg["moe_intermediate_size"]),
        Is=int(cfg["moe_shared_expert_intermediate_size"])
        * int(cfg.get("num_shared_experts", 1)),
    )


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for the configuration's file."""
    z = _sizes(cfg)
    H, n, d, D = z["H"], z["n"], z["d"], z["D"]
    shapes: Dict[str, Any] = {
        "embed": (z["V"], H), "final_norm": (H,), "lm_head": (H, z["V"]),
    }
    for name, attn, mlp, L in _groups(cfg):
        leaves: Dict[str, tuple] = {"ln1": (L, H), "ln2": (L, H)}
        if attn == "kda":
            leaves.update(
                kda_q_proj=(L, H, D), kda_k_proj=(L, H, D), kda_v_proj=(L, H, D),
                kda_conv=(L, z["K"], 3 * D), kda_f_proj=(L, H, D),
                kda_a_log=(L, n), kda_dt_bias=(L, D), kda_b_proj=(L, H, n),
                kda_g_proj=(L, H, D), kda_o_norm=(L, d), o_proj=(L, D, H),
            )
        else:
            leaves.update(
                mla_q_proj=(L, H, n * (z["nope"] + z["rope"])),
                mla_kva_proj=(L, H, z["rank"] + z["rope"]),
                mla_kv_norm=(L, z["rank"]),
                mla_kvb_proj=(L, z["rank"], n * (z["nope"] + z["dv"])),
                mla_g_proj=(L, H, n), o_proj=(L, n * z["dv"], H),
            )
        if mlp == "dense":
            leaves.update(
                gate_proj=(L, H, z["I"]), up_proj=(L, H, z["I"]),
                down_proj=(L, z["I"], H),
            )
        else:
            leaves.update(
                router=(L, H, z["E"]), router_bias=(L, z["E"]),
                expert_gate_proj=(L, z["held"], H, z["Im"]),
                expert_up_proj=(L, z["held"], H, z["Im"]),
                expert_down_proj=(L, z["held"], z["Im"], H),
                shared_gate_proj=(L, H, z["Is"]), shared_up_proj=(L, H, z["Is"]),
                shared_down_proj=(L, z["Is"], H),
            )
        shapes[name] = leaves
    return shapes


def init_rule(name: str) -> str:
    """How ``weights.py`` makes the leaf of that name."""
    if name in _NORMS:
        return "norm"
    if name in _BIASES:
        return "bias"
    return {"embed": "vocab_rows", "lm_head": "vocab_columns"}.get(name, "matrix")


def _fake_int8(w, axis: int):
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _w(x, control: Optional[str], axis: int = -2):
    x = x.astype(F32)
    return _fake_int8(x, axis) if control == "int8w" else x


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _act(x, control: Optional[str]):
    """The ``bf16act`` diagnoses round an activation to bfloat16
    (``reduce_precision``: a cast there and back may be dropped)."""
    if control in DIAGNOSES:
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def _rope_pairs(x, positions, theta: float):
    """Rotate the pairs (0, 1), (2, 3), ... of the last axis in place.
    ``x``: [T, ..., d]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return out.reshape(x.shape)


def _kda(x, lw, z, eps, lower, control, prompt_len):
    T = x.shape[0]
    n, d, D, K = z["n"], z["d"], z["D"], z["K"]
    taps = lw["kda_conv"].astype(F32)  # [K, 3D], the last tap on the current input
    decoded = (jnp.arange(T) >= prompt_len)[:, None]

    def conv(u, w):  # u [T, D]; zeros before the sequence
        padded = jnp.concatenate([jnp.zeros((K - 1, D), F32), u], axis=0)
        out = sum(w[j] * padded[j : j + T] for j in range(K))
        if control == "conv_tail":
            out = jnp.where(decoded, w[K - 1] * u, out)
        return out

    parts = []
    for i, name in enumerate("qkv"):
        u = x @ _w(lw[f"kda_{name}_proj"], control)
        parts.append(jax.nn.silu(conv(u, taps[:, i * D : (i + 1) * D])).reshape(T, n, d))
    q, k, v = parts
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True)) * d**-0.5
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True))
    f = x @ _w(lw["kda_f_proj"], control) + lw["kda_dt_bias"].astype(F32)
    rate = jnp.exp(lw["kda_a_log"].astype(F32))[None, :, None]
    alpha = jnp.exp(lower * jax.nn.sigmoid(rate * f.reshape(T, n, d)))
    beta = jax.nn.sigmoid(x @ _w(lw["kda_b_proj"], control))  # [T, n]

    def token(S, xs):
        q_t, k_t, v_t, a_t, b_t = xs
        if control == "kda_reset":
            S = jnp.zeros_like(S)
        S = a_t[:, :, None] * S
        delta = v_t - jnp.einsum("nkv,nk->nv", S, k_t)
        S = S + b_t[:, None, None] * k_t[:, :, None] * delta[:, None, :]
        if control == "bf16state":
            # reduce_precision, not a cast there and back: the compiler
            # may drop a round trip through bfloat16 as excess precision
            # (on the TPU it did: the control read exactly 0).
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        return S, jnp.einsum("nkv,nk->nv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((n, d, d), F32), (q, k, v, alpha, beta))
    gate = jax.nn.sigmoid(x @ _w(lw["kda_g_proj"], control)).reshape(T, n, d)
    o = _rms(o, lw["kda_o_norm"], eps) * gate
    return o.reshape(T, D) @ _w(lw["o_proj"], control)


def _mla(x, lw, z, eps, theta, control):
    T = x.shape[0]
    n, rank, nope, rope, dv = z["n"], z["rank"], z["nope"], z["rope"], z["dv"]
    pos = jnp.arange(T)
    q = (x @ _w(lw["mla_q_proj"], control)).reshape(T, n, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], pos, theta)], axis=-1)
    kva = x @ _w(lw["mla_kva_proj"], control)
    c = _rms(kva[:, :rank], lw["mla_kv_norm"], eps)
    r = _rope_pairs(kva[:, rank:], pos, theta)  # [T, rope], one for all heads
    kv = (c @ _w(lw["mla_kvb_proj"], control)).reshape(T, n, nope + dv)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(r[:, None, :], (T, n, rope))], axis=-1
    )
    v = kv[..., nope:]
    outs = []
    for lo in range(0, T, _Q_CHUNK):
        hi = min(T, lo + _Q_CHUNK)
        s = jnp.einsum("tnd,snd->nts", q[lo:hi], k[:hi]) / jnp.sqrt(F32(nope + rope))
        s = jnp.where((pos[lo:hi, None] >= pos[None, :hi])[None], s, -jnp.inf)
        outs.append(jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v[:hi]))
    o = jnp.concatenate(outs, axis=0)
    gate = jax.nn.sigmoid(x @ _w(lw["mla_g_proj"], control))  # [T, n]
    return (o * gate[:, :, None]).reshape(T, n * dv) @ _w(lw["o_proj"], control)


def _swiglu(x, gate, up, down, control):
    return (jax.nn.silu(x @ _w(gate, control)) * (x @ _w(up, control))) @ _w(down, control)


def _route(x, lw, z, route_cfg, control, forced=None):
    """Weights ``[T, held]`` of the held experts for every token, zero
    where an expert was not chosen, and the choice itself ``[T, E]`` (1
    where chosen), which ``forced`` replaces where it is given."""
    n_group, topk_group, k, scaling, norm, first = route_cfg
    T, E = x.shape[0], z["E"]
    s = jax.nn.sigmoid(x @ _w(lw["router"], control))
    choice = (s + lw["router_bias"].astype(F32)).reshape(T, n_group, E // n_group)
    group_score = jnp.sort(choice, axis=-1)[..., -2:].sum(axis=-1)
    threshold = jnp.sort(group_score, axis=-1)[:, -topk_group][:, None]
    choice = jnp.where((group_score >= threshold)[:, :, None], choice, -jnp.inf)
    chosen = jax.lax.top_k(choice.reshape(T, E), k)[1]  # [T, k]
    picked = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], chosen].set(1.0)
    if forced is not None:
        picked = forced
    w = picked * s
    if norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return (w * scaling)[:, first : first + z["held"]], picked


@partial(jax.jit, static_argnames=("control",))
def _expert_block(x, w, gate, up, down, *, control):
    """Sum over a block of experts of ``w[:, e] * E_e(x)``."""
    with jax.default_matmul_precision("highest"):
        h = jnp.einsum("th,ehi->eti", x, _w(gate, control))
        h = jax.nn.silu(h) * jnp.einsum("th,ehi->eti", x, _w(up, control))
        y = jnp.einsum("eti,eih->eth", h, _w(down, control))
        return jnp.einsum("eth,te->th", y, w)


@partial(jax.jit, static_argnames=("attn", "z", "eps", "theta", "lower", "control"))
def _attention_part(h, lw, prompt_len, *, attn, z, eps, theta, lower, control):
    with jax.default_matmul_precision("highest"):
        z = dict(z)
        x = _act(_rms(h, lw["ln1"], eps), control)
        a = _kda(x, lw, z, eps, lower, control, prompt_len) if attn == "kda" else _mla(
            x, lw, z, eps, theta, control
        )
        h = _act(h + _act(a, control), control)
        return h, _act(_rms(h, lw["ln2"], eps), control)


@partial(jax.jit, static_argnames=("z", "route_cfg", "control"))
def _shared_and_route(x, lw, forced=None, *, z, route_cfg, control):
    with jax.default_matmul_precision("highest"):
        shared = _swiglu(
            x, lw["shared_gate_proj"], lw["shared_up_proj"], lw["shared_down_proj"], control
        )
        return shared, *_route(x, lw, dict(z), route_cfg, control, forced)


@partial(jax.jit, static_argnames=("control",))
def _dense_mlp(x, lw, *, control):
    with jax.default_matmul_precision("highest"):
        return _swiglu(x, lw["gate_proj"], lw["up_proj"], lw["down_proj"], control)


@partial(jax.jit, static_argnames=("control",))
def _embed(embed, tokens, *, control):
    rows = embed[tokens].astype(F32)
    return _fake_int8(rows, -1) if control == "int8w" else rows


@partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_norm, head, *, eps, control):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm, eps)
        V = head.shape[1]
        step = -(-V // 8)
        return jnp.concatenate(
            [x @ _w(head[:, lo : lo + step], control) for lo in range(0, V, step)],
            axis=-1,
        )


def forward_logits(
    params: Dict[str, Any],
    cfg: Dict[str, Any],
    tokens: Sequence[int],
    positions: Sequence[int],
    control: Optional[str] = None,
):
    """Float32 logits [len(positions), vocab] of one full forward pass over
    ``tokens`` at the given positions."""
    forced = None
    if control == "bf16act_routed":
        forced = []
        _forward(params, cfg, tokens, positions, None, picks_out=forced)
    return _forward(params, cfg, tokens, positions, control, picks_in=forced)


def _forward(params, cfg, tokens, positions, control, picks_in=None, picks_out=None):
    """``picks_out`` collects each routed layer's choice ``[T, E]``;
    ``picks_in`` hands such a list back, a layer at a time."""
    z = _sizes(cfg)
    zt = tuple(sorted(z.items()))
    eps = float(cfg["rms_norm_eps"])
    first = int((cfg.get("experts_held") or [0])[0])
    route_cfg = (
        int(cfg["n_group"]), int(cfg["topk_group"]), int(cfg["num_experts_per_tok"]),
        float(cfg["routed_scaling_factor"]), bool(cfg["norm_topk_prob"]), first,
    )
    h = _embed(params["embed"], jnp.asarray(list(tokens), jnp.int32), control=control)
    prompt_len = jnp.int32(int(positions[0]) + 1)
    picks_in = iter(picks_in) if picks_in is not None else None
    groups = _groups(cfg)
    for name, attn, mlp, count in groups:
        for i in range(count):
            lw = {leaf: w[i] for leaf, w in params[name].items()}
            experts = {k: lw.pop(k) for k in list(lw) if k.startswith("expert_")}
            h, x = _attention_part(
                h, lw, prompt_len, attn=attn, z=zt, eps=eps, theta=float(cfg["rope_theta"]),
                lower=float(cfg["kda_lower_bound"]), control=control,
            )
            if mlp == "dense":
                h = _act(h + _act(_dense_mlp(x, lw, control=control), control), control)
                continue
            out, w, picked = _shared_and_route(
                x, lw, next(picks_in) if picks_in is not None else None,
                z=zt, route_cfg=route_cfg, control=control,
            )
            if picks_out is not None:
                picks_out.append(picked)
            routed = [(g[0], j) for g in groups if g[2] == "moe" for j in range(g[3])]
            dropped = {"moe_drop": routed[-1], "moe_drop_first": routed[0]}.get(control)
            if (name, i) == dropped:
                w = jnp.zeros_like(w)
            for lo in range(0, z["held"], _E_BLOCK):
                block = slice(lo, lo + _E_BLOCK)
                out = out + _expert_block(
                    x, w[:, block], experts["expert_gate_proj"][block],
                    experts["expert_up_proj"][block], experts["expert_down_proj"][block],
                    control=control,
                )
            h = _act(h + _act(out, control), control)
    rows = h[jnp.asarray(list(positions), jnp.int32)]
    return _head(rows, params["final_norm"], params["lm_head"], eps=eps, control=control)
