"""The ``lfm2_moe`` architecture (LFM2 with routed experts: LFM2-24B-A2B):
its served tree, how each leaf is made, its plain reference and that
reference's controls (see ``__init__.py`` for what the harness asks of an
architecture).

The tree: top-level ``embed`` and ``final_norm`` (the published
``embedding_norm``), ``lm_head`` only where ``tie_embedding`` is false,
and one group ``stack<i>`` for every run of consecutive layers of one
(operator, MLP) kind, stacked on a leading layer axis. The layers are
those of ``kept_layers`` (published indices; default all): layer ``j`` of
them has the operator ``layer_types[kept_layers[j]]`` and a dense MLP
where ``j < num_dense_layers``, routed experts after. Every expert is
held.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
tail, no batching, nothing imported from the program. One sequence, one
layer at a time, each layer's weights raised from the bf16 tree as they
are used, the experts a block at a time and attention a block of query
rows at a time, so that 4,096 positions fit beside the served tree. The
block, as ISSUE 42 writes it down (``Lfm2MoeDecoderLayer``); what the
published configuration does not settle is marked ASSUMED here and listed
under ``assumed`` in the configuration's file:

- pre-norm residual layers, ``h = h + Op(N1 h)``, ``h = h + FF(N2 h)``,
  RMSNorm with ``norm_eps``, a final RMSNorm, the head tied to the
  embedding (ASSUMED: the family's default; the config has no such key);
- ``conv``: ``[B ; C ; u] = W_in x`` split in that order, ``z_t = sum_j
  w_j (B * u)_{t - K + 1 + j}`` over ``K = conv_L_cache`` taps, depth-wise
  and causal, zeros before the prompt, computed as K shifted products;
  ``y = W_out (C * z)``; no bias (``conv_bias`` false), no activation;
- ``full_attention``: q as ``num_attention_heads`` heads and k, v as
  ``num_key_value_heads`` heads of ``hidden / heads``; q and k through an
  RMSNorm over the head with one weight a layer shared by the heads,
  BEFORE the rotary embedding (ASSUMED order: the family's released
  code); rotary half-split (``rope_type`` default) at ``rope_theta`` over
  the whole head; causal softmax of ``q k^T / sqrt(d)``, a kv head shared
  by ``heads / kv heads`` query heads; ``W_o``;
- dense layers: SwiGLU of ``intermediate_size``; expert layers: ``s =
  sigmoid(W_r x)`` in float32, the ``num_experts_per_tok`` largest of ``s +
  expert_bias`` chosen (``use_expert_bias``: the bias chooses and does not
  weigh), weights ``s_e / (sum of the chosen s + 1e-6)`` (ASSUMED
  epsilon: the released code's) times ``routed_scaling_factor``, no
  shared expert. ``expert_bias`` is the leaf ``router_bias``, made by the
  ``bias`` rule, so a seeded bias is not zero and the choice differs from
  the largest scores.

``control`` puts something else in the reference's place, which the
comparison in ``correct.py`` has to refuse (``CONTROLS``). Two lower
precisions:

- ``"int8w"``: every matrix (projections, taps, experts, router, the
  embedding and so the tied head) rounded to int8 with one scale per
  output channel;
- ``"fp8cache"``: each attention layer's keys (normed and rotated) and
  values rounded to float8 (e4m3), what an fp8 K/V cache would hold.

And planted faults, what a program that left a piece of the block out
would compute:

- ``"qk_norm_off"``: the two per-head norms left out;
- ``"bias_off"``: the experts chosen by score alone;
- ``"bias_weighs"``: the bias added to the weights as well;
- ``"topk_norm_off"``: the chosen scores not normalised by their sum;
- ``"tail_off"``: the convolution's taps before the current token left
  out (a program that lost its tail);
- ``"moe_drop"``: the last expert layer's experts left out.

Two that are no controls but a diagnosis (``DIAGNOSES``, as in
``pangu_ultra_moe.py``): ``"bf16act"`` rounds the residual stream, every
normed input and every sub-layer output to bfloat16, about what the
program's arithmetic does; ``"bf16act_routed"`` does the same but chooses
each token's experts as the float32 pass chose them.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = (
    "int8w", "fp8cache", "qk_norm_off", "bias_off", "bias_weighs",
    "topk_norm_off", "tail_off", "moe_drop",
)
DIAGNOSES = ("bf16act", "bf16act_routed")
_Q_CHUNK = 256  # query rows per attention block: scores [32, 256, 4096] float32, 134 MB
_E_BLOCK = 4  # experts raised to float32 at a time (4 x 3 x 12.6 MB)
_ROUTER_EPS = 1e-6

_NORMS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
_KINDS = {"conv": "conv", "full_attention": "gqa"}


def _layers(cfg: Dict[str, Any]) -> List[Tuple[str, str]]:
    """(operator, mlp) of every kept layer."""
    kept = cfg.get("kept_layers")
    if kept is None:
        kept = range(int(cfg["num_hidden_layers"]))
    dense = int(cfg.get("num_dense_layers", 0))
    return [
        (_KINDS[cfg["layer_types"][int(i)]], "dense" if j < dense else "moe")
        for j, i in enumerate(kept)
    ]


def _groups(cfg: Dict[str, Any]) -> List[Tuple[str, str, str, int]]:
    """(name, operator, mlp, layers) of each run of equal layers."""
    runs: List[List[Any]] = []
    for kind in _layers(cfg):
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return [(f"stack{i}", op, mlp, n) for i, ((op, mlp), n) in enumerate(runs)]


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    H, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        H=H, n=n, n_kv=int(cfg.get("num_key_value_heads", n)),
        d=int(cfg.get("head_dim") or H // n), I=int(cfg["intermediate_size"]),
        V=int(cfg["vocab_size"]), K=int(cfg.get("conv_L_cache", 3)),
        E=int(cfg["num_experts"]), Im=int(cfg["moe_intermediate_size"]),
    )


def _tied(cfg: Dict[str, Any]) -> bool:
    return bool(cfg.get("tie_embedding", cfg.get("tie_word_embeddings", True)))


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for the configuration's file."""
    z = _sizes(cfg)
    H, n, n_kv, d = z["H"], z["n"], z["n_kv"], z["d"]
    shapes: Dict[str, Any] = {"embed": (z["V"], H), "final_norm": (H,)}
    if not _tied(cfg):
        shapes["lm_head"] = (H, z["V"])
    for name, op, mlp, L in _groups(cfg):
        leaves: Dict[str, tuple] = {"ln1": (L, H), "ln2": (L, H)}
        if op == "conv":
            leaves.update(
                conv_in_proj=(L, H, 3 * H), conv_w=(L, z["K"], H), o_proj=(L, H, H),
            )
        else:
            leaves.update(
                q_proj=(L, H, n * d), k_proj=(L, H, n_kv * d), v_proj=(L, H, n_kv * d),
                q_norm=(L, d), k_norm=(L, d), o_proj=(L, n * d, H),
            )
        if mlp == "dense":
            leaves.update(
                gate_proj=(L, H, z["I"]), up_proj=(L, H, z["I"]),
                down_proj=(L, z["I"], H),
            )
        else:
            if cfg.get("use_expert_bias", True):
                leaves.update(router_bias=(L, z["E"]))
            leaves.update(
                router=(L, H, z["E"]),
                expert_gate_proj=(L, z["E"], H, z["Im"]),
                expert_up_proj=(L, z["E"], H, z["Im"]),
                expert_down_proj=(L, z["E"], z["Im"], H),
            )
        shapes[name] = leaves
    return shapes


def init_rule(name: str) -> str:
    """How ``weights.py`` makes the leaf of that name."""
    if name in _NORMS:
        return "norm"
    return {
        "embed": "vocab_rows", "lm_head": "vocab_columns", "router_bias": "bias",
    }.get(name, "matrix")


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


def _rope_halves(x, positions, theta: float):
    """Rotate the halves of the last axis (``rope_type`` default: value i
    pairs with value i + d / 2). ``x``: [T, heads, d]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _conv(x, lw, z, control):
    """The gated short convolution, as K shifted products."""
    T, H, K = x.shape[0], z["H"], z["K"]
    bcu = x @ _w(lw["conv_in_proj"], control)
    b, c, u = bcu[:, :H], bcu[:, H : 2 * H], bcu[:, 2 * H :]
    bu = b * u
    taps = _w(lw["conv_w"], control, axis=0)  # [K, H]: a channel's taps share a scale
    out = taps[K - 1] * bu
    if control != "tail_off":
        for back in range(1, K):  # the input ``back`` positions before
            shifted = jnp.concatenate([jnp.zeros((back, H), F32), bu[: T - back]], axis=0)
            out = out + taps[K - 1 - back] * shifted
    return (c * out) @ _w(lw["o_proj"], control)


def _gqa(x, lw, z, eps, theta, control):
    T, n, n_kv, d = x.shape[0], z["n"], z["n_kv"], z["d"]
    pos = jnp.arange(T)
    q = (x @ _w(lw["q_proj"], control)).reshape(T, n, d)
    k = (x @ _w(lw["k_proj"], control)).reshape(T, n_kv, d)
    v = (x @ _w(lw["v_proj"], control)).reshape(T, n_kv, d)
    if control != "qk_norm_off":
        q, k = _rms(q, lw["q_norm"], eps), _rms(k, lw["k_norm"], eps)
    q, k = _rope_halves(q, pos, theta), _rope_halves(k, pos, theta)
    if control == "fp8cache":
        k, v = (
            jax.lax.reduce_precision(part, exponent_bits=4, mantissa_bits=3)
            for part in (k, v)
        )
    rep = n // n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # a kv head's queries
    outs = []
    for lo in range(0, T, _Q_CHUNK):
        hi = min(T, lo + _Q_CHUNK)
        s = jnp.einsum("tnd,snd->nts", q[lo:hi], k[:hi]) / jnp.sqrt(F32(d))
        s = jnp.where((pos[lo:hi, None] >= pos[None, :hi])[None], s, -jnp.inf)
        outs.append(jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v[:hi]))
    return jnp.concatenate(outs, axis=0).reshape(T, n * d) @ _w(lw["o_proj"], control)


def _swiglu(x, gate, up, down, control):
    return (jax.nn.silu(x @ _w(gate, control)) * (x @ _w(up, control))) @ _w(down, control)


def _route(x, lw, z, route_cfg, control, forced=None):
    """Weights ``[T, E]`` of the experts for every token, zero where an
    expert was not chosen, and the choice itself (1 where chosen), which
    ``forced`` replaces where it is given."""
    k, scaling, norm = route_cfg
    T, E = x.shape[0], z["E"]
    s = jax.nn.sigmoid(x @ _w(lw["router"], control))
    bias = lw["router_bias"].astype(F32) if "router_bias" in lw else 0.0
    by = s if control == "bias_off" else s + bias  # the bias chooses
    chosen = jax.lax.top_k(by, k)[1]
    picked = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], chosen].set(1.0)
    if forced is not None:
        picked = forced
    w = picked * (s + bias if control == "bias_weighs" else s)  # and does not weigh
    if norm and control != "topk_norm_off":
        w = w / (w.sum(axis=-1, keepdims=True) + _ROUTER_EPS)
    return w * scaling, picked


@partial(jax.jit, static_argnames=("control",))
def _expert_block(x, w, gate, up, down, *, control):
    """Sum over a block of experts of ``w[:, e] * E_e(x)``."""
    with jax.default_matmul_precision("highest"):
        h = jnp.einsum("th,ehi->eti", x, _w(gate, control))
        h = jax.nn.silu(h) * jnp.einsum("th,ehi->eti", x, _w(up, control))
        y = jnp.einsum("eti,eih->eth", h, _w(down, control))
        return jnp.einsum("eth,te->th", y, w)


@partial(jax.jit, static_argnames=("op", "z", "eps", "theta", "control"))
def _operator_part(h, lw, *, op, z, eps, theta, control):
    """``x = h + Op(N1(h))`` and ``N2(x)``."""
    with jax.default_matmul_precision("highest"):
        x = _act(_rms(h, lw["ln1"], eps), control)
        if op == "conv":
            a = _conv(x, lw, dict(z), control)
        else:
            a = _gqa(x, lw, dict(z), eps, theta, control)
        h = _act(h + _act(a, control), control)
        return h, _act(_rms(h, lw["ln2"], eps), control)


@partial(jax.jit, static_argnames=("control",))
def _add(h, m, *, control):
    return _act(h + _act(m, control), control)


@partial(jax.jit, static_argnames=("z", "route_cfg", "control"))
def _routed(x, lw, forced=None, *, z, route_cfg, control):
    with jax.default_matmul_precision("highest"):
        return _route(x, lw, dict(z), route_cfg, control, forced)


@partial(jax.jit, static_argnames=("control",))
def _dense_mlp(x, gate, up, down, *, control):
    with jax.default_matmul_precision("highest"):
        return _swiglu(x, gate, up, down, control)


@partial(jax.jit, static_argnames=("control",))
def _embed(embed, tokens, *, control):
    rows = embed[tokens].astype(F32)
    return _fake_int8(rows, -1) if control == "int8w" else rows


@partial(jax.jit, static_argnames=("eps", "tied", "control"))
def _head(h, final_norm, head, *, eps, tied, control):
    """``head``: the embedding ``[V, H]`` where it is tied (its int8 scale
    is a row's, the one the lookup used), else ``lm_head`` ``[H, V]``."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm, eps)
        V = head.shape[0] if tied else head.shape[1]
        step = -(-V // 8)
        parts = []
        for lo in range(0, V, step):
            if tied:
                parts.append(x @ _w(head[lo : lo + step], control, axis=-1).T)
            else:
                parts.append(x @ _w(head[:, lo : lo + step], control))
        return jnp.concatenate(parts, axis=-1)


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
    eps = float(cfg.get("norm_eps", 1e-5))
    theta = float((cfg.get("rope_parameters") or {}).get("rope_theta", 1e6))
    route_cfg = (
        int(cfg["num_experts_per_tok"]), float(cfg.get("routed_scaling_factor", 1.0)),
        bool(cfg.get("norm_topk_prob", True)),
    )
    h = _embed(params["embed"], jnp.asarray(list(tokens), jnp.int32), control=control)
    picks_in = iter(picks_in) if picks_in is not None else None
    groups = _groups(cfg)
    routed = [(name, j) for name, _, mlp, count in groups if mlp == "moe" for j in range(count)]
    dropped = routed[-1:] if control == "moe_drop" else []
    for name, op, mlp, count in groups:
        stack = params[name]
        for i in range(count):
            # A layer's operator, norms and router, cut out of the stack;
            # one layer's at a time, so wait for the layer before.
            jax.block_until_ready(h)
            lw = {
                leaf: w[i] for leaf, w in stack.items()
                if not leaf.startswith(("expert_", "gate_proj", "up_proj", "down_proj"))
            }
            h, x = _operator_part(h, lw, op=op, z=zt, eps=eps, theta=theta, control=control)
            if mlp == "dense":
                m = _dense_mlp(
                    x, stack["gate_proj"][i], stack["up_proj"][i], stack["down_proj"][i],
                    control=control,
                )
                h = _add(h, m, control=control)
                continue
            w, picked = _routed(
                x, lw, next(picks_in) if picks_in is not None else None,
                z=zt, route_cfg=route_cfg, control=control,
            )
            if picks_out is not None:
                picks_out.append(picked)
            if (name, i) in dropped:
                w = jnp.zeros_like(w)
            out = 0.0
            for lo in range(0, z["E"], _E_BLOCK):
                block = slice(lo, lo + _E_BLOCK)
                out = out + _expert_block(
                    x, w[:, block], stack["expert_gate_proj"][i, block],
                    stack["expert_up_proj"][i, block], stack["expert_down_proj"][i, block],
                    control=control,
                )
            h = _add(h, out, control=control)
    rows = h[jnp.asarray(list(positions), jnp.int32)]
    tied = "lm_head" not in params
    return _head(
        rows, params["final_norm"], params["embed"] if tied else params["lm_head"],
        eps=eps, tied=tied, control=control,
    )
