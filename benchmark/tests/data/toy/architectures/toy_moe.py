"""Test data: an architecture that the harness does not know, added by
files alone (``tests/test_architecture_by_name.py``).

Two stacked groups: ``dense_layers`` (the leading layers, a SwiGLU MLP)
and ``moe_layers`` (a sigmoid router over ``n_routed_experts`` with a
score-correction bias that only the choice sees, ``num_experts_per_tok``
of them renormalised and scaled by ``routed_scaling_factor``, plus one
shared expert). Grouped-query causal attention with rotate-half RoPE,
RMSNorm, untied head. Plain ``jax.numpy`` in float32 at ``highest``, one
sequence, one layer at a time, every expert computed for every token.
``control="int8w"`` rounds every matrix to int8 with one scale per output
channel (per row for the embedding).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("int8w",)
_ATTENTION = {"ln1": "H", "ln2": "H", "q_proj": "Hq", "k_proj": "Hk", "v_proj": "Hk", "o_proj": "qH"}


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    H, V = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    d = int(cfg["head_dim"])
    q, k = int(cfg["num_attention_heads"]) * d, int(cfg["num_key_value_heads"]) * d
    I, Im = int(cfg["intermediate_size"]), int(cfg["moe_intermediate_size"])
    E = int(cfg["n_routed_experts"])
    Ld = int(cfg["first_k_dense_replace"])
    Lm = int(cfg["num_hidden_layers"]) - Ld
    size = {"H": (H,), "Hq": (H, q), "Hk": (H, k), "qH": (q, H)}

    def attention(L):
        return {name: (L,) + size[s] for name, s in _ATTENTION.items()}

    return {
        "embed": (V, H),
        "final_norm": (H,),
        "lm_head": (H, V),
        "dense_layers": dict(
            attention(Ld), gate_proj=(Ld, H, I), up_proj=(Ld, H, I), down_proj=(Ld, I, H)
        ),
        "moe_layers": dict(
            attention(Lm),
            router=(Lm, H, E), router_bias=(Lm, E),
            experts_gate=(Lm, E, H, Im), experts_up=(Lm, E, H, Im), experts_down=(Lm, E, Im, H),
            shared_gate=(Lm, H, Im), shared_up=(Lm, H, Im), shared_down=(Lm, Im, H),
        ),
    }


def init_rule(name: str) -> str:
    if name in ("ln1", "ln2", "final_norm"):
        return "norm"
    if name.endswith("_bias"):
        return "bias"
    return {"embed": "vocab_rows", "lm_head": "vocab_columns"}.get(name, "matrix")


def _w(x, control: Optional[str], axis: int = -2):
    x = x.astype(F32)
    if control != "int8w":
        return x
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(x / scale).clip(-127, 127) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta: float):
    T, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, lw, nh, nkv, d, eps, theta, control):
    T = h.shape[0]
    x = _rms(h, lw["ln1"], eps)
    q = _rope((x @ _w(lw["q_proj"], control)).reshape(T, nh, d), theta)
    k = _rope((x @ _w(lw["k_proj"], control)).reshape(T, nkv, d), theta)
    v = (x @ _w(lw["v_proj"], control)).reshape(T, nkv, d)
    s = jnp.einsum("tkgd,skd->kgts", q.reshape(T, nkv, nh // nkv, d), k) / jnp.sqrt(F32(d))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("kgts,skd->tkgd", p, v).reshape(T, nh * d)
    return h + a @ _w(lw["o_proj"], control)


def _swiglu(x, gate, up, down, control):
    return (jax.nn.silu(x @ _w(gate, control)) * (x @ _w(up, control))) @ _w(down, control)


def _moe(x, lw, top, scaling, control):
    scores = jax.nn.sigmoid(x @ _w(lw["router"], control))  # [T, E]
    _, chosen = jax.lax.top_k(scores + lw["router_bias"].astype(F32), top)
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True) * scaling
    weight = jnp.zeros_like(scores).at[jnp.arange(x.shape[0])[:, None], chosen].set(gates)
    every = jnp.stack([
        _swiglu(x, lw["experts_gate"][e], lw["experts_up"][e], lw["experts_down"][e], control)
        for e in range(scores.shape[-1])
    ], axis=1)  # [T, E, H]
    routed = jnp.einsum("te,teh->th", weight, every)
    return routed + _swiglu(x, lw["shared_gate"], lw["shared_up"], lw["shared_down"], control)


@partial(jax.jit, static_argnames=("sizes", "control"))
def _layer(h, lw, *, sizes, control):
    nh, nkv, d, eps, theta, top, scaling = sizes
    with jax.default_matmul_precision("highest"):
        h = _attention(h, lw, nh, nkv, d, eps, theta, control)
        x = _rms(h, lw["ln2"], eps)
        if "router" in lw:
            return h + _moe(x, lw, top, scaling, control)
        return h + _swiglu(x, lw["gate_proj"], lw["up_proj"], lw["down_proj"], control)


def forward_logits(
    params: Dict[str, Any],
    cfg: Dict[str, Any],
    tokens: Sequence[int],
    positions: Sequence[int],
    control: Optional[str] = None,
):
    eps = float(cfg["rms_norm_eps"])
    sizes = (
        int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
        eps, float(cfg["rope_theta"]), int(cfg["num_experts_per_tok"]),
        float(cfg["routed_scaling_factor"]),
    )
    h = _w(params["embed"][jnp.asarray(list(tokens), jnp.int32)], control, axis=-1)
    for group in ("dense_layers", "moe_layers"):
        stack = params[group]
        for i in range(next(iter(stack.values())).shape[0]):
            h = _layer(h, {name: w[i] for name, w in stack.items()}, sizes=sizes, control=control)
    with jax.default_matmul_precision("highest"):
        rows = _rms(h[jnp.asarray(list(positions), jnp.int32)], params["final_norm"], eps)
        return rows @ _w(params["lm_head"], control)
