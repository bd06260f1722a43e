"""The Qwen2 architecture: its served tree, how each leaf is made, its
plain reference and that reference's controls (see ``__init__.py`` for
what the harness asks of an architecture).

The tree has the layout the program's ``Transformer`` reads (``embed``,
``layers/<name>`` stacked on a leading layer axis, ``final_norm``,
``lm_head`` where the embedding is not tied); the shapes are computed here
from the configuration file.

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, nothing imported from the program. One sequence at a time, one
layer at a time (a 3B or 7B float32 tree does not fit beside the served
one, so each layer's weights are raised from the bf16 tree as it is
used). Follows the published Qwen2 block: RMSNorm, QKV projections with
bias, rotate-half RoPE at ``rope_theta``, grouped-query causal attention,
SwiGLU MLP, residual adds, final RMSNorm, LM head (the embedding where
``tie_word_embeddings``).

``control`` switches one tempting lower precision on, which the
comparison in ``correct.py`` has to refuse:

- ``"int8w"``: every projection, the embedding and the head rounded to
  int8 with one scale per output channel (per row for the embedding), the
  program's own weight-only scheme;
- ``"fp8kv"``: keys and values rounded to float8_e5m2 before attention,
  the program's own fp8 pool.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("int8w", "fp8kv")
_Q_CHUNK = 512  # query rows per attention block: bounds the score matrix


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for an HF-style configuration."""
    H = int(cfg["hidden_size"])
    L = int(cfg["num_hidden_layers"])
    nh = int(cfg["num_attention_heads"])
    nkv = int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or H // nh)
    I = int(cfg["intermediate_size"])
    V = int(cfg["vocab_size"])
    shapes = {
        "embed": (V, H),
        "final_norm": (H,),
        "layers": {
            "ln1": (L, H),
            "ln2": (L, H),
            "q_proj": (L, H, nh * d),
            "k_proj": (L, H, nkv * d),
            "v_proj": (L, H, nkv * d),
            "o_proj": (L, nh * d, H),
            "gate_proj": (L, H, I),
            "up_proj": (L, H, I),
            "down_proj": (L, I, H),
        },
    }
    if cfg.get("attention_bias", cfg.get("model_type") == "qwen2"):
        shapes["layers"].update(
            q_bias=(L, nh * d), k_bias=(L, nkv * d), v_bias=(L, nkv * d)
        )
    if not cfg.get("tie_word_embeddings", False):
        shapes["lm_head"] = (H, V)
    return shapes


def init_rule(name: str) -> str:
    """How ``weights.py`` makes the leaf of that name."""
    if name in ("ln1", "ln2", "final_norm"):
        return "norm"
    if name.endswith("_bias"):
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


def _rope(x, positions, theta: float):
    # x [T, n, d]; rotate-half convention of the published implementation.
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@partial(jax.jit, static_argnames=("nh", "nkv", "eps", "theta", "control"))
def _layer(h, lw, *, nh, nkv, eps, theta, control):
    with jax.default_matmul_precision("highest"):
        T, H = h.shape
        d = lw["q_proj"].shape[-1] // nh
        x = _rms(h, lw["ln1"], eps)
        q = x @ _w(lw["q_proj"], control)
        k = x @ _w(lw["k_proj"], control)
        v = x @ _w(lw["v_proj"], control)
        if "q_bias" in lw:  # Qwen2: bias on q, k and v, none on o
            q = q + lw["q_bias"].astype(F32)
            k = k + lw["k_bias"].astype(F32)
            v = v + lw["v_bias"].astype(F32)
        pos = jnp.arange(T)
        q = _rope(q.reshape(T, nh, d), pos, theta)
        k = _rope(k.reshape(T, nkv, d), pos, theta)
        v = v.reshape(T, nkv, d)
        if control == "fp8kv":
            k = k.astype(jnp.float8_e5m2).astype(F32)
            v = v.astype(jnp.float8_e5m2).astype(F32)
        g = nh // nkv
        qg = q.reshape(T, nkv, g, d)
        outs = []
        for lo in range(0, T, _Q_CHUNK):
            hi = min(T, lo + _Q_CHUNK)
            s = jnp.einsum("tkgd,skd->kgts", qg[lo:hi], k[:hi]) / jnp.sqrt(F32(d))
            mask = pos[lo:hi, None] >= pos[None, :hi]
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("kgts,skd->tkgd", p, v[:hi]))
        a = jnp.concatenate(outs, axis=0).reshape(T, nh * d)
        h = h + a @ _w(lw["o_proj"], control)
        x = _rms(h, lw["ln2"], eps)
        gate = x @ _w(lw["gate_proj"], control)
        up = x @ _w(lw["up_proj"], control)
        return h + (jax.nn.silu(gate) * up) @ _w(lw["down_proj"], control)


@partial(jax.jit, static_argnames=("control",))
def _embed(embed, tokens, *, control):
    rows = embed[tokens].astype(F32)
    if control == "int8w":
        # Per-row scales: the rows taken are rounded exactly as in the table.
        rows = _fake_int8(rows, -1)
    return rows


@partial(jax.jit, static_argnames=("eps", "control", "tied"))
def _head(h, final_norm, head, *, eps, control, tied):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, final_norm, eps)
        V = head.shape[0] if tied else head.shape[1]
        step = -(-V // 8)
        parts = []
        for lo in range(0, V, step):  # the float32 head in eighths
            if tied:
                w = _w(head[lo : lo + step], control, axis=-1)
                parts.append(x @ w.T)
            else:
                w = _w(head[:, lo : lo + step], control)
                parts.append(x @ w)
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
    nh = int(cfg["num_attention_heads"])
    nkv = int(cfg["num_key_value_heads"])
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    toks = jnp.asarray(list(tokens), jnp.int32)
    h = _embed(params["embed"], toks, control=control)
    layers = params["layers"]
    for i in range(int(cfg["num_hidden_layers"])):
        lw = {name: w[i] for name, w in layers.items()}
        h = _layer(h, lw, nh=nh, nkv=nkv, eps=eps, theta=theta, control=control)
    tied = "lm_head" not in params
    head = params["embed"] if tied else params["lm_head"]
    rows = h[jnp.asarray(list(positions), jnp.int32)]
    return _head(rows, params["final_norm"], head, eps=eps, control=control, tied=tied)
