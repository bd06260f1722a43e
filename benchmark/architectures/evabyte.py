"""The ``evabyte`` architecture (EvaByte 6.5B: a byte-level model whose
attention is EVA): its served tree, how each leaf is made, its plain
reference and that reference's controls (see ``__init__.py`` for what the
harness asks of an architecture).

The tree: top-level ``embed``, ``final_norm`` and ``lm_head`` (prediction
head 0; the embedding is not tied), and ONE group ``stack0`` of all the
layers, stacked on a leading layer axis: every layer is the same (EVA
attention, SwiGLU MLP).

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, NO CACHE and NO
ROW MAP, no batching, nothing imported from the program. One sequence, one
layer at a time, each layer's matrices raised from the bf16 tree as they
are used, attention a block of query rows at a time against ALL the keys
before it with the window written as a mask, the MLP a slice of its width
at a time. The block, as ISSUE 50 writes it down (Zheng, Wang, Kong,
"Efficient Attention via Control Variates", ICLR 2023, as released with
the model; what the published configuration does not settle is marked
ASSUMED here and listed under ``assumed`` in the configuration's file):

- pre-norm residual layers, ``h = h + Attn(N1 h)``, ``h = h + FF(N2 h)``,
  RMSNorm with ``rms_norm_eps`` and the weight ``1 + w``
  (``norm_add_unit_offset``), a final RMSNorm, an untied head of
  ``vocab_size`` ids; the residual sum and the logits in float32
  (``fp32_skip_add``, ``fp32_logits``: here everything is);
- q, k, v as ``num_attention_heads`` heads of ``hidden / heads`` each
  (``num_key_value_heads`` equal: plain multi-head); q and k rotated,
  half-split pairs (ASSUMED), at ``rope_theta`` over the whole head;
- with ``W = window_size``, ``c = chunk_size``, ``s = 1 / sqrt(d)`` and two
  learned vectors a head and layer, ``mu`` and ``phi``: every chunk ``C``
  (``c`` consecutive positions, aligned to absolute positions: ASSUMED) has
  the summary ``k~_C = sum_{j in C} softmax_j(s k_j . mu) k_j`` of its
  ROTATED keys (not rotated again: ASSUMED) and ``v~_C = sum_{j in C}
  softmax_j(s k_j . phi) v_j``, each softmax over the chunk's positions
  with the same scale ``s`` (ASSUMED);
- the query at ``i``, in window ``w = i // W``, takes ONE softmax over the
  exact keys ``{k_j : j // W = w, j <= i}`` and the summaries ``{k~_C : C
  in a window < w}`` (a window's summaries are used only once the window is
  complete: ASSUMED), scores ``s q_i . k``, values ``v_j`` and ``v~_C``;
  ``W_o``;
- SwiGLU of ``intermediate_size``.

``mu`` and ``phi`` are made by the ``norm`` rule (1 + 0.1 n a value), NOT
the released 0.01 scale and not ``bias`` (0.1 n, which ISSUE 50 proposed):
the chunk softmax's logits ``s k . mu`` then have a standard deviation
near 1 and its weights are far from uniform, so that a program which took
plain chunk means (``mu_uniform``) computes something else by far more
than bf16 rounding; at 0.1 n the logits' deviation is 0.1 and the weights
lie within a tenth of uniform, which a bf16 program's own error would
hide. The norm weights are made by the ``bias`` rule (0.1 n: the weight is
``1 + w``).

``control`` puts something else in the reference's place, which the
comparison in ``correct.py`` has to refuse (``CONTROLS``). Two lower
precisions:

- ``"int8w"``: every matrix (projections, MLP, the embedding, the head)
  rounded to int8 with one scale per output channel;
- ``"fp8cache"``: each layer's rotated keys, values and their summaries
  rounded to float8 (e4m3), what an fp8 cache would hold.

And planted faults, what a program that left a piece of the block out
would compute:

- ``"summaries_off"``: the earlier windows dropped (a sliding window);
- ``"mu_uniform"``: plain chunk means for both summaries;
- ``"unit_offset_off"``: the norm weights taken as ``w``, not ``1 + w``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("int8w", "fp8cache", "summaries_off", "mu_uniform", "unit_offset_off")
_Q_CHUNK = 256  # query rows per attention block: scores [32, 256, 6148] float32, 201 MB
_I_BLOCKS = 4  # slices of the MLP's width raised to float32 at a time

_NORMS = ("ln1", "ln2", "final_norm")


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    H, n = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        H=H, n=n, d=int(cfg.get("head_dim") or H // n),
        I=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
        L=int(cfg["num_hidden_layers"]), W=int(cfg["window_size"]),
        c=int(cfg["chunk_size"]),
    )


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for the configuration's file."""
    z = _sizes(cfg)
    H, n, d, L, I = z["H"], z["n"], z["d"], z["L"], z["I"]
    return {
        "embed": (z["V"], H), "final_norm": (H,), "lm_head": (H, z["V"]),
        "stack0": dict(
            ln1=(L, H), ln2=(L, H),
            q_proj=(L, H, n * d), k_proj=(L, H, n * d), v_proj=(L, H, n * d),
            eva_mu=(L, n, d), eva_phi=(L, n, d), o_proj=(L, n * d, H),
            gate_proj=(L, H, I), up_proj=(L, H, I), down_proj=(L, I, H),
        ),
    }


def init_rule(name: str) -> str:
    """How ``weights.py`` makes the leaf of that name."""
    if name in _NORMS:
        return "bias"  # the weight is 1 + w
    return {
        "embed": "vocab_rows", "lm_head": "vocab_columns",
        "eva_mu": "norm", "eva_phi": "norm",
    }.get(name, "matrix")


def _fake_int8(w, axis: int):
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.round(w / scale).clip(-127, 127) * scale


def _w(x, control: Optional[str], axis: int = -2):
    x = x.astype(F32)
    return _fake_int8(x, axis) if control == "int8w" else x


def _rms(x, w, eps, control):
    w = w.astype(F32)
    if control != "unit_offset_off":
        w = 1.0 + w
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope_halves(x, positions, theta: float):
    """Rotate the halves of the last axis (value i pairs with value i + d /
    2). ``x``: [T, heads, d]."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _fp8(x):
    return jax.lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)


def _eva(x, lw, z, theta, control):
    """EVA attention over one sequence, every position's output."""
    T, n, d, W, c = x.shape[0], z["n"], z["d"], z["W"], z["c"]
    s = 1.0 / jnp.sqrt(F32(d))
    pos = jnp.arange(T)
    q = (x @ _w(lw["q_proj"], control)).reshape(T, n, d)
    k = (x @ _w(lw["k_proj"], control)).reshape(T, n, d)
    v = (x @ _w(lw["v_proj"], control)).reshape(T, n, d)
    q, k = _rope_halves(q, pos, theta), _rope_halves(k, pos, theta)
    if control == "fp8cache":
        k, v = _fp8(k), _fp8(v)
    # One summary a whole chunk; positions after the last whole chunk lie
    # in an incomplete window, whose summaries no query uses.
    G = T // c
    kc, vc = k[: G * c].reshape(G, c, n, d), v[: G * c].reshape(G, c, n, d)
    if control == "mu_uniform":
        wk = wv = jnp.full((G, c, n), 1.0 / c, F32)
    else:
        wk = jax.nn.softmax(s * jnp.einsum("gcnd,nd->gcn", kc, lw["eva_mu"].astype(F32)), axis=1)
        wv = jax.nn.softmax(s * jnp.einsum("gcnd,nd->gcn", kc, lw["eva_phi"].astype(F32)), axis=1)
    k_sum = jnp.einsum("gcn,gcnd->gnd", wk, kc)
    v_sum = jnp.einsum("gcn,gcnd->gnd", wv, vc)
    if control == "fp8cache":
        k_sum, v_sum = _fp8(k_sum), _fp8(v_sum)
    chunk_window = (jnp.arange(G) * c) // W
    outs = []
    for lo in range(0, T, _Q_CHUNK):
        hi = min(T, lo + _Q_CHUNK)
        window = pos[lo:hi] // W  # the queries' windows
        exact = s * jnp.einsum("tnd,jnd->ntj", q[lo:hi], k[:hi])
        own = (pos[None, :hi] // W == window[:, None]) & (pos[None, :hi] <= pos[lo:hi, None])
        exact = jnp.where(own[None], exact, -jnp.inf)
        earlier = s * jnp.einsum("tnd,gnd->ntg", q[lo:hi], k_sum)
        seen = chunk_window[None, :] < window[:, None]
        if control == "summaries_off":
            seen = jnp.zeros_like(seen)
        earlier = jnp.where(seen[None], earlier, -jnp.inf)
        p = jax.nn.softmax(jnp.concatenate([exact, earlier], axis=-1), axis=-1)
        outs.append(
            jnp.einsum("ntj,jnd->tnd", p[..., :hi], v[:hi])
            + jnp.einsum("ntg,gnd->tnd", p[..., hi:], v_sum)
        )
    return jnp.concatenate(outs, axis=0).reshape(T, n * d) @ _w(lw["o_proj"], control)


@partial(jax.jit, static_argnames=("z", "eps", "theta", "control"))
def _attention_part(h, lw, *, z, eps, theta, control):
    """``x = h + Attn(N1(h))`` and ``N2(x)``."""
    with jax.default_matmul_precision("highest"):
        h = h + _eva(_rms(h, lw["ln1"], eps, control), lw, dict(z), theta, control)
        return h, _rms(h, lw["ln2"], eps, control)


@partial(jax.jit, static_argnames=("control",))
def _mlp_slice(x, gate, up, down, *, control):
    """A slice of the SwiGLU's width: its part of the output."""
    with jax.default_matmul_precision("highest"):
        act = jax.nn.silu(x @ _w(gate, control)) * (x @ _w(up, control))
        return act @ down


@partial(jax.jit, static_argnames=("control",))
def _embed(embed, tokens, *, control):
    rows = embed[tokens].astype(F32)
    return _fake_int8(rows, -1) if control == "int8w" else rows


@partial(jax.jit, static_argnames=("eps", "control"))
def _head(h, final_norm, head, *, eps, control):
    with jax.default_matmul_precision("highest"):
        return _rms(h, final_norm, eps, control) @ _w(head, control)


def forward_logits(
    params: Dict[str, Any],
    cfg: Dict[str, Any],
    tokens: Sequence[int],
    positions: Sequence[int],
    control: Optional[str] = None,
):
    """Float32 logits [len(positions), vocab] of one full forward pass over
    ``tokens`` at the given positions."""
    z = _sizes(cfg)
    zt = tuple(sorted(z.items()))
    eps = float(cfg.get("rms_norm_eps", 1e-5))
    theta = float(cfg.get("rope_theta", 100000))
    h = _embed(params["embed"], jnp.asarray(list(tokens), jnp.int32), control=control)
    stack = params["stack0"]
    I = z["I"]
    step = -(-I // _I_BLOCKS)
    for i in range(z["L"]):
        # One layer's matrices at a time, so wait for the layer before.
        jax.block_until_ready(h)
        lw = {
            leaf: w[i] for leaf, w in stack.items()
            if leaf not in ("gate_proj", "up_proj", "down_proj")
        }
        h, x = _attention_part(h, lw, z=zt, eps=eps, theta=theta, control=control)
        # The down projection's int8 scale is a whole column's: rounded
        # before it is sliced by rows.
        down = _w(stack["down_proj"][i], control)
        for lo in range(0, I, step):
            h = h + _mlp_slice(
                x, stack["gate_proj"][i, :, lo : lo + step],
                stack["up_proj"][i, :, lo : lo + step], down[lo : lo + step],
                control=control,
            )
    rows = h[jnp.asarray(list(positions), jnp.int32)]
    return _head(rows, params["final_norm"], params["lm_head"], eps=eps, control=control)
