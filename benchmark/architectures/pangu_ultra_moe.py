"""The ``pangu_ultra_moe`` architecture (openPangu-Ultra-MoE): its served
tree, how each leaf is made, its plain reference and that reference's
controls (see ``__init__.py`` for what the harness asks of an
architecture).

The tree: top-level ``embed``, ``final_norm``, ``lm_head``, and one group
``stack<i>`` for every run of consecutive layers of one kind, stacked on a
leading layer axis: the ``first_k_dense_replace`` leading layers with a
dense MLP, the rest with routed experts. Every layer is latent attention.
Of the ``n_routed_experts_published`` experts the router scores, the tree
holds ``experts_held = [first, count]`` (``n_routed_experts`` of the file
is that count).

The reference is straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
absorption, no batching, nothing imported from the program. One sequence,
one layer at a time, each layer's weights raised from the bf16 tree as
they are used, the experts and the dense MLP's columns a block at a time
and attention a block of query rows at a time, so that what is in flight
fits beside the served tree. The block, as ISSUE 39 writes it down; what
the published configuration does not settle is marked ASSUMED here and
listed under ``assumed`` in the configuration's file:

- residual path with sandwich norms (``sandwich_norm``): ``x = h +
  N2(MLA(N1(h)))``, ``h' = x + N4(F(N3(x)))``, four RMSNorms a layer, the
  second and fourth on each sub-layer's OUTPUT before the add (ASSUMED
  placement: the family's published sandwich norm; its depth-scaled
  initial gains do not matter to seeded weights); final RMSNorm, untied
  head;
- MLA with a query LoRA (``q_lora_rank``): ``c_q = RMSNorm(W_qa x)``, ``q
  = W_qb c_q``, a head 128 + 64 values; ``[c_kv ; k_r] = W_kva x``, ``c =
  RMSNorm(c_kv)``; rotary embedding (``rope_theta``, ASSUMED: no scaling,
  the config has no such key; pairs (0, 1), (2, 3), ... rotated in place:
  the program brings them to halves first, which permutes q_r and k_r
  alike) on ``q_r`` and on ``k_r``, one for all heads; keys ``[W_uk c ;
  k_r]`` and values ``W_uv c`` EXPANDED for every position and head;
  scores scaled by (128 + 64)^-1/2 (ASSUMED: no further factor), causal
  softmax, ``W_o``. No output gate;
- dense layers: SwiGLU of ``intermediate_size``; expert layers: the shared
  expert, a SwiGLU of ``n_shared_experts x moe_intermediate_size``
  (ASSUMED: the shared experts run as one of their summed width), plus
  ``routed_scaling_factor * sum over the top 8 of (s_e / (sum of the 8 s
  + 1e-20)) * SwiGLU_e``, ``s = sigmoid(W_r x)`` in float32 (ASSUMED from
  the family's released code: sigmoid scores, one group, no selection
  bias: the config has no ``scoring_func``, ``n_group``, ``topk_group``).
  Only the chosen experts that are HELD are computed and added. What the
  other chips' experts would add is left out, here as in the program, and
  the partial sum goes through ``N4`` to the next layer;
- the multi-token-prediction layer (``num_nextn_predict_layers``) is not
  built (a departure, in ``reduced``).

``control`` puts something else in the reference's place, which the
comparison in ``correct.py`` has to refuse (``CONTROLS``). Two lower
precisions:

- ``"int8w"``: every matrix (projections, experts, router, embedding,
  head) rounded to int8 with one scale per output channel;
- ``"fp8cache"``: the cached latent row ``[c ; k_r]`` rounded to float8
  (e4m3) before keys and values are raised from it.

And planted faults, what a program that left a piece of the block out
would compute:

- ``"q_norm_off"``: the query latent's RMSNorm left out;
- ``"post_attn_norm_off"``, ``"post_mlp_norm_off"``: one of the two output
  norms left out on every layer;
- ``"scale_off"``: the routed sum not scaled by ``routed_scaling_factor``;
- ``"topk_norm_off"``: the 8 chosen scores not normalised by their sum;
- ``"moe_drop"``, ``"moe_drop_first"``: the routed experts of the last, or
  of the first, expert layer left out (its shared expert stays).

Two that are no controls but a diagnosis (``DIAGNOSES``, as in
``bailing_hybrid.py``): ``"bf16act"`` rounds the residual stream, every
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
    "int8w", "fp8cache", "q_norm_off", "post_attn_norm_off", "post_mlp_norm_off",
    "scale_off", "topk_norm_off", "moe_drop", "moe_drop_first",
)
DIAGNOSES = ("bf16act", "bf16act_routed")
_Q_CHUNK = 256  # query rows per attention block
_HEAD_GROUP = 32  # heads expanded at a time: scores [32, 256, 3080] float32, 101 MB
_E_BLOCK = 2  # experts raised to float32 at a time (2 x 3 x 63 MB)
_I_BLOCK = 4608  # columns of the dense MLP raised to float32 at a time

_NORMS = (
    "ln1", "ln2", "post_attn_norm", "post_mlp_norm", "final_norm", "mla_q_norm",
    "mla_kv_norm",
)


def _groups(cfg: Dict[str, Any]) -> List[Tuple[str, str, int]]:
    """(name, mlp, layers) of each run of equal layers."""
    layers, dense = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    runs = [("dense", min(dense, layers)), ("moe", max(layers - dense, 0))]
    return [(f"stack{i}", mlp, n) for i, (mlp, n) in enumerate(r for r in runs if r[1])]


def _sizes(cfg: Dict[str, Any]) -> Dict[str, int]:
    return dict(
        H=int(cfg["hidden_size"]), n=int(cfg["num_attention_heads"]),
        I=int(cfg["intermediate_size"]), V=int(cfg["vocab_size"]),
        q_rank=int(cfg.get("q_lora_rank") or 0), rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        dv=int(cfg["v_head_dim"]),
        E=int(cfg.get("n_routed_experts_published", cfg["n_routed_experts"])),
        held=int(cfg["n_routed_experts"]), Im=int(cfg["moe_intermediate_size"]),
        Is=int(cfg["moe_intermediate_size"]) * int(cfg.get("n_shared_experts", 1)),
    )


def tree_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the served tree for the configuration's file."""
    z = _sizes(cfg)
    H, n = z["H"], z["n"]
    qk = z["nope"] + z["rope"]
    shapes: Dict[str, Any] = {
        "embed": (z["V"], H), "final_norm": (H,), "lm_head": (H, z["V"]),
    }
    for name, mlp, L in _groups(cfg):
        leaves: Dict[str, tuple] = {
            "ln1": (L, H), "ln2": (L, H),
            "mla_kva_proj": (L, H, z["rank"] + z["rope"]),
            "mla_kv_norm": (L, z["rank"]),
            "mla_kvb_proj": (L, z["rank"], n * (z["nope"] + z["dv"])),
            "o_proj": (L, n * z["dv"], H),
        }
        if z["q_rank"]:
            leaves.update(
                mla_qa_proj=(L, H, z["q_rank"]), mla_q_norm=(L, z["q_rank"]),
                mla_qb_proj=(L, z["q_rank"], n * qk),
            )
        else:
            leaves.update(mla_q_proj=(L, H, n * qk))
        if cfg.get("sandwich_norm"):
            leaves.update(post_attn_norm=(L, H), post_mlp_norm=(L, H))
        if mlp == "dense":
            leaves.update(
                gate_proj=(L, H, z["I"]), up_proj=(L, H, z["I"]),
                down_proj=(L, z["I"], H),
            )
        else:
            leaves.update(
                router=(L, H, z["E"]),
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


def _mla(x, lw, z, eps, theta, control):
    T = x.shape[0]
    n, rank, nope, rope, dv = z["n"], z["rank"], z["nope"], z["rope"], z["dv"]
    pos = jnp.arange(T)
    if "mla_qa_proj" in lw:
        q_in = x @ _w(lw["mla_qa_proj"], control)
        if control != "q_norm_off":
            q_in = _rms(q_in, lw["mla_q_norm"], eps)
        w_q = lw["mla_qb_proj"]
    else:
        q_in, w_q = x, lw["mla_q_proj"]
    kva = x @ _w(lw["mla_kva_proj"], control)
    c = _rms(kva[:, :rank], lw["mla_kv_norm"], eps)
    r = _rope_pairs(kva[:, rank:], pos, theta)  # [T, rope], one for all heads
    if control == "fp8cache":
        c, r = (
            jax.lax.reduce_precision(part, exponent_bits=4, mantissa_bits=3)
            for part in (c, r)
        )

    def heads(ws):
        """The output of a group of ``g`` heads, through its rows of W_o.
        No absorption: its keys and values raised for every position."""
        w_q, w_kvb, w_o = ws  # [in, g * (nope + rope)], [rank, g * (nope + dv)], [g * dv, H]
        q = (q_in @ _w(w_q, control)).reshape(T, g, nope + rope)
        q = jnp.concatenate([q[..., :nope], _rope_pairs(q[..., nope:], pos, theta)], axis=-1)
        kv = (c @ _w(w_kvb, control)).reshape(T, g, nope + dv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(r[:, None, :], (T, g, rope))], axis=-1
        )
        v = kv[..., nope:]
        outs = []
        for lo in range(0, T, _Q_CHUNK):
            hi = min(T, lo + _Q_CHUNK)
            s = jnp.einsum("tnd,snd->nts", q[lo:hi], k[:hi]) / jnp.sqrt(F32(nope + rope))
            s = jnp.where((pos[lo:hi, None] >= pos[None, :hi])[None], s, -jnp.inf)
            outs.append(jnp.einsum("nts,snd->tnd", jax.nn.softmax(s, axis=-1), v[:hi]))
        # W_o's int8 scales (one per output channel) are taken over the
        # whole matrix, before it is cut by head.
        return jnp.concatenate(outs, axis=0).reshape(T, g * dv) @ w_o

    # A group of heads at a time (``lax.map``), so that 128 heads x 3,080
    # positions of expanded float32 keys, values and scores fit.
    g = _HEAD_GROUP if n % _HEAD_GROUP == 0 else n
    G = n // g

    def by_group(w, per_head):  # columns are head-major: [in, n * per_head]
        return jnp.moveaxis(w.reshape(w.shape[0], G, g * per_head), 1, 0)

    parts = jax.lax.map(
        heads,
        (
            by_group(w_q, nope + rope), by_group(lw["mla_kvb_proj"], nope + dv),
            _w(lw["o_proj"], control).reshape(G, g * dv, -1),
        ),
    )
    return parts.sum(axis=0)


def _swiglu(x, gate, up, down, control):
    return (jax.nn.silu(x @ _w(gate, control)) * (x @ _w(up, control))) @ _w(down, control)


def _route(x, lw, z, route_cfg, control, forced=None):
    """Weights ``[T, held]`` of the held experts for every token, zero
    where an expert was not chosen, and the choice itself ``[T, E]`` (1
    where chosen), which ``forced`` replaces where it is given."""
    k, scaling, norm, first = route_cfg
    T, E = x.shape[0], z["E"]
    s = jax.nn.sigmoid(x @ _w(lw["router"], control))
    chosen = jax.lax.top_k(s, k)[1]  # one group, no selection bias
    picked = jnp.zeros((T, E), F32).at[jnp.arange(T)[:, None], chosen].set(1.0)
    if forced is not None:
        picked = forced
    w = picked * s
    if norm and control != "topk_norm_off":
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    if control != "scale_off":
        w = w * scaling
    return w[:, first : first + z["held"]], picked


@partial(jax.jit, static_argnames=("control",))
def _expert_block(x, w, gate, up, down, *, control):
    """Sum over a block of experts of ``w[:, e] * E_e(x)``."""
    with jax.default_matmul_precision("highest"):
        h = jnp.einsum("th,ehi->eti", x, _w(gate, control))
        h = jax.nn.silu(h) * jnp.einsum("th,ehi->eti", x, _w(up, control))
        y = jnp.einsum("eti,eih->eth", h, _w(down, control))
        return jnp.einsum("eth,te->th", y, w)


@partial(jax.jit, static_argnames=("z", "eps", "theta", "control"))
def _attention_part(h, lw, *, z, eps, theta, control):
    """``x = h + N2(MLA(N1(h)))`` and ``N3(x)``."""
    with jax.default_matmul_precision("highest"):
        x = _act(_rms(h, lw["ln1"], eps), control)
        a = _mla(x, lw, dict(z), eps, theta, control)
        if "post_attn_norm" in lw and control != "post_attn_norm_off":
            a = _rms(a, lw["post_attn_norm"], eps)
        h = _act(h + _act(a, control), control)
        return h, _act(_rms(h, lw["ln2"], eps), control)


@partial(jax.jit, static_argnames=("eps", "control"))
def _add_mlp(h, m, norm, *, eps, control):
    """``h + N4(m)`` (``norm``: N4's weight, None without sandwich norms)."""
    if norm is not None and control != "post_mlp_norm_off":
        m = _rms(m, norm, eps)
    return _act(h + _act(m, control), control)


@partial(jax.jit, static_argnames=("z", "route_cfg", "control"))
def _shared_and_route(x, lw, forced=None, *, z, route_cfg, control):
    with jax.default_matmul_precision("highest"):
        shared = _swiglu(
            x, lw["shared_gate_proj"], lw["shared_up_proj"], lw["shared_down_proj"], control
        )
        return shared, *_route(x, lw, dict(z), route_cfg, control, forced)


@partial(jax.jit, static_argnames=("control",))
def _dense_block(x, gate, up, down, *, control):
    with jax.default_matmul_precision("highest"):
        return _swiglu(x, gate, up, down, control)


def _dense_mlp(x, stack, i, control):
    """Layer ``i`` of the stack, a block of the intermediate columns at a
    time, each cut out of the stack as it is used: the sum over blocks is
    the whole SwiGLU (int8 scales are per output channel of each matrix:
    the up and gate matrices' are a column's own, the down matrix's are
    taken over a block's rows)."""
    I = stack["gate_proj"].shape[-1]
    out = 0.0
    for lo in range(0, I, _I_BLOCK):
        cols = slice(lo, lo + _I_BLOCK)
        out = out + _dense_block(
            x, stack["gate_proj"][i, :, cols], stack["up_proj"][i, :, cols],
            stack["down_proj"][i, cols], control=control,
        )
    return out


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
        int(cfg["num_experts_per_tok"]), float(cfg["routed_scaling_factor"]),
        bool(cfg["norm_topk_prob"]), first,
    )
    h = _embed(params["embed"], jnp.asarray(list(tokens), jnp.int32), control=control)
    picks_in = iter(picks_in) if picks_in is not None else None
    groups = _groups(cfg)
    routed = [(name, j) for name, mlp, count in groups if mlp == "moe" for j in range(count)]
    dropped = {"moe_drop": routed[-1:], "moe_drop_first": routed[:1]}.get(control, [])
    big = ("expert_", "gate_proj", "up_proj", "down_proj")  # cut out a block at a time
    for name, mlp, count in groups:
        stack = params[name]
        for i in range(count):
            # A layer's attention, norms, router and shared expert, cut out
            # of the stack (a copy: 0.5 GB at the published widths); one
            # layer's at a time, so wait for the layer before.
            jax.block_until_ready(h)
            lw = {leaf: w[i] for leaf, w in stack.items() if not leaf.startswith(big)}
            h, x = _attention_part(
                h, lw, z=zt, eps=eps, theta=float(cfg["rope_theta"]), control=control,
            )
            if mlp == "dense":
                h = _add_mlp(
                    h, _dense_mlp(x, stack, i, control), lw.get("post_mlp_norm"),
                    eps=eps, control=control,
                )
                continue
            out, w, picked = _shared_and_route(
                x, lw, next(picks_in) if picks_in is not None else None,
                z=zt, route_cfg=route_cfg, control=control,
            )
            if picks_out is not None:
                picks_out.append(picked)
            if (name, i) in dropped:
                w = jnp.zeros_like(w)
            for lo in range(0, z["held"], _E_BLOCK):
                block = slice(lo, lo + _E_BLOCK)
                out = out + _expert_block(
                    x, w[:, block], stack["expert_gate_proj"][i, block],
                    stack["expert_up_proj"][i, block], stack["expert_down_proj"][i, block],
                    control=control,
                )
            h = _add_mlp(h, out, lw.get("post_mlp_norm"), eps=eps, control=control)
    rows = h[jnp.asarray(list(positions), jnp.int32)]
    return _head(rows, params["final_norm"], params["lm_head"], eps=eps, control=control)
