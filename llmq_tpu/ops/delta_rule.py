"""The gated delta rule of KDA layers (Kimi Linear, arXiv:2510.26692) and
their short causal convolution, in plain XLA.

A head keeps a float32 state ``S [d_k, d_v]`` a sequence. One token:

    S' = Diag(alpha) S
    S_new = S' + beta k (v - S'^T k)^T
    o = S_new^T q

``alpha = exp(g)`` per channel of ``d_k`` with ``g <= 0``, ``beta`` a scalar
a head. Everything here is float32 element-wise work and reductions (no
matmul precision enters): the state is read twice and written once a
token.

Decode runs :func:`kda_step` once a row; prefill runs it under a
``lax.scan`` over the padded bucket (:func:`kda_scan`), positions past a
row's length as no-ops (``alpha = 1``, ``beta = 0``). The chunk-wise form
of the paper (chunks of 64, WY representation) is not built yet.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

F32 = jnp.float32


def kda_step(
    S: jnp.ndarray,  # [..., d_k, d_v] float32
    q: jnp.ndarray,  # [..., d_k]
    k: jnp.ndarray,  # [..., d_k]
    v: jnp.ndarray,  # [..., d_v]
    alpha: jnp.ndarray,  # [..., d_k] decay in (0, 1]
    beta: jnp.ndarray,  # [...] write strength in [0, 1]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the recurrence: (new state, output ``[..., d_v]``).

    ``o = S_new^T q = S'^T q + (k . q) beta (v - S'^T k)``: both
    reductions read the decayed state in one pass, and the new state is
    one more element-wise pass."""
    decayed = alpha[..., None] * S
    s_k = jnp.sum(decayed * k[..., None], axis=-2)
    s_q = jnp.sum(decayed * q[..., None], axis=-2)
    u = beta[..., None] * (v - s_k)
    S_new = decayed + k[..., None] * u[..., None, :]
    o = s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return S_new, o


def kda_scan(
    q: jnp.ndarray,  # [B, T, n, d_k] float32
    k: jnp.ndarray,
    v: jnp.ndarray,  # [B, T, n, d_v]
    alpha: jnp.ndarray,  # [B, T, n, d_k]
    beta: jnp.ndarray,  # [B, T, n]
    valid: jnp.ndarray,  # [B, T] bool: position < the row's length
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence over a padded bucket from a zero state: (final
    state ``[B, n, d_k, d_v]``, outputs ``[B, T, n, d_v]``). Padded
    positions leave the state as it is, so the final state is the one at
    the row's true end."""
    alpha = jnp.where(valid[:, :, None, None], alpha, 1.0)
    beta = jnp.where(valid[:, :, None], beta, 0.0)
    B, _, n, d_k = q.shape
    S0 = jnp.zeros((B, n, d_k, v.shape[-1]), F32)

    def step(S, xs):
        return kda_step(S, *xs)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta))
    S, o = jax.lax.scan(step, S0, xs)
    return S, jnp.moveaxis(o, 0, 1)


def causal_conv(
    u: jnp.ndarray,  # [B, T, C] inputs, positions 0..T-1
    w: jnp.ndarray,  # [K, C] depth-wise taps, the last on the current input
    lengths: jnp.ndarray,  # [B]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``c_t = sum_j w[j] * u_{t-K+1+j}`` with zeros before the sequence:
    (float32 outputs ``[B, T, C]``, the last ``K - 1`` inputs of each row
    at its true length ``[B, K-1, C]``, zeros where the row is shorter)."""
    K = w.shape[0]
    T = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
    w32 = w.astype(F32)
    out = sum(w32[j] * padded[:, j : j + T].astype(F32) for j in range(K))
    at = lengths[:, None] + jnp.arange(K - 1)[None, :]  # padded index
    tail = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    return out, tail


def conv_step(
    tail: jnp.ndarray,  # [S, K-1, C] the last inputs before this one
    u: jnp.ndarray,  # [S, C] this token's input
    w: jnp.ndarray,  # [K, C]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the convolution: (float32 output ``[S, C]``, new tail)."""
    window = jnp.concatenate([tail, u[:, None, :].astype(tail.dtype)], axis=1)
    out = jnp.sum(window.astype(F32) * w.astype(F32)[None], axis=1)
    return out, window[:, 1:]
