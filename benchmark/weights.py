"""Weights of a run, made by the benchmark on the device from ``--seed``.

One jitted call makes the whole tree in the type it is served in (bf16),
already placed on the shards the program asked for. The tree is whatever
the configuration's architecture says (``architectures/<name>.py``:
``tree_shapes`` for the leaves, ``init_rule`` for how each is made):
top-level leaves, and groups of leaves stacked on a leading layer axis.
Each stacked group is made one layer at a time inside that call
(``lax.map``), so the float32 transient is one layer's, not the model's.
The tree is checked against the program's own before it replaces it.

The rules (``RULES``), n standard normal:

- ``norm``: 1 + 0.1 n, and ``bias`` (of any kind, a router's too): 0.1 n.
  With the program's own ones and zeros a reference could drop the bias or
  the norm weight and still agree;
- ``matrix``: n / sqrt(fan-in), the fan-in the axis before the last (of a
  stack of experts too);
- ``vocab_rows``, ``vocab_columns``: a table with the vocabulary on its
  first or its last axis, n / sqrt(hidden), made in eighths of the
  vocabulary so that the float32 transient is an eighth of a table that is
  gigabytes wide.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

RULES = ("norm", "bias", "matrix", "vocab_rows", "vocab_columns")


def _leaf(key, rule: str, shape, dtype):
    if rule == "norm":
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if rule == "bias":
        return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if rule not in RULES:
        raise ValueError(f"no rule {rule!r} to make a leaf by: {RULES}")
    fan_in = shape[-1] if rule == "vocab_rows" else shape[-2]

    def normal(k, shp):
        return (jax.random.normal(k, shp, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    if rule == "matrix":
        return normal(key, shape)
    axis = 0 if rule == "vocab_rows" else 1
    V = shape[axis]
    n = 8 if V % 8 == 0 else 1
    part = (V // n, shape[1]) if axis == 0 else (shape[0], V // n)
    parts = jax.lax.map(lambda k: normal(k, part), jax.random.split(key, n))
    if axis == 1:
        parts = jnp.moveaxis(parts, 0, 1)
    return parts.reshape(shape)


def _stacked(key, group: str, shapes: Dict[str, tuple], init_rule, dtype):
    """One group of leaves stacked on a leading layer axis, a layer at a
    time."""
    names = sorted(shapes)
    depths = {shape[0] for shape in shapes.values()}
    if len(depths) != 1:
        raise ValueError(f"the leaves of group {group!r} are not stacked on one layer axis: {shapes}")

    def one_layer(k):
        return {
            n: _leaf(kk, init_rule(n), shapes[n][1:], dtype)
            for n, kk in zip(names, jax.random.split(k, len(names)))
        }

    return jax.lax.map(one_layer, jax.random.split(key, depths.pop()))


def make_weights(arch, cfg: Dict[str, Any], seed: int, shardings, dtype=jnp.bfloat16):
    """The tree of ``arch`` for ``seed``, placed by ``shardings`` (a
    matching tree)."""
    shapes = arch.tree_shapes(cfg)
    top_names = sorted(n for n, s in shapes.items() if not isinstance(s, dict))
    groups = sorted(n for n, s in shapes.items() if isinstance(s, dict))

    def build(key):
        k_top, k_groups = jax.random.split(key)
        top_keys = jax.random.split(k_top, len(top_names))
        out = {
            n: _leaf(k, arch.init_rule(n), shapes[n], dtype)
            for n, k in zip(top_names, top_keys)
        }
        for i, g in enumerate(groups):
            # The first group takes the key that the one group of a tree
            # always had, so a tree of one group is what it was.
            k_group = k_groups if i == 0 else jax.random.fold_in(k_groups, i)
            out[g] = _stacked(k_group, g, shapes[g], arch.init_rule, dtype)
        return out

    # Seeds run to a little over 2**31: fold the halves into the key.
    key = jax.random.fold_in(jax.random.key(int(seed) & 0x7FFFFFFF), int(seed) >> 31)
    return jax.jit(build, out_shardings=shardings)(key)


def check_same_layout(ours, theirs) -> None:
    """Refuse to serve a tree whose layout the program would not read."""
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), ours)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), theirs)
    if a != b:
        raise RuntimeError(
            "the benchmark's weight tree does not have the layout of the "
            f"program's: ours {a} program's {b}"
        )
