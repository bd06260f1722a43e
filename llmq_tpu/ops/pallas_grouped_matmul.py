"""A grouped matmul that reads a layer's experts where they lie in their
stack: a Pallas TPU kernel for the expert products of a prefill
(``models/hybrid._experts_grouped`` has the XLA form, ``lax.ragged_dot``,
which stays the reference and the CPU path).

``lhs [M, K]`` are assignments sorted by expert, ``group_sizes [G]`` how
many rows each of the ``G`` held experts takes (rows past their sum belong
to experts held elsewhere), and the right-hand operand is the WHOLE stack
``[layers, G, K, N]`` of a group of equal layers with the layer's index
beside it: row ``r`` of group ``g`` gives ``lhs[r] @ stack[layer, g]``.
``lax.ragged_dot`` takes its matrices as one buffer, so inside a layer
scan each layer's three were first copied out of their stack (3 x 503 MB a
layer at ling's widths, 8 % of that cell's device time: PERF.md section 6,
PR 51); here layer and expert are block indices, handed in through scalar
prefetch, and nothing of an expert matrix's size exists beside the stack.

A grid step is one VISIT: a tile of ``tm`` rows and one expert that has
rows in it (the tile-to-group table of
``jax.experimental.pallas.ops.tpu.megablox``, without its sharding). The
expert's ``[K, tn]`` block, the whole contraction at once, is multiplied
by the tile and the rows that are the expert's are stored; a tile that
several experts share is visited once by each, in turn, and keeps what the
earlier visits stored. The grid is as long as there can be visits (tiles
+ experts - 1) and how many there are is known on the device only: a
step past the last visit computes nothing and names the last visit's
blocks again, so it fetches nothing either. An expert with no rows is
never fetched, tiles past ``sum(group_sizes)`` are never computed (their
rows of the output are left as they were allocated: the caller masks
them), and successive tiles of one expert find its block already in VMEM.
A step's blocks stay inside the VMEM a kernel has without asking for more
(``RHS_BLOCK_VALUES``): asked for 18 MB, the same kernel hung ling's 4 x
512 and 4 x 1,024 prefill programs on the chip and ran in every other
(PERF.md section 6, PR 51). bf16 or float32 operands,
float32 accumulation, the result in ``lhs``'s dtype as ``ragged_dot``
gives it. The result depends on the operands' values and shapes alone: the
tiles are :func:`tiles`' and read nothing else.

:func:`ops.dispatch.grouped_experts_plan` says where this runs.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Values of an expert's ``[K, tn]`` block: 4 MB in bf16, buffered twice,
#: so that with a tile of rows and its product beside it a step stays
#: inside the 16 MB of VMEM a kernel has without asking for more. ling's
#: 2,560 x 768 matrix is one block (the tile of rows is then read once),
#: lfm2's 2,048 x 1,536 two, openpangu's 7,680 x 2,048 eight.
RHS_BLOCK_VALUES = 2 * 2**20

#: Rows of a tile. Measured on a v5e at the three expert cells' shapes
#: (``tools/expert_matmul_bench.py``; PERF.md section 6, PR 51): 128 and
#: 256 give the same time at every shape and number of rows (an expert's
#: successive tiles reuse its block, so small tiles cost no weight
#: traffic), 512 is slower everywhere by what a tile shared by several
#: experts is multiplied whole for each.
TILE_ROWS = 128


def tiles(k: int, n: int) -> Tuple[int, int]:
    """(rows of a tile, columns of an expert's block) for experts of ``[k,
    n]``: a pure function of the shapes. Columns: the most whole lane
    tiles that divide ``n`` and keep the block within
    ``RHS_BLOCK_VALUES``."""
    lanes = n // 128
    per_block = max(1, RHS_BLOCK_VALUES // (k * 128))
    tn = 128 * max(d for d in range(1, lanes + 1) if lanes % d == 0 and d <= per_block)
    return TILE_ROWS, tn


def tile_visits(group_sizes: jnp.ndarray, m: int, tm: int):
    """The grid's table for ``m`` rows in tiles of ``tm``: (``offsets [G +
    1]``: the row at which each group starts, ``group_of [V]`` and
    ``tile_of [V]``: the expert and the tile of rows of each visit, the
    number of visits), ``V = tiles + G - 1`` the most there can be. Visits
    run by expert, an expert's by tile, so a tile's visits are successive
    and so are an expert's; an empty expert has none. Past the number of
    visits the table repeats the last visit."""
    G = group_sizes.shape[0]
    n_tiles = pl.cdiv(m, tm)
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    visits_of = jnp.where(group_sizes > 0, (ends + tm - 1) // tm - first_tile, 0)
    visit_ends = jnp.cumsum(visits_of)
    V = n_tiles + G - 1
    group_of = jnp.repeat(
        jnp.arange(G, dtype=jnp.int32), visits_of, total_repeat_length=V
    )
    nth = jnp.arange(V, dtype=jnp.int32) - (visit_ends - visits_of)[group_of]
    tile_of = jnp.clip(first_tile[group_of] + nth, 0, n_tiles - 1)
    # so that a step there fetches nothing and leaves the output's tile
    # where it is
    visits = visit_ends[-1].astype(jnp.int32)
    last = jnp.minimum(jnp.arange(V, dtype=jnp.int32), jnp.maximum(visits - 1, 0))
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (
        offsets.astype(jnp.int32), group_of[last], tile_of[last].astype(jnp.int32),
        visits,
    )


def _grouped_matmul_kernel(
    # scalar prefetch
    layer_ref,  # [1] int32: the layer of the stack
    visits_ref,  # [1] int32: the steps that are visits
    offsets_ref,  # [G + 1] int32
    group_of_ref,  # [V] int32
    tile_of_ref,  # [V] int32
    # blocked inputs
    lhs_ref,  # [tm, K]
    rhs_ref,  # [K, tn]: stack[layer, group_of[v], :, n-th block]
    # output
    out_ref,  # [tm, tn]
    *,
    tm: int,
):
    v = pl.program_id(1)

    @pl.when(v < visits_ref[0])
    def _visit():
        g = group_of_ref[v]
        first = tile_of_ref[v] * tm
        start, end = offsets_ref[g], offsets_ref[g + 1]
        product = jnp.dot(
            lhs_ref[...], rhs_ref[...], preferred_element_type=F32
        ).astype(out_ref.dtype)
        whole = jnp.logical_and(start <= first, first + tm <= end)

        @pl.when(whole)
        def _all_rows():
            out_ref[...] = product

        @pl.when(jnp.logical_not(whole))
        def _own_rows():
            row = first + jax.lax.broadcasted_iota(jnp.int32, product.shape, 0)
            mine = jnp.logical_and(row >= start, row < end)
            out_ref[...] = jnp.where(mine, product, out_ref[...])


@functools.partial(jax.jit, static_argnames=("tile_rows", "interpret"))
def grouped_matmul_stacked(
    lhs: jnp.ndarray,  # [M, K], rows sorted by group
    stack: jnp.ndarray,  # [layers, G, K, N]
    layer: jnp.ndarray,  # [] int32 (traced: layers run under a scan)
    group_sizes: jnp.ndarray,  # [G] int32
    *,
    tile_rows: int = 0,  # 0: :func:`tiles`' (a bench sweeps it)
    interpret: bool = False,
) -> jnp.ndarray:
    """``[M, N]`` in ``lhs``'s dtype: row ``r`` of group ``g`` is ``lhs[r]
    @ stack[layer, g]``; rows past ``sum(group_sizes)`` hold nothing
    meant."""
    M, K = lhs.shape
    _, G, _, N = stack.shape
    tm, tn = tiles(K, N)
    tm = tile_rows or tm
    offsets, group_of, tile_of, visits = tile_visits(group_sizes, M, tm)

    def lhs_index(n, v, layer, visits, offsets, group_of, tile_of):
        return tile_of[v], 0

    def rhs_index(n, v, layer, visits, offsets, group_of, tile_of):
        return layer[0], group_of[v], 0, n

    def out_index(n, v, layer, visits, offsets, group_of, tile_of):
        return tile_of[v], n

    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_grouped_matmul_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            # Columns outermost: within one block of columns an expert's
            # successive tiles find its block where the last visit left it.
            grid=(N // tn, group_of.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, K), lhs_index),
                pl.BlockSpec((None, None, K, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), out_index),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * M * K * N,
            bytes_accessed=(M * K * (N // tn) + G * K * N + M * N) * itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
        name="grouped_matmul_stacked",
    )(
        jnp.asarray(layer, jnp.int32).reshape(1), visits.reshape(1),
        offsets, group_of, tile_of, lhs, stack,
    )
