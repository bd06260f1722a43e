"""What a sequence keeps on the device, declared once: the pools a model's
step programs carry, what they cost, which cache rows a position lands
on, what a decode step's span says of them, which decode schedule the
pool gets and what the cache cannot do.

The engine builds one :class:`CacheLayout` (:func:`cache_layout`) and asks
it; the scheduler is handed it for the row map. Two implementations,
chosen once: :class:`UniformLayout`, the K pool and the V pool of a
uniform stack (``models/transformer.py``), and :class:`PatternLayout`, a
declared layer pattern's paged pool and state pool (``models/hybrid.py``
reads the widths its steps need from here). Inside a pattern a kind of
layer is an entry of ``KINDS``: the state leaves it keeps a sequence, the
fields it adds to a decode span, why it refuses an option. The EVA row
arithmetic stays in ``ops/attention`` (``eva_row``, ``eva_context``,
``eva_table_pages``).

A pattern's pools, in the places the uniform model has its K and V pools,
so that the step programs pass, donate and return them alike:

- the paged pool ``[L_paged, P, page, width]``, one row a token,
  addressed through the block table (no V pool); see
  :func:`latent_pool_width` and :func:`paged_rank`;
- the state pool, a dict of leaves ``[L_kind, R, ...]``, one row a
  sequence (the engine: slot + 1; row 0 is scratch, as page 0 is). ``S``
  and ``conv`` ride every pattern's programs, with no layers where the
  pattern has no KDA or convolution layer (they cost no HBM); ``ring``
  exists only beside "swa" layers.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import NamedSharding, PartitionSpec as P

from llmq_tpu.models.config import ModelConfig
from llmq_tpu.ops.attention import (
    eva_context, eva_table_pages, latent_decode_pages_visited,
)
from llmq_tpu.parallel.mesh import TP_AXIS

F32 = jnp.float32

#: What a pattern's kinds keep a sequence: "paged" kinds a row a token in
#: the first cache place, "state" kinds a row a sequence in the second.
PAGED_KINDS = ("mla", "gqa", "eva")
STATE_KINDS = ("kda", "conv", "swa")


def count_layers(config: ModelConfig, *attn: str) -> int:
    return sum(1 for a, _ in config.layer_pattern if a in attn)


def latent_width(config: ModelConfig) -> int:
    return config.kv_lora_rank + config.qk_rope_head_dim


def kv_width(config: ModelConfig) -> int:
    """Values of a token's keys (or values) in a "gqa" layer: every kv
    head's, side by side."""
    return config.num_kv_heads * config.head_dim_


def paged_rank(config: ModelConfig) -> int:
    """The first values of a pool row that are the row's VALUE part, which
    decode attention sums: MLA's latent ``c``; a "gqa" layer's V, all kv
    heads of it (its K follows), and an "eva" layer's likewise."""
    if count_layers(config, "gqa", "eva"):
        return kv_width(config)
    return config.kv_lora_rank


def ring_pages(config: ModelConfig) -> Tuple[int, int]:
    """(rows a page, pages a sequence) of the "swa" layers' ring: pages of
    128 rows where the window is whole pages (what the latent kernel
    walks), else the window as one page."""
    W = config.swa_window
    page = 128 if W % 128 == 0 else W
    return page, W // page


def latent_pool_width(config: ModelConfig) -> int:
    """A pool row: the latent row (a "gqa" pattern's: a token's V then K,
    see ``HybridTransformer._gqa_decode``) in whole lane tiles of 128
    (576 -> 640, zeros beyond; 2 x 8 x 64 = 1,024 as it is). A row-major
    pool takes that room on the chip anyway, and for a width that is not
    whole tiles the TPU runtime's default layout puts the tokens minor
    instead: every step then copied the whole pool into row-major order
    and back (2.8 ms of a 28.7 ms decode step at 2,305 pages, my chip run,
    PR 33)."""
    if count_layers(config, "mla"):
        width = latent_width(config)
    else:  # with no paged layer at all the pool has no layers
        width = 2 * kv_width(config)
    return -(-width // 128) * 128


# ---------------------------------------------------------------------------
# A pattern's kinds
# ---------------------------------------------------------------------------

_Leaves = Dict[str, Tuple[tuple, Any]]  # leaf -> (shape of one layer's rows, dtype)


def _kda_leaves(config: ModelConfig, rows: int, dtype) -> _Leaves:
    """The state matrix, and the tails of the q|k|v convolution."""
    n, d = config.num_heads, config.head_dim_
    return {
        "S": ((rows, n, d, d), F32),
        "conv": ((rows, config.short_conv_kernel_size - 1, 3 * n * d), dtype),
    }


def _conv_leaves(config: ModelConfig, rows: int, dtype) -> _Leaves:
    """The tail of the gated input ``B * u`` alone: K - 1 rows."""
    return {
        "conv": ((rows, config.short_conv_kernel_size - 1, config.hidden_size), dtype)
    }


def _swa_leaves(config: ModelConfig, rows: int, dtype) -> _Leaves:
    """The last ``swa_window`` pool rows, whatever ``max_model_len`` is, as
    the ``per`` ring pages of the sequence's state row."""
    page, per = ring_pages(config)
    return {"ring": ((rows * per, page, latent_pool_width(config)), dtype)}


def _state_rows_span(layout: "PatternLayout", lengths: Sequence[int]) -> Dict[str, int]:
    return {"state_rows": len(lengths)}


def _swa_span(layout: "PatternLayout", lengths: Sequence[int]) -> Dict[str, int]:
    """Ring rows a sliding-window layer attends in a decode step: each
    sequence's tokens or the window."""
    window = layout.config.swa_window
    rows = sum(min(n, window) for n in lengths)
    return {"state_rows": len(lengths), "window_rows": rows}


def _eva_span(layout: "PatternLayout", lengths: Sequence[int]) -> Dict[str, int]:
    """Rows a decode step attends, by kind: the summaries of earlier
    windows and the exact rows of each sequence's own window."""
    own = sum((n - 1) % layout.config.eva_window + 1 for n in lengths)
    return {"summary_rows": sum(layout.contexts(lengths)) - own, "window_rows": own}


@dataclasses.dataclass(frozen=True)
class Kind:
    """One kind of layer of a pattern, as the cache sees it."""

    #: What a pattern with such layers is and why it refuses an option;
    #: None: the kind forces no refusal of its own.
    refusal: Optional[Tuple[str, str]] = None
    #: The state leaves a layer of it keeps ``rows`` sequences.
    leaves: Callable[[ModelConfig, int, Any], _Leaves] = lambda config, rows, dtype: {}
    #: What it adds to a decode step's span for rows of ``lengths`` tokens.
    span: Callable[["PatternLayout", Sequence[int]], Dict[str, int]] = (
        lambda layout, lengths: {}
    )


KINDS: Dict[str, Kind] = {
    "mla": Kind(),
    "gqa": Kind(),
    "eva": Kind(
        refusal=(
            "EVA layers over a compressed paged cache whose rows are not positions",
            "a closed window's rows are overwritten by its summaries, so the cache "
            "cannot be shared by a prefix, cut at a chunk, rewound by a length or "
            "moved between pools; a step yields one token (the extra prediction "
            "heads are not served)",
        ),
        span=_eva_span,
    ),
    "kda": Kind(
        refusal=(
            "per-sequence KDA state beside a latent cache",
            "the state cannot be shared by a prefix, cut at a chunk, rewound by a "
            "length or moved between pools",
        ),
        leaves=_kda_leaves,
        span=_state_rows_span,
    ),
    "conv": Kind(
        refusal=(
            "gated short-convolution layers beside a K/V paged cache",
            "a per-sequence convolution tail cannot be shared by a prefix, cut at "
            "a chunk or rewound, moving it between pools is not built",
        ),
        leaves=_conv_leaves,
        span=_state_rows_span,
    ),
    "swa": Kind(
        refusal=(
            "sliding-window layers over a per-sequence ring beside full-attention "
            "layers over a K/V paged cache",
            "a ring of the last window's rows cannot be shared by a prefix, cut at "
            "a chunk or rewound by a length without storing it, moving it between "
            "pools is not built",
        ),
        leaves=_swa_leaves,
        span=_swa_span,
    ),
}

#: The refusal of a pattern none of whose kinds has one: paged layers alone.
_UNBUILT = (
    "a latent cache alone, no per-sequence state",
    "chunked prefill, verify, the mixed step and moving a latent pool are "
    "not built for a layer pattern (HybridTransformer has whole-prompt "
    "prefill and decode)",
)


def _kind_of(config: ModelConfig, kinds: Tuple[str, ...]) -> Optional[str]:
    """The one kind among ``kinds`` the pattern has (``hybrid.layer_groups``
    refuses a second), or None."""
    return next((attn for attn, _ in config.layer_pattern if attn in kinds), None)


def state_leaves(config: ModelConfig, rows: int, dtype) -> _Leaves:
    """Shape and dtype of every leaf of a pattern's state pool for ``rows``
    sequences: its state kind's leaves a layer, and ``S`` and ``conv``
    without layers where no layer keeps them."""
    leaves = {
        name: ((0, *shape), dt)
        for name, (shape, dt) in _kda_leaves(config, rows, dtype).items()
    }
    kind = _kind_of(config, STATE_KINDS)
    if kind is not None:
        layers = count_layers(config, kind)
        for name, (shape, dt) in KINDS[kind].leaves(config, rows, dtype).items():
            leaves[name] = ((layers, *shape), dt)
    return leaves


def state_bytes(config: ModelConfig, rows: int, dtype) -> Dict[str, int]:
    """Bytes of each leaf of :func:`state_leaves`."""
    return {
        name: math.prod(shape) * jnp.dtype(dt).itemsize
        for name, (shape, dt) in state_leaves(config, rows, dtype).items()
    }


# ---------------------------------------------------------------------------
# Allocation
# ---------------------------------------------------------------------------


def _pool_shape(
    config: ModelConfig, num_pages: int, page_size: int, num_layers: Optional[int] = None
) -> tuple:
    """A K (or V) pool ``[L, P, page, n_kv, d]``; a pattern's paged pool
    ``[L_paged, P, page, width]``."""
    if config.layer_pattern is not None:
        return (
            count_layers(config, *PAGED_KINDS), num_pages, page_size,
            latent_pool_width(config),
        )
    return (
        config.num_layers if num_layers is None else num_layers,
        num_pages, page_size, config.num_kv_heads, config.head_dim_,
    )


def make_kv_pages(
    config: ModelConfig,
    num_pages: int,
    page_size: int,
    dtype=jnp.bfloat16,
    *,
    num_layers: Optional[int] = None,
    placement: Any = None,
    state_rows: Optional[int] = None,
) -> Tuple[Any, Any]:
    """Allocate the paged KV cache: [L, P, page, n_kv, d] ×2.

    A model with a layer pattern gets, in the same two places, its paged
    pool and its per-sequence state pool of ``state_rows`` rows (none
    given: one row a page, so that a sequence's first page can name its
    row).

    ``num_layers`` overrides the leading depth for per-stage pools under
    pipeline parallelism (each stage caches only its own layers).
    ``placement`` (a sharding or layout ``Format``) creates the pools
    already placed: a tp-sharded pool is sized per device and, whole,
    would not fit the one device an unplaced ``zeros`` lands on."""
    shape = _pool_shape(config, num_pages, page_size, num_layers)
    if config.layer_pattern is not None:
        leaves = state_leaves(
            config, num_pages if state_rows is None else state_rows, dtype
        )

        def alloc():
            return jnp.zeros(shape, dtype), {
                name: jnp.zeros(leaf, dt) for name, (leaf, dt) in leaves.items()
            }

        if placement is None:
            return alloc()
        return jax.jit(alloc, out_shardings=placement)()
    if placement is None:
        return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)
    alloc = jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=placement)
    return alloc(), alloc()


# ---------------------------------------------------------------------------
# The layout
# ---------------------------------------------------------------------------


class CacheLayout:
    """What both layouts share; :func:`cache_layout` builds the one a
    configuration needs. Plain arithmetic on ints, built once."""

    #: Pools a page spans: K and V; a pattern's paged pool alone.
    pools_a_page = 2
    #: Rows of the state pool: a row a slot, and row 0 scratch. None: no
    #: state pool.
    state_rows: Optional[int] = None
    #: The kind of the pattern's state layers ("kda", "conv", "swa"), or None.
    state_kind: Optional[str] = None
    #: Positions a window of the cache spans, where the decode step that
    #: writes a window's last position compacts it (EVA); None: no step does.
    closing_window: Optional[int] = None
    #: ``ops/dispatch.kda_decode_plan``'s shape arguments (the step hands
    #: the model the int ``1``: a slot's row is its index + 1); None for a
    #: model with no KDA layer.
    kda_plan_args: Optional[tuple] = None
    #: Bytes that come off the budget before pages (a state pool's).
    fixed_bytes = 0

    def __init__(
        self, config: ModelConfig, *, page_size: int, max_model_len: int,
        max_num_seqs: int, kv_dtype=jnp.bfloat16,
    ) -> None:
        self.config = config
        self.page_size = page_size
        self.max_model_len = max_model_len
        self.max_num_seqs = max_num_seqs
        self.kv_dtype = kv_dtype

    # --- the row map -------------------------------------------------------
    def table_pages(self, start: int, stop: int) -> int:
        """Places of a block table that hold the cache rows of positions
        ``[start, stop)``: a row a position."""
        return -(-stop // self.page_size)

    def contexts(self, lengths: Sequence[int]) -> List[int]:
        """Cache rows a sequence of each of ``lengths`` tokens attends in a
        decode step (the new token's included): its tokens."""
        return list(lengths)

    # --- what to allocate ---------------------------------------------------
    def allocate(self, num_pages: int, placement: Any, *, num_layers: Optional[int] = None):
        """The two pools for ``num_pages`` under ``placement`` (one of
        :meth:`placements`), or a pipeline stage's of ``num_layers``."""
        return make_kv_pages(
            self.config, num_pages, self.page_size, dtype=self.kv_dtype,
            num_layers=num_layers, placement=placement, state_rows=self.state_rows,
        )

    # --- what it costs --------------------------------------------------------
    def page_bytes(self, placement: Any) -> int:
        """HBM one page (every layer's, K and V) takes on each device as
        the compiler lays the pool out: asked of the compiler, not
        computed from the shape. A K/V pool's kv-head axis sits on the
        sublanes, and a shard left with fewer heads than one packed tile
        holds (one bf16 head at tp == num_kv_heads, two fp8 heads) is
        padded to it, i.e. twice the bytes the shape says
        (tests/test_tpu_compile.py); a latent row of 576 values is padded
        to whole lane tiles."""
        probe = 8
        shape = _pool_shape(self.config, probe, self.page_size)
        alloc = jax.jit(
            lambda: jnp.zeros(shape, self.kv_dtype), out_shardings=placement
        )
        pool = alloc.lower().compile().memory_analysis().output_size_in_bytes
        return self.pools_a_page * pool // probe

    @property
    def max_useful_pages(self) -> int:
        """The most pages that are of use: every slot's longest table and
        a page of headroom each, and the scratch page."""
        per_seq = self.table_pages(0, self.max_model_len)
        return self.max_num_seqs * (per_seq + 1) + 1

    # --- what stats() and a snapshot say of it ---------------------------------
    def stats(self) -> Dict[str, int]:
        return {}

    def snapshot_sig(self) -> Dict[str, Any]:
        """The shape contract a snapshot's KV pages must match. Weights are
        deliberately NOT part of the signature — the handoff plane assumes
        peers serve the same checkpoint (same queue, same model), which is
        also what the prefix cache and greedy bit-exactness already rely
        on."""
        return {
            "num_layers": int(self.config.num_layers),
            "num_kv_heads": int(self.config.num_kv_heads),
            "head_dim": int(self.config.head_dim_),
            "kv_dtype": str(jnp.dtype(self.kv_dtype)),
        }


class UniformLayout(CacheLayout):
    """A uniform stack's K pool and V pool, ``[L, P, page, n_kv, d]`` each."""

    def placements(self, stage_meshes, *, pin: bool) -> List[Any]:
        """A placement a pipeline stage: the kv-head axis over tp, and
        ``pin``ned to row-major layout at every jit boundary. Left to
        itself XLA picks a different parameter layout than the Pallas
        custom call's required default, then inserts FOUR full-pool
        transpose copies per step in the entry computation (~12 ms/step
        at 3B — measured round 2; dwarfs the attention kernel itself). A
        model that takes the XLA attention path on a TPU runs no custom
        call, and the pin would only force the compiler's own compact
        layout through a padded copy: the caller leaves it unpinned."""
        # parallel/sharding.py imports models.config: not while this loads
        from llmq_tpu.parallel.sharding import kv_page_pspec

        shardings = [
            NamedSharding(m, kv_page_pspec(self.config, m.shape[TP_AXIS]))
            for m in stage_meshes
        ]
        if not pin:
            return shardings
        return [Format(Layout(tuple(range(5))), sh) for sh in shardings]

    @property
    def decode_plan(self) -> Tuple[str, tuple]:
        """The function of ``ops/dispatch`` that names this pool's decode
        schedule, and its shape arguments."""
        mc = self.config
        return "decode_kernel_plan", (mc.num_heads, mc.num_kv_heads, self.kv_dtype)

    def live_pages(self, lengths: Sequence[int]) -> int:
        """KV pages the decode kernel visits a layer: the page places that
        overlap each sequence's attended span (all of its context, or its
        window where every layer of the model slides)."""
        page, mc = self.page_size, self.config
        window = mc.sliding_window if mc.sliding_window_pattern <= 1 else None
        return sum(
            -(-n // page) - (max(n - window, 0) // page if window else 0)
            for n in lengths
        )

    def decode_span(self, lengths: Sequence[int], plan: Callable[[], str]) -> Dict[str, int]:
        """What a decode step's span says of the cache, for running
        sequences of ``lengths`` tokens."""
        return {"live_pages": self.live_pages(lengths)}


class PatternLayout(CacheLayout):
    """A layer pattern's paged pool and per-sequence state pool."""

    pools_a_page = 1

    def __init__(self, config: ModelConfig, **sizes) -> None:
        super().__init__(config, **sizes)
        self.state_rows = self.max_num_seqs + 1
        self._state_bytes = state_bytes(config, self.state_rows, self.kv_dtype)
        self.fixed_bytes = sum(self._state_bytes.values())
        self.state_kind = _kind_of(config, STATE_KINDS)
        self._kinds = [
            KINDS[k]
            for k in (self.state_kind, _kind_of(config, PAGED_KINDS))
            if k is not None
        ]
        self._eva: Optional[Tuple[int, int]] = None
        if count_layers(config, "eva"):
            self._eva = (config.eva_window, config.eva_chunk)
            self.closing_window = config.eva_window
        if count_layers(config, "kda"):
            self.kda_plan_args = (1, F32, config.head_dim_, config.head_dim_)
        # The paged pool's schedule (latent rows, or a token's V and K side
        # by side; state layers have no attention kernel).
        self.decode_plan = "latent_decode_kernel_plan", (
            paged_rank(config), self.page_size, latent_pool_width(config),
            jnp.dtype(self.kv_dtype),
        )

    # --- the row map (EVA: a row is not a position) ----------------------------
    def table_pages(self, start: int, stop: int) -> int:
        if self._eva is None:
            return super().table_pages(start, stop)
        return eva_table_pages(start, stop, *self._eva, self.page_size)

    def contexts(self, lengths: Sequence[int]) -> List[int]:
        """Its tokens, or what the row map makes of them (EVA layers:
        earlier windows' summaries + its own window)."""
        if self._eva is None:
            return list(lengths)
        return [eva_context(n, *self._eva) for n in lengths]

    # --- what to allocate ---------------------------------------------------
    def placements(self, stage_meshes, *, pin: bool) -> List[Any]:
        """Paged pool and state pool differ in rank: one placement that
        fits both (tp = 1: whole on the device), and no layout pin: the
        paged pool's rows are whole lane tiles (:func:`latent_pool_width`),
        so the runtime's default layout is the row-major one the step
        computes in. (A pin at the jit boundary worked until a program
        came back from the compile cache and handed the pool on in the
        default layout: my chip run, PR 33, PERF.md section 6.)"""
        return [NamedSharding(stage_meshes[-1], P())]

    # --- what a decode step's span says of it ----------------------------------------
    def live_pages(self, lengths: Sequence[int]) -> int:
        """Pages of the paged pool that hold each sequence's attended rows."""
        page = self.page_size
        return sum(-(-n // page) for n in self.contexts(lengths))

    def decode_span(self, lengths: Sequence[int], plan: Callable[[], str]) -> Dict[str, int]:
        """``live_pages``, each of the pattern's kinds' own fields, and the
        pages a step reads out of the paged pool a layer (beside what is
        live) by the schedule ``plan()`` names."""
        fields = {"live_pages": self.live_pages(lengths)}
        for kind in self._kinds:
            fields.update(kind.span(self, lengths))
        fields["latent_pages_visited"] = latent_decode_pages_visited(
            plan(), self.contexts(lengths), self.max_num_seqs,
            -(-self.max_model_len // self.page_size), self.page_size,
        )
        return fields

    # --- what it cannot do, and why --------------------------------------------------
    def refusal(self, what: str) -> str:
        """Why an option is refused for this layer pattern: the reason of
        its state kind, else of its paged kind, else that the paths are
        not built."""
        pattern, reason = next(
            (k.refusal for k in self._kinds if k.refusal is not None), _UNBUILT
        )
        return (
            f"{what} is not supported for a model with a layer pattern "
            f"({pattern}): {reason}, and its experts are held whole on one device"
        )

    # --- what stats() says of it ------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Beside a ring: what a page costs, the layers it spans (the
        full-attention ones alone), and the window layers' rings."""
        ring = self._state_bytes.get("ring")
        if ring is None:
            return {}
        return {
            "kv_pool_layers": count_layers(self.config, *PAGED_KINDS),
            "swa_ring_bytes": ring,
        }


def cache_layout(config: ModelConfig, **sizes) -> CacheLayout:
    """The layout of ``config``'s cache (``page_size``, ``max_model_len``,
    ``max_num_seqs``, ``kv_dtype``)."""
    layout = UniformLayout if config.layer_pattern is None else PatternLayout
    return layout(config, **sizes)
