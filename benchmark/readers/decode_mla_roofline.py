"""The latent decode attention's share of its roofline (%): the least
time the chip could take for the bytes and operations of what runs under
``llmq.attn.mla_decode`` (``kernel_cost_mla.py``: the live latent rows
once a latent layer, the scope's matrices once), the larger of the HBM
and the MXU bound, over the device time under that scope per step. The
latent layers are counted from the configuration's layer pattern
(``kernel_cost_mla.latent_layers``), and ``row_values`` is what the pool
stores a token where that is not ``kv_lora_rank + qk_rope_head_dim``. The
live cache is read from the engine in the middle of the trace
(``decode_attn_roofline.py`` reads it so). Nothing to read where the
program has no such scope."""

from .. import kernel_cost, kernel_cost_mla, span_join


def read(ctx, *, program, scope, row_values=None):
    j = span_join.load(ctx)
    if j is None or ctx.peaks is None or not ctx.live_kv:
        return None
    ms = span_join.scope_ms_per_run(j, program, scope)
    if not ms:
        return None
    m = ctx.model
    shape = dict(
        live_tokens=ctx.live_kv["tokens"], rows=ctx.live_kv["sequences"],
        layers=kernel_cost_mla.latent_layers(m), hidden=int(m["hidden_size"]),
        heads=int(m["num_attention_heads"]), kv_rank=int(m["kv_lora_rank"]),
        nope=int(m["qk_nope_head_dim"]), rope=int(m["qk_rope_head_dim"]),
        v_dim=int(m["v_head_dim"]), q_lora=bool(m.get("q_lora_rank")),
    )
    least = kernel_cost.roofline_ms(
        kernel_cost_mla.mla_decode_flops(**shape),
        kernel_cost_mla.mla_decode_bytes(row_values=row_values, **shape), ctx.peaks,
    )
    return 100.0 * least / ms
