"""The grouped-query decode attention's share of its roofline (%) in a
model whose layers are not all attention: the least time the chip could
take for the bytes and operations of what runs under
``llmq.attn.gqa_decode`` (``kernel_cost_gqa.py``: the live keys and values
once an attention layer, the scope's matrices once), the larger of the
HBM and the MXU bound, over the device time under that scope per step.
The attention layers are counted from the configuration's ``layer_types``
(``kernel_cost_gqa.attention_layers``). The live cache is read from the
engine in the middle of the trace (``decode_attn_roofline.py`` reads it
so). Nothing to read where the program has no such scope."""

from .. import kernel_cost, kernel_cost_gqa, span_join


def read(ctx, *, program, scope):
    j = span_join.load(ctx)
    if j is None or ctx.peaks is None or not ctx.live_kv:
        return None
    ms = span_join.scope_ms_per_run(j, program, scope)
    if not ms:
        return None
    m = ctx.model
    heads = int(m["num_attention_heads"])
    shape = dict(
        live_tokens=ctx.live_kv["tokens"], rows=ctx.live_kv["sequences"],
        layers=kernel_cost_gqa.attention_layers(m), hidden=int(m["hidden_size"]),
        heads=heads, kv_heads=int(m.get("num_key_value_heads", heads)),
        head_dim=int(m.get("head_dim") or int(m["hidden_size"]) // heads),
    )
    least = kernel_cost.roofline_ms(
        kernel_cost_gqa.gqa_decode_flops(**shape),
        kernel_cost_gqa.gqa_decode_bytes(**shape), ctx.peaks,
    )
    return 100.0 * least / ms
