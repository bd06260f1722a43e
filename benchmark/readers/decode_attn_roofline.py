"""The decode attention kernel's share of its roofline (%): the least
time the chip could take for the bytes and operations the step's live
cache needs (``kernel_cost.py``), over the kernel's measured time per
step. The live cache is read from the engine in the middle of the trace."""

from .. import kernel_cost, trace_reduce


def read(ctx, *, program, op):
    if ctx.trace is None or not ctx.live_kv:
        return None
    ms = trace_reduce.op_ms_per_run(ctx.trace, program, op)
    if not ms:
        return None
    m = ctx.model
    heads = int(m["num_attention_heads"])
    d = int(m.get("head_dim") or int(m["hidden_size"]) // heads)
    shape = dict(layers=int(m["num_hidden_layers"]), q_heads=heads, head_dim=d)
    nbytes = kernel_cost.decode_attention_bytes(
        live_tokens=ctx.live_kv["tokens"],
        sequences=ctx.live_kv["sequences"],
        kv_heads=int(m["num_key_value_heads"]),
        **shape,
    )
    flops = kernel_cost.decode_attention_flops(
        live_tokens=ctx.live_kv["tokens"], **shape
    )
    return 100.0 * kernel_cost.roofline_ms(flops, nbytes, ctx.peaks) / ms
