"""The KDA layers' share of their roofline (%) in a decode step: the
least time the chip could take for the live rows' state (read and written
once) and the layers' projections (``kernel_cost_hybrid.py``), over the
device time under ``llmq.attn.kda`` per step. The live rows are the mean
``state_rows`` of the window's ``decode_dispatch`` spans; nothing to read
where the program writes no such field."""

from .. import kernel_cost, kernel_cost_hybrid, span_join
from . import span_stat


def read(ctx, *, program, scope):
    j = span_join.load(ctx)
    if j is None or ctx.peaks is None:
        return None
    rows = span_stat.read(ctx, name="decode_dispatch", field="state_rows")
    ms = span_join.scope_ms_per_run(j, program, scope)
    if not rows or not ms:
        return None
    m = ctx.model
    group = int(m["layer_group_size"])
    kept = m.get("kept_layers") or range(int(m["num_hidden_layers"]))
    heads = int(m["num_attention_heads"])
    shape = dict(
        rows=rows, layers=sum(1 for i in kept if (i + 1) % group), hidden=int(m["hidden_size"]),
        heads=heads, head_dim=int(m.get("head_dim") or int(m["hidden_size"]) // heads),
    )
    return 100.0 * kernel_cost.roofline_ms(
        kernel_cost_hybrid.kda_decode_flops(**shape),
        kernel_cost_hybrid.kda_decode_bytes(**shape), ctx.peaks,
    ) / ms
