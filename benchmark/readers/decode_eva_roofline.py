"""The EVA decode attention's share of its roofline (%): the least time
the chip could take for the bytes and operations of what runs under
``llmq.attn.eva_decode`` (``kernel_cost_eva.py``: the ATTENDED rows once a
layer, earlier windows' summaries and the own window's exact rows, and the
scope's matrices once), the larger of the HBM and the MXU bound, over the
device time under that scope per step. The attended rows and the
sequences of a step are the means of ``summary_rows + window_rows`` and
``rows`` over the ``decode_dispatch`` spans inside the window (the
traced stretch: the rings are on while the profile is taken); tier-1 holds
those fields to ``kernel_cost_eva.attended_rows``. Every layer of the
configuration is an EVA layer. Nothing to read where the program has no
such scope or no such fields."""

from .. import kernel_cost, kernel_cost_eva, span_join


def read(ctx, *, program, scope):
    j = span_join.load(ctx)
    if j is None or ctx.peaks is None:
        return None
    ms = span_join.scope_ms_per_run(j, program, scope)
    steps = [
        s for s in j.spans
        if s["name"] == "decode_dispatch" and "summary_rows" in s and "window_rows" in s
        and span_join.in_window(ctx, s["t0_ns"])
    ]
    if not ms or not steps:
        return None
    m = ctx.model
    heads = int(m["num_attention_heads"])
    shape = dict(
        attended=sum(s["summary_rows"] + s["window_rows"] for s in steps) / len(steps),
        rows=sum(s["rows"] for s in steps) / len(steps),
        layers=int(m["num_hidden_layers"]), hidden=int(m["hidden_size"]),
        heads=heads, head_dim=int(m.get("head_dim") or int(m["hidden_size"]) // heads),
    )
    least = kernel_cost.roofline_ms(
        kernel_cost_eva.eva_decode_flops(**shape),
        kernel_cost_eva.eva_decode_bytes(**shape), ctx.peaks,
    )
    return 100.0 * least / ms
