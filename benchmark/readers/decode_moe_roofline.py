"""The routed experts' share of their roofline (%) in a decode step: the
least time the chip could take for the held experts that were hit and
their assignments (``kernel_cost_hybrid.py``; both from the program's
counters ``moe_experts_hit`` and ``moe_assignments_held`` over the
window's decode steps), over the device time under ``llmq.moe.experts``
per step. Nothing to read where the program has no such counters."""

from .. import kernel_cost, kernel_cost_hybrid, span_join


def per_step(ctx, key):
    d = ctx.records.drive
    steps = d.stats1.get("decode_steps", 0) - d.stats0.get("decode_steps", 0)
    if key not in d.stats0 or key not in d.stats1 or steps <= 0:
        return None
    return (d.stats1[key] - d.stats0[key]) / steps


def read(ctx, *, program, scope):
    hit = per_step(ctx, "moe_experts_hit")
    held = per_step(ctx, "moe_assignments_held")
    j = span_join.load(ctx)
    if hit is None or held is None or j is None or ctx.peaks is None:
        return None
    ms = span_join.scope_ms_per_run(j, program, scope)
    if not ms:
        return None
    m = ctx.model
    shape = dict(hidden=int(m["hidden_size"]), width=int(m["moe_intermediate_size"]))
    nbytes = kernel_cost_hybrid.moe_expert_bytes(
        experts_hit=hit, assignments=held, **shape
    )
    flops = kernel_cost_hybrid.moe_expert_flops(assignments=held, **shape)
    return 100.0 * kernel_cost.roofline_ms(flops, nbytes, ctx.peaks) / ms
