"""Layer: XLA programs. The least time the chip could take for the real
rows of the traced calls (perf/lib/work.py: the larger of bytes over the
HBM peak and FLOPs over the bf16 peak) over the summed device time of the
verb's own program (XLA modules matching the configuration's
`program_modules`) in the traced slice, over the chips that ran it."""


def read(ctx):
    spent = ctx.trace["program_seconds"]
    if not spent or not ctx.traced_calls:
        return None
    rows = ctx.rows_per_call * len(ctx.traced_calls)
    least, bound = ctx.least_seconds(rows, ctx.work, ctx.peaks)
    ctx.trace["program_roofline_bound"] = bound
    return 100.0 * least / spent
