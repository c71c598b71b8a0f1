"""Layer: whole window, matmul. FLOPs the algorithm needs per real row
times this run's rows/s, over the bf16 peak of the chips the cell holds."""


def read(ctx):
    rate = ctx.end_to_end["rows_per_s"]
    if not rate:
        return None
    return (100.0 * ctx.work["flops_per_row"] * rate
            / (ctx.peaks["bf16_flops_per_s"] * ctx.chips))
