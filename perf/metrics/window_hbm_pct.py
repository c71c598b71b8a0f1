"""Layer: whole window, elementwise. Bytes the algorithm needs per real
row times this run's rows/s, over the HBM peak of the chips the cell
holds."""


def read(ctx):
    rate = ctx.end_to_end["rows_per_s"]
    if not rate:
        return None
    return (100.0 * ctx.work["bytes_per_row"] * rate
            / (ctx.peaks["hbm_bytes_per_s"] * ctx.chips))
