"""Layer: XLA programs. The least time the chip could take for the
state-space scans of the traced calls (perf/lib/work_map_blocks_lm_hybrid:
the larger of `ssd_flops` at the bf16 peak and `ssd_bytes` at the HBM
peak) over the device time of the scan kernel: the device operations
whose label matches the configuration's `kernel_ops.ssd_scan`, as
`perf/lib/trace.py` lists them."""

from perf.lib import ssd_ops


def read(ctx):
    spent = ssd_ops.seconds(ctx)
    if not spent or not ctx.traced_calls:
        return None
    from perf.lib import work_map_blocks_lm_hybrid as work

    tokens = ctx.rows_per_call * len(ctx.traced_calls) * ctx.config["score_window"]
    least = max(
        work.ssd_flops(ctx.config, tokens) / ctx.peaks["bf16_flops_per_s"],
        work.ssd_bytes(ctx.config, tokens) / ctx.peaks["hbm_bytes_per_s"],
    ) / ctx.chips
    return 100.0 * least / spent
