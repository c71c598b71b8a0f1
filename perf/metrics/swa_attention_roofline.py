"""Layer: XLA programs. The least time the chip could take for the sliding
layers' attention core of the traced calls at the band's pairs alone
(perf/lib/work_map_blocks_lm_window.window_flops: windows x Σ_t min(t + 1,
sliding_window) x heads x 2 x 2 x head_dim x sliding layers, at the bf16
peak) over the device time of the windowed kernel: the device operations
whose label matches `kernel_ops.swa_attention` (named after its scope
`lm.swa`). The pairs a visited block holds outside the band read as lost."""

from perf.lib import swa_ops


def read(ctx):
    spent = swa_ops.seconds(ctx)
    if not spent or not ctx.traced_calls:
        return None
    from perf.lib import work_map_blocks_lm_window as work

    rows = ctx.rows_per_call * len(ctx.traced_calls)
    least = work.window_flops(ctx.config, rows) / (ctx.peaks["bf16_flops_per_s"] * ctx.chips)
    return 100.0 * least / spent
