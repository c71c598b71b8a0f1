"""Layer: XLA programs. The least time the chip could take for the expert
matmuls of the traced calls (perf/lib/work_map_blocks_lm.expert_flops:
tokens x expert layers x experts per token x three matrices, at the bf16
peak) over the device time of the grouped expert matmuls: the device
operations whose label matches the configuration's
`kernel_ops.moe_experts`, as `perf/lib/trace.py` lists them."""

from perf.lib import moe_ops


def read(ctx):
    spent = moe_ops.seconds(ctx)
    if not spent or not ctx.traced_calls:
        return None
    from perf.lib import work_map_blocks_lm as work

    tokens = ctx.rows_per_call * len(ctx.traced_calls) * ctx.config["score_window"]
    least = work.expert_flops(ctx.config, tokens) / (
        ctx.peaks["bf16_flops_per_s"] * ctx.chips
    )
    return 100.0 * least / spent
