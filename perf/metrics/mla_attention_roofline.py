"""Layer: XLA programs. The least time the chip could take for the
attention core of the traced calls (perf/lib/work_map_blocks_lm_latent.
attention_flops: tokens x layers x heads x (score width + value width) x
the causal half of the window, at the bf16 peak) over the device time of
the latent-attention kernel: the device operations whose label matches
the configuration's `kernel_ops.mla_attention`, as `perf/lib/trace.py`
lists them."""

from perf.lib import mla_ops


def read(ctx):
    spent = mla_ops.seconds(ctx)
    if not spent or not ctx.traced_calls:
        return None
    from perf.lib import work_map_blocks_lm_latent as work

    tokens = ctx.rows_per_call * len(ctx.traced_calls) * ctx.config["score_window"]
    least = work.attention_flops(ctx.config, tokens) / (
        ctx.peaks["bf16_flops_per_s"] * ctx.chips
    )
    return 100.0 * least / spent
