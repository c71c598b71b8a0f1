"""Layer: XLA programs. The least time the chip could take for the sparse
core of the traced calls at the SELECTED pairs alone (perf/lib/
work_map_blocks_lm_sparse.attention_flops: windows x Σ_t min(t + 1,
index_topk) x layers x heads x 2 x (score width + value width), at the
bf16 peak) over the device time of the sparse attention kernel: the device
operations whose label matches `kernel_ops.dsa_attention` (named after its
scope `lm.dsa`). A kernel that computes more pairs than it keeps reads
that much lower."""

from perf.lib import dsa_ops


def read(ctx):
    from perf.lib import work_map_blocks_lm_sparse as work

    return dsa_ops.roofline(ctx, "dsa_attention", work.attention_flops)
