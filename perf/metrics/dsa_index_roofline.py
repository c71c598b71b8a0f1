"""Layer: XLA programs. The least time the chip could take for the index
scores of the traced calls (perf/lib/work_map_blocks_lm_sparse.index_flops:
windows x causal pairs x index heads x 2 x index_head_dim x full layers,
at the bf16 peak) over the device time of the indexer kernel: the device
operations whose label matches the configuration's `kernel_ops.dsa_index`
(the kernel's custom call, named after its scope `lm.dsa_index`)."""

from perf.lib import dsa_ops


def read(ctx):
    from perf.lib import work_map_blocks_lm_sparse as work

    return dsa_ops.roofline(ctx, "dsa_index", work.index_flops)
