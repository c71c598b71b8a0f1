"""Layer: XLA programs. Share of the verb's own program's device time (XLA
modules matching `program_modules`) spent in the indexer kernel (device
operations matching `kernel_ops.dsa_index`)."""

from perf.lib import dsa_ops


def read(ctx):
    return dsa_ops.share(ctx, "dsa_index")
