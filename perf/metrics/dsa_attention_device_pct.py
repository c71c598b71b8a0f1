"""Layer: XLA programs. Share of the verb's own program's device time spent
in the sparse attention kernel (device operations matching
`kernel_ops.dsa_attention`)."""

from perf.lib import dsa_ops


def read(ctx):
    return dsa_ops.share(ctx, "dsa_attention")
