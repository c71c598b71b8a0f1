"""Layer: XLA programs. Share of the verb's own program's device time
(XLA modules matching `program_modules`) spent in the latent-attention
kernel (device operations matching `kernel_ops.mla_attention`)."""

from perf.lib import mla_ops


def read(ctx):
    spent = mla_ops.seconds(ctx)
    if not spent or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * spent / ctx.trace["program_seconds"]
