"""Layer: XLA programs. Share of the verb's own program's device time
(XLA modules matching `program_modules`) spent in the sliding-window
attention kernel (device operations matching `kernel_ops.swa_attention`)."""

from perf.lib import swa_ops


def read(ctx):
    spent = swa_ops.seconds(ctx)
    if not spent or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * spent / ctx.trace["program_seconds"]
