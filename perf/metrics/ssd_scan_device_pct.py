"""Layer: XLA programs. Share of the verb's own program's device time
(XLA modules matching `program_modules`) spent in the state-space scan
kernel (device operations matching `kernel_ops.ssd_scan`)."""

from perf.lib import ssd_ops


def read(ctx):
    spent = ssd_ops.seconds(ctx)
    if not spent or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * spent / ctx.trace["program_seconds"]
