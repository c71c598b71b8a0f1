"""Layer: XLA programs. Share of the verb's own program's device time
(XLA modules matching `program_modules`) spent in the grouped expert
matmuls (device operations matching `kernel_ops.moe_experts`)."""

from perf.lib import moe_ops


def read(ctx):
    spent = moe_ops.seconds(ctx)
    if not spent or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * spent / ctx.trace["program_seconds"]
