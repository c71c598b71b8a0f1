"""Device seconds of a configuration's sparse-attention kernels in a traced
slice: the operations of `trace.reduce_events`' `device_ops` (the ten that
took most device time, by `op_label`) whose label matches the
configuration's `kernel_ops[which]` (`dsa_index`, `dsa_attention`). None
where the configuration names no such pattern or nothing matches (a
program without the kernel, or a kernel that is not among the ten)."""

import re


def seconds(ctx, which):
    pattern = ctx.config.get("kernel_ops", {}).get(which)
    if not pattern:
        return None
    found = [s for label, s in ctx.trace["device_ops"] if re.search(pattern, label)]
    return sum(found) if found else None


def share(ctx, which):
    """The kernel's share of the verb's own program's device time, %."""
    spent = seconds(ctx, which)
    if not spent or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * spent / ctx.trace["program_seconds"]


def roofline(ctx, which, flops):
    """The least time for `flops(config, rows)` of the traced calls' rows
    at the bf16 peak, over the kernel's device time, %."""
    spent = seconds(ctx, which)
    if not spent or not ctx.traced_calls:
        return None
    rows = ctx.rows_per_call * len(ctx.traced_calls)
    least = flops(ctx.config, rows) / (ctx.peaks["bf16_flops_per_s"] * ctx.chips)
    return 100.0 * least / spent
