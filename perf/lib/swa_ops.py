"""Device seconds of a configuration's sliding-window attention kernel in
a traced slice: the operations of `trace.reduce_events`' `device_ops` (the
ten that took most device time, by `op_label`) whose label matches the
configuration's `kernel_ops.swa_attention`. None where the configuration
names no such pattern or nothing matches (a program without the kernel, or
a kernel that is not among the ten)."""

import re


def seconds(ctx):
    pattern = ctx.config.get("kernel_ops", {}).get("swa_attention")
    if not pattern:
        return None
    found = [s for label, s in ctx.trace["device_ops"] if re.search(pattern, label)]
    return sum(found) if found else None
