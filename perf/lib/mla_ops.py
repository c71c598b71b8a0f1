"""Device seconds of a configuration's latent-attention kernel in a traced
slice: the operations of `trace.reduce_events`' `device_ops` (the ten that
took most device time, by `op_label`) whose label matches the
configuration's `kernel_ops.mla_attention`. None where the configuration
names no such pattern or nothing matches (a program without the kernel)."""

import re


def seconds(ctx):
    pattern = ctx.config.get("kernel_ops", {}).get("mla_attention")
    if not pattern:
        return None
    found = [s for label, s in ctx.trace["device_ops"] if re.search(pattern, label)]
    return sum(found) if found else None
