"""Layer: executor and scheduler. How evenly the block scheduler spread
the window's rows over its devices: 100 x the least over the most of the
counters `scheduler.rows{device=}` (a device that was planned nothing
reads 0 there, so it counts). None where the program has no such
counter."""


def read(ctx):
    rows = [v for k, v in ctx.counters.items()
            if k.startswith("scheduler.rows{")]
    if not rows or not max(rows):
        return None
    return 100.0 * min(rows) / max(rows)
