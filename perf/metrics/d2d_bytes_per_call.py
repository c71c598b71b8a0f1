"""Layer: executor and scheduler. Bytes a call copied between devices
(or from the host): the feeds that changed device on their way to their
blocks' devices (counters `scheduler.bytes_in{device=}`, summed over the
receiving devices) and the parts copied back to the anchor
(`scheduler.bytes_back`), over the window's calls. None where the
program has no such counter."""


def summed(counters, name):
    """The sum over the labels of the counter `name` (`name{device=..}`
    as `flat_counters` renders them), or None where there is none."""
    got = [v for k, v in counters.items() if k.startswith(name + "{")]
    return sum(got) if got else None


def calls_in_window(ctx):
    return ctx.window["rows"] / ctx.rows_per_call if ctx.rows_per_call else 0


def read(ctx):
    calls = calls_in_window(ctx)
    there = summed(ctx.counters, "scheduler.bytes_in")
    back = ctx.counters.get("scheduler.bytes_back")
    if not calls or (there is None and back is None):
        return None
    return ((there or 0.0) + (back or 0.0)) / calls
