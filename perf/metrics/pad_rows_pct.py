"""Layer: shape policy. Pad rows dispatched over real rows dispatched in
the window, from the program's counter `shape_bucketing.pad_rows`."""


def read(ctx):
    if "shape_bucketing.pad_rows" not in ctx.counters or not ctx.window["rows"]:
        return None
    return 100.0 * ctx.counters["shape_bucketing.pad_rows"] / ctx.window["rows"]
