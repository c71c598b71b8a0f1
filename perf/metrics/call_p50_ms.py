"""Layer: whole window. The median call, issue to ready as the caller
sees it: the steadier statistic beside `call_p95_ms`, and the only call
time of a cell whose window holds too few calls for a tail."""


def read(ctx):
    return ctx.window["call_p50_ms"]
