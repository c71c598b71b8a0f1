"""Layer: device. 1 - the union of device-operation intervals over the
traced slice; the mean over the cell's devices (each device's own is
printed on an earlier line of the run)."""


def read(ctx):
    per = ctx.trace["per_device"]
    if not per or not ctx.trace["window_s"]:
        return None
    return sum(d["idle_pct"] for d in per) / len(per)
