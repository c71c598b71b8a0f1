"""Layer: executor and scheduler. Host milliseconds a call spent issuing
copies between devices: the block scheduler's puts of the feeds onto
their blocks' devices (counters `scheduler.put_seconds{device=}`, summed
over the devices) and the gather of the parts back to the anchor
(`scheduler.gather_seconds`), over the window's calls. The puts lie
inside the dispatch spans and the gather inside `frame.concat`, so this
is a part of `dispatch_host_ms_per_call` + `cut_concat_host_ms_per_call`,
not beside them. None where the program has no such counter."""

from perf.metrics.d2d_bytes_per_call import calls_in_window, summed


def read(ctx):
    calls = calls_in_window(ctx)
    puts = summed(ctx.counters, "scheduler.put_seconds")
    gather = ctx.counters.get("scheduler.gather_seconds")
    if not calls or (puts is None and gather is None):
        return None
    return 1e3 * ((puts or 0.0) + (gather or 0.0)) / calls
