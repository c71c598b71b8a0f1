"""Layer: verb front end. The benchmark's own clock around each verb
call up to its return (work enqueued, before the wait); mean over the
window's calls."""


def read(ctx):
    return ctx.window["verb_host_ms_per_call"]
