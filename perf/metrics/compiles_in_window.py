"""Layer: executor and scheduler. `jax.monitoring` backend-compile
events (a compile or a fetch from the persistent cache: a program the
warm-up did not reach) inside the window and the traced slice."""


def read(ctx):
    return ctx.compiles_in_window
