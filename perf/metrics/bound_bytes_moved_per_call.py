"""Layer: executor and scheduler. Bytes of bound leaves copied to a
device (from the host or from another device) a call in the window, from
the program's counter `bindings.bytes_placed`. Weights bound as resident
device arrays read 0; None where the program has no such counter."""


def read(ctx):
    moved = ctx.counters.get("bindings.bytes_placed")
    calls = ctx.window["rows"] / ctx.rows_per_call if ctx.rows_per_call else 0
    if moved is None or not calls:
        return None
    return moved / calls
