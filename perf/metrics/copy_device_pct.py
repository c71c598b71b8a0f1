"""Layer: XLA programs. Share of the device's module time spent in
modules other than the verb's own program: pad `concatenate`, slices,
the concat of block outputs."""


def read(ctx):
    total = ctx.trace["program_seconds"] + ctx.trace["other_module_seconds"]
    if not total or not ctx.trace["program_seconds"]:
        return None
    return 100.0 * ctx.trace["other_module_seconds"] / total
