"""Layer: executor and scheduler. Host milliseconds a call in the
`dispatch`-kind spans (the jitted call as the program's wrapper issues
it) plus the self time of `<verb>.blocks`: the block loop's own glue.
Mean over the traced slice's calls whose spans are all still in the
package's ring (perf/lib/spans.py)."""

from perf.lib import spans


def read(ctx):
    return spans.metric(ctx, "dispatch_ms")
