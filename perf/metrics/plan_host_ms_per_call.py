"""Layer: verb front end. Host milliseconds a call in the span `<verb>.plan`:
graph analysis, column matching, the executor's and the scheduler's
look-ups, before the first block is dispatched.
Mean over the traced slice's calls whose spans are all still in the
package's ring (perf/lib/spans.py)."""

from perf.lib import spans


def read(ctx):
    return spans.metric(ctx, "plan_ms")
