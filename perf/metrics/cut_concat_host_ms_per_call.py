"""Layer: frame. Host milliseconds a call in the spans `frame.cut`
(a block sliced out of the frame's columns) and `frame.concat` (the
blocks' outputs joined into one column).
Mean over the traced slice's calls whose spans are all still in the
package's ring (perf/lib/spans.py)."""

from perf.lib import spans


def read(ctx):
    return spans.metric(ctx, "cut_concat_ms")
