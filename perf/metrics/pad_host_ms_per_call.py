"""Layer: shape policy. Host milliseconds a call in the spans `shape.pad`
(pad a block's feeds up to the bucket) and `shape.unpad` (pad rows off
the outputs).
Mean over the traced slice's calls whose spans are all still in the
package's ring (perf/lib/spans.py)."""

from perf.lib import spans


def read(ctx):
    return spans.metric(ctx, "pad_ms")
