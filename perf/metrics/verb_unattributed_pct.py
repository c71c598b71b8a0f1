"""Layer: verb front end. The verb span's self time over its duration: the
share of a call's host time that no span below the verb names yet.
Mean over the traced slice's calls whose spans are all still in the
package's ring (perf/lib/spans.py)."""

from perf.lib import spans


def read(ctx):
    return spans.metric(ctx, "unattributed_pct")
