"""95th percentile of latency, due time to answer, of the requests answered
from the cache (``Response.cached``, coalesced waiters of a hit included):
the latency the cache exists to cut."""
import math

from harness.runner import percentile


def value(run):
    rec = run.records
    if rec.due[0] is None:
        return None
    xs = run.latencies_ms(lambda i: rec.resp[i] is not None
                          and rec.resp[i].cached)
    if not xs:
        return None
    v = percentile(xs, 95)
    return v if math.isfinite(v) else None
