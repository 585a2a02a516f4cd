"""95th percentile of request latency, each request timed from the instant
it was due to the instant its answer reached its caller (open loop). A
request that got no answer counts as infinitely late."""
import math

from harness.runner import percentile


def value(run):
    if run.records.due[0] is None:
        return None
    v = percentile(run.latencies_ms(), 95)
    return v if math.isfinite(v) else None
