"""Median wait of a leader request for the batch ahead of it: the
``engine_busy`` child of ``queue_wait`` (``obs/trace.py``), from arrival
to the instant the scheduler loop was free to admit again (zero for a
request that arrived while it was free), over requests whose wait lies
inside the window (``harness.spans``)."""
import statistics

from harness.spans import inside


def read(run):
    xs = [s.t1 - s.t0 for tr in run.traces or () for s in tr.spans
          if s.name == "engine_busy" and inside(run, s.t0, s.t1)]
    return statistics.median(xs) * 1e3 if xs else None
