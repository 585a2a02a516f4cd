"""Median wait of a leader request in the scheduler's admission queue,
arrival to batch formation: the ``queue_wait`` span of ``obs/trace.py``."""
import statistics


def read(run):
    xs = [s.t1 - s.t0 for tr in run.traces or () for s in tr.spans
          if s.name == "queue_wait"]
    return statistics.median(xs) * 1e3 if xs else None
