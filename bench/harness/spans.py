"""The program's request-trace spans that lie inside the measured window.

The traced run starts and stops the profiler on the event loop at the
window's edges (``trace.Profile``), which stalls the loop there. A span
that crosses the window's open or close also times that stall, and the
batches served after the close drain a queue no longer fed; so the
readers of the program's own spans count only spans, or requests, that
lie wholly inside ``[run.start, run.close]``.
"""
from __future__ import annotations


def inside(run, t0: float, t1: float) -> bool:
    return run.start <= t0 and t1 <= run.close


def batch_spans(run, name: str) -> list[float]:
    """Durations (s) of the distinct spans ``name`` inside the window: a
    per-batch span is shared by the batch's traces and counted once."""
    seen = {}
    for tr in run.traces or ():
        for s in tr.spans:
            if s.name == name and inside(run, s.t0, s.t1):
                seen[(s.t0, s.t1)] = s.t1 - s.t0
    return list(seen.values())
