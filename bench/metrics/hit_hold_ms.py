"""Median time a hit's answer was held after it was known: over leader
requests the engine served as hits (``meta["path"] == "hit"``) inside the
window (``harness.spans``), the end of the batch's ``resolve`` span (its
last future set) minus the trace's ``ready_t`` (the end of the peek's
host sync, ``peek_sync``)."""
import statistics

from harness.spans import inside


def read(run):
    xs = []
    for tr in run.traces or ():
        ready = tr.meta.get("ready_t")
        if tr.meta.get("path") != "hit" or ready is None:
            continue
        ends = [s.t1 for s in tr.spans if s.name == "resolve"]
        if ends and inside(run, ready, ends[-1]):
            xs.append(ends[-1] - ready)
    return statistics.median(xs) * 1e3 if xs else None
