"""Mean ``embed`` span per engine batch: host-side hash embedding of the
batch's queries in ``CachedEngine.serve_batch``."""
import statistics


def read(run):
    xs = run.stage_spans("embed")
    return statistics.fmean(xs) * 1e3 if xs else None
