"""Mean ``backend_call`` span per engine batch: the LLM API's round trip
for the batch's misses, on the engine's worker thread."""
import statistics


def read(run):
    xs = run.stage_spans("backend_call")
    return statistics.fmean(xs) * 1e3 if xs else None
