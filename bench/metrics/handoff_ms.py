"""Time per batch spent handing work between the scheduler's event loop
and the engine's worker thread, in ms: the mean per batch of ``dispatch``
(formed batch to the engine's first stage) plus ``resolve`` (the engine's
last stage to the batch's last future set), spans of ``obs/trace.py``,
over the batches inside the window (``harness.spans``)."""
from harness.spans import batch_spans


def read(run):
    dispatch = batch_spans(run, "dispatch")
    if not dispatch:
        return None
    total = sum(dispatch) + sum(batch_spans(run, "resolve"))
    return 1e3 * total / len(dispatch)
