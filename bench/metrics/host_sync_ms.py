"""Host time blocked on the device per engine batch, in ms: the mean per
batch of ``peek_sync`` (the peek's hit mask to the host), ``step_sync``
(the fused step's ``block_until_ready``) and ``fetch`` (the step's
results to the host), children of ``device_step``, ``insert`` and
``respond`` in ``obs/trace.py``, over the batches inside the window
(``harness.spans``)."""
from harness.spans import batch_spans


def read(run):
    peek = batch_spans(run, "peek_sync")
    if not peek:
        return None
    total = sum(peek) + sum(batch_spans(run, "step_sync")) \
        + sum(batch_spans(run, "fetch"))
    return 1e3 * total / len(peek)
