"""The slab scan kernel's share of its roofline, in percent.

The least time the chip could take for one scan of the whole slab by one
batch (``harness.roofline.slab_scan_work``: 2·B·N·d float GEMM operations
against the bf16 peak, the keys' bytes and a validity byte per slot
against HBM bandwidth, whichever is longer) over the mean device time of
one run of the scan kernel. The events that count as the scan are the
Pallas cosine top-k kernels of ``kernels/cosine_topk.py``, whose ops are
named after their entry point (``cosine_topk_pallas``,
``quant_cosine_topk_pallas``, their interval and masked forms).
"""
import re

from harness import roofline

SCAN_KERNEL = re.compile(r"^%?(quant_)?cosine_topk(_interval|_masked)?"
                         r"_pallas(\.\d+)?$")


def scan_events(run):
    out = []
    for dev in run.trace["raw"]["devices"].values():
        out += [e for e in dev["ops"]
                if SCAN_KERNEL.match(e[0].split(" = ", 1)[0])]
    return out


def read(run):
    if run.trace is None:
        return None
    events = scan_events(run)
    if not events:
        return None
    mean_s = sum(b - a for _, a, b in events) / len(events)
    ops, nbytes = roofline.slab_scan_work(run.config)
    least, _ = roofline.least_time_s(ops, nbytes, run.peaks)
    return 100.0 * least / mean_s
