"""Device time of the peek program per batch, in ms: the busy time of the
runs of the program that holds the slab scan (the engine's pure lookup,
``core/cache.py`` on the ``core/store.py`` slab), averaged over its runs.
The program is found as the one whose runs contain the scan kernel's
events (named as in ``slab_scan_roofline.py``)."""
from harness.cell import BENCH, load_module
from harness.trace import union_s, within

SCAN_KERNEL = load_module(BENCH / "metrics" /
                          "slab_scan_roofline.py").SCAN_KERNEL


def read(run):
    if run.trace is None:
        return None
    per_run = []
    for dev in run.trace["raw"]["devices"].values():
        inside = within(dev["ops"], dev["modules"])
        counts = {}
        for (name, _, _), evs in inside.items():
            n = sum(bool(SCAN_KERNEL.match(e[0].split(" = ", 1)[0]))
                    for e in evs)
            counts[name] = counts.get(name, 0) + n
        if not counts or max(counts.values()) == 0:
            continue
        peek = max(counts, key=counts.get)
        per_run += [union_s([(a, b) for _, a, b in evs])
                    for (name, _, _), evs in inside.items() if name == peek]
    return 1e3 * sum(per_run) / len(per_run) if per_run else None
