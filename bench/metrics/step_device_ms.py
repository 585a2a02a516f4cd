"""Device time of the fused step per batch, in ms: the busy time of each
run of the program named ``jit_step`` (``CachedEngine``'s commit of the
peek plus the masked insert), averaged over its runs in the trace."""
import re

from harness.trace import union_s, within

STEP_PROGRAM = re.compile(r"^jit_step\(")


def read(run):
    if run.trace is None:
        return None
    per_run = []
    for dev in run.trace["raw"]["devices"].values():
        steps = [m for m in dev["modules"] if STEP_PROGRAM.match(m[0])]
        for evs in within(dev["ops"], steps).values():
            per_run.append(union_s([(a, b) for _, a, b in evs]))
    return 1e3 * sum(per_run) / len(per_run) if per_run else None
