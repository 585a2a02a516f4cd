"""Device time of the fused step per batch, in ms: the busy time of each
run of the program named ``jit_step`` (``CachedEngine``'s commit of the
peek plus the masked insert), averaged over its runs in the trace."""
import re

from harness.trace import program_ms

STEP_PROGRAM = re.compile(r"^jit_step\(")


def read(run):
    return program_ms(run, STEP_PROGRAM)
