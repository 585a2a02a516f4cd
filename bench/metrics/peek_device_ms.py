"""Device time of the peek program per batch, in ms: the busy time of each
run of the program named ``jit_peek`` (``CachedEngine``'s pure lookup,
the slab scan of ``core/cache.py`` on the ``core/store.py`` slab),
averaged over its runs in the trace."""
import re

from harness.trace import program_ms

PEEK_PROGRAM = re.compile(r"^jit_peek\(")


def read(run):
    return program_ms(run, PEEK_PROGRAM)
