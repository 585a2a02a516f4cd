"""Compile counter: every program this process lowers or compiles.

JAX reports its compile pipeline through ``jax.monitoring``. Under jax
0.9.0 each program the in-memory jit cache misses fires, once each and
on the compiling thread, a time span

    /jax/core/compile/jaxpr_to_mlir_module_duration   lowering
    /jax/core/compile/backend_compile_duration        backend compile,
                                                       or a load from the
                                                       persistent cache

and a load from the persistent compilation cache also fires the plain
event ``/jax/compilation_cache/cache_hits`` inside the backend span. A
program served from the in-memory cache fires none of them, so the
number of lowerings is the number of programs made since the listener
was registered: a steady serving window should add none.

``compile_log()`` registers the listeners once per process (JAX's
monitoring hooks are process-wide, so the log is too) and returns the
``CompileLog`` they feed: a lowering count and a bounded ring of
``CompileSpan``s on the ``time.perf_counter`` clock the request traces
use. The listeners run only on the compile path, never on a cached
dispatch. ``/metrics`` exports the count as ``repro_xla_compiles_total``.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque

LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


@dataclasses.dataclass(frozen=True)
class CompileSpan:
    """One step of one program's compile. ``kind`` is ``lowering``,
    ``backend_compile`` or ``cache_load``; ``t0``/``t1`` are
    ``time.perf_counter`` seconds."""

    kind: str
    fun_name: str
    t0: float
    t1: float


class CompileLog:
    """Lowering count plus the most recent ``maxlen`` compile spans."""

    def __init__(self, maxlen: int = 4096):
        self.lowerings = 0
        self._ring: deque[CompileSpan] = deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._hit = threading.local()   # a cache hit inside this thread's
                                        # open backend compile

    def on_event(self, event: str, **_kw) -> None:
        if event == CACHE_HIT_EVENT:
            self._hit.seen = True

    def on_span(self, event: str, start: float, end: float,
                fun_name: str = "", **_kw) -> None:
        if event == LOWERING_EVENT:
            kind = "lowering"
        elif event == BACKEND_EVENT:
            kind = ("cache_load" if getattr(self._hit, "seen", False)
                    else "backend_compile")
            self._hit.seen = False
        else:
            return
        # jax times spans on the wall clock; the traces use perf_counter
        shift = time.perf_counter() - time.time()
        span = CompileSpan(kind, str(fun_name), start + shift, end + shift)
        with self._lock:
            if kind == "lowering":
                self.lowerings += 1
            self._ring.append(span)

    def spans(self, t0: float = float("-inf"),
              t1: float = float("inf")) -> list[CompileSpan]:
        """Retained spans that start inside ``[t0, t1]``."""
        with self._lock:
            return [s for s in self._ring if t0 <= s.t0 <= t1]


_log: CompileLog | None = None
_log_lock = threading.Lock()


def compile_log() -> CompileLog:
    """The process's compile log; registers its listeners on first call."""
    global _log
    with _log_lock:
        if _log is None:
            from jax import monitoring
            log = CompileLog()
            monitoring.register_event_time_span_listener(log.on_span)
            monitoring.register_event_listener(log.on_event)
            _log = log
        return _log
