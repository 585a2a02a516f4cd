"""Device time from a ``jax.profiler`` trace of the measured window.

``Profile`` starts and stops the profiler (host Python tracing off) and
reads the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData``. On a
TPU each chip is a plane ``/device:TPU:<n>``; its ``XLA Ops`` line holds
one event per operation run, its ``XLA Modules`` line one per program run.
Event times are nanoseconds from the profile's start, which the plane
``Task Environment`` gives on the wall clock.

``reduce`` turns a trace into plain numbers: busy seconds (the union of
operation intervals) against the traced window, operation time by name,
program runs by name, and idle gaps. The per-layer metrics read those.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
import shutil
import tempfile
import time
import warnings

_DEVICE = re.compile(r"^/device:TPU:\d+$")


def op_name(event_name: str) -> str:
    """``%fusion.10 = f32[...] fusion(...)`` -> ``fusion.10``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def union_s(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out = []
    t = lo
    for a, b in sorted(intervals):
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def tile(spans: list[tuple[float, float, str]]
         ) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the union of named spans.
    Where spans overlap, the one that started last names the piece, so
    that no instant is counted twice (a waiter's long ``respond`` span
    lies over the stages of the batches it waited through)."""
    if not spans:
        return []
    events = sorted(spans)
    points = sorted({t for a, b, _ in spans for t in (a, b)})
    active: list = []   # (-start, order, end, name): the latest start on top
    out: list[tuple[float, float, str]] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(events) and events[i][0] <= a:
            t0, t1, name = events[i]
            heapq.heappush(active, (-t0, i, t1, name))
            i += 1
        while active and active[0][2] <= a:
            heapq.heappop(active)
        if not active:
            continue
        name = active[0][3]
        if out and out[-1][1] == a and out[-1][2] == name:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def read(path: str) -> dict:
    """The parts of one ``.xplane.pb`` that the metrics need: per device
    plane its ops ``[(name, start_s, end_s)]`` and program runs, plus the
    window's start and stop on the wall clock (ns)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "start_ns": None, "stop_ns": None}
    for plane in data.planes:
        if plane.name == "Task Environment":
            with warnings.catch_warnings():
                # jax's stats type lacks a __module__; harmless
                warnings.simplefilter("ignore", DeprecationWarning)
                stats = dict(plane.stats)
            out["start_ns"] = int(stats["profile_start_time"])
            out["stop_ns"] = int(stats["profile_stop_time"])
        if not _DEVICE.match(plane.name):
            continue
        dev = {"ops": [], "modules": []}
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
            if key is None:
                continue
            for e in line.events:
                dev[key].append((e.name, e.start_ns / 1e9,
                                 (e.start_ns + e.duration_ns) / 1e9))
        out["devices"][plane.name] = dev
    return out


def reduce(trace: dict) -> dict:
    """Busy and idle time, op and program time, from ``read``'s output.
    Averages over the chips traced."""
    window = (trace["stop_ns"] - trace["start_ns"]) / 1e9
    devices = trace["devices"]
    if not devices or window <= 0:
        return {"window_s": window, "busy_s": 0.0, "devices": 0}
    busy = []
    op_s: dict[str, float] = {}
    for dev in devices.values():
        spans = [(a, min(b, window)) for _, a, b in dev["ops"] if a < window]
        busy.append(union_s(spans))
        for name, a, b in dev["ops"]:
            n = op_name(name)
            op_s[n] = op_s.get(n, 0.0) + (b - a)
    n_dev = len(devices)
    return {"window_s": window, "busy_s": sum(busy) / n_dev,
            "devices": n_dev,
            "op_s": {k: v / n_dev for k, v in op_s.items()}}


class Profile:
    """Trace the window on the device; host Python calls are not traced."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.clock = None     # (wall ns, perf_counter s) read together
        self.path = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.clock = (time.time_ns(), time.perf_counter())
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self) -> None:
        import jax

        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = found[0] if found else None

    def to_perf_counter(self, trace: dict, t_s: float) -> float:
        """A trace time (s from the profile's start) on ``perf_counter``."""
        wall_ns, pc = self.clock
        return pc + (trace["start_ns"] - wall_ns) / 1e9 + t_s

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def idle_pct(run) -> float | None:
    """100 × (1 − busy / window) of a traced run, None without a trace."""
    if run.trace is None:
        return None
    red = run.trace["reduced"]
    if red["window_s"] <= 0 or not red["devices"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def within(events: list, runs: list) -> dict:
    """For each program run ``(name, a, b)``, the events inside it."""
    out = {}
    events = sorted(events, key=lambda e: e[1])
    starts = [e[1] for e in events]
    for run in runs:
        _, a, b = run
        j = bisect.bisect_left(starts, a)
        inside = []
        while j < len(events) and events[j][1] < b:
            if events[j][2] <= b:
                inside.append(events[j])
            j += 1
        out[run] = inside
    return out


def program_ms(run, name) -> float | None:
    """Mean busy time in ms of the runs of the programs whose name matches
    ``name`` (a compiled pattern), over every device traced; None without
    a trace or such a run."""
    if run.trace is None:
        return None
    per_run = []
    for dev in run.trace["raw"]["devices"].values():
        runs = [m for m in dev["modules"] if name.match(m[0])]
        for evs in within(dev["ops"], runs).values():
            per_run.append(union_s([(a, b) for _, a, b in evs]))
    return 1e3 * sum(per_run) / len(per_run) if per_run else None
