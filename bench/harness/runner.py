"""One run of one cell: set up, drive the window, check, report.

The order matters. Set-up builds, fills and warms the deployment; the
window drives the traffic; then the device's peak memory is read, the
trace reduced, the program's state freed, and only then does the
reference run, so that it never sets the peak.
"""
from __future__ import annotations

import asyncio
import bisect
import gc
import statistics
import sys
import time

from harness import cell as cellmod
from harness import check, deploy, roofline, trace, traffic, window


class Run:
    """What the metric readers read."""

    def __init__(self, spec, seconds: float):
        self.spec = spec
        self.config = spec.config
        self.seconds = seconds
        self.records = None
        self.start = None         # window start, perf_counter
        self.setup_s = None
        self.batches_served = None
        self.traces = None        # the window's RequestTraces (traced run)
        self.trace = None         # read trace + reduction (traced run)
        self.peaks = None
        self.device = None

    @property
    def close(self) -> float:
        return self.start + self.seconds

    def latencies_ms(self, pick=None) -> list[float]:
        """Due time to answer, in ms, of every request (open loop); one
        that got no answer counts as infinitely late."""
        rec = self.records
        out = []
        for i in range(len(rec)):
            if pick is not None and not pick(i):
                continue
            if rec.resp[i] is None:
                out.append(float("inf"))
            else:
                out.append((rec.t_obs[i] - rec.due[i]) * 1e3)
        return out

    def stage_spans(self, name: str) -> list[float]:
        """Durations (s) of one engine stage, once per batch."""
        seen = {}
        for tr in self.traces or ():
            for s in tr.spans:
                if s.name == name:
                    seen[(s.t0, s.t1)] = s.t1 - s.t0
        return list(seen.values())


def percentile(xs: list[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(xs)
    if not xs:
        return float("nan")
    r = (len(xs) - 1) * p / 100.0
    lo = int(r)
    hi = min(lo + 1, len(xs) - 1)
    if xs[hi] == float("inf"):
        return float("inf") if r > lo or xs[lo] == float("inf") else xs[lo]
    return xs[lo] + (xs[hi] - xs[lo]) * (r - lo)


def make_request(item):
    from repro.serving import Request

    return Request(query=item.query, category=item.category,
                   source_id=item.source_id, semantic_key=item.semantic_key)


def serve_window(spec, seed: int, seconds: float, *, traced: bool,
                 t_start: float, engine_hook=None) -> tuple[Run, list]:
    """Set up and drive one window. Returns the run and the corpus.
    ``engine_hook`` lets a test break the engine before the window."""
    from repro.serving import AsyncCacheServer

    run = Run(spec, seconds)
    corpus = deploy.corpus_of(spec.config)
    tracer = None
    if traced:
        from repro.obs import TraceConfig, Tracer
        tracer = Tracer(TraceConfig(sample_rate=1.0, head=0,
                                    max_traces=1 << 22))
    engine = deploy.build(spec.config, corpus, seed, tracer=tracer)
    if engine_hook is not None:
        engine_hook(engine)
    flow = traffic.generate(spec.traffic, corpus, seed, seconds)
    profile = trace.Profile() if traced else None
    server = AsyncCacheServer(engine, deploy.scheduler_config(spec.config))

    async def go():
        await server.start()
        b0 = server.scheduler.batches_served
        n0 = len(tracer.traces()) if tracer else 0
        rec, start = await window.drive(
            server, flow, make_request, seconds,
            on_open=profile.start if profile else None,
            on_close=profile.stop if profile else None)
        await server.stop()
        run.batches_served = server.scheduler.batches_served - b0
        run.traces = tracer.traces()[n0:] if tracer else None
        return rec, start

    run.records, run.start = asyncio.run(go())
    run.setup_s = run.start - t_start
    if profile is not None:
        if profile.path is not None:
            raw = trace.read(profile.path)
            run.trace = {"raw": raw, "reduced": trace.reduce(raw),
                         "profile": profile}
        profile.close()
    del server, engine
    gc.collect()
    return run, corpus


def device_info(count: int) -> dict:
    import jax

    devs = jax.devices()[:count]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def reference_check(run: Run, corpus, seed: int, *, control=None
                    ) -> tuple[bool, dict, dict]:
    """Replay the program's batches through the configuration's reference
    and compare. ``control``, a scorer, puts a lower-precision reference
    in the program's place instead."""
    ref = run.spec.reference()
    config = run.config
    rec = run.records
    batches, unplaced = check.replay_batches(rec)
    embedder = ref.Embedder(int(config["dim"]))
    texts = sorted({rec.items[i].query for b in batches for i in b})
    tops = check.filler_tops(texts, embedder.embed, config, seed)
    entries = [(p.question, p.answer, p.semantic_key) for p in corpus]

    def replay(**kw):
        cache = ref.ExactCache(config, entries, embedder.embed, tops, **kw)
        return check.reference_rows(rec, batches, cache)

    owed, orphans = replay()
    if control is None:
        served = check.served_rows(rec)
    else:
        rows, _ = replay(scorer=control)
        served = {i: check.Served(r.hit, r.score, r.answer)
                  for i, r in rows.items()}
    values, seen = check.compare(served, owed, float(config["threshold"]),
                                 float(config["limits"]["score_gap"]))
    values["unanswered"] = sum(r is None for r in rec.resp)
    values["unplaced"] = len(unplaced) + orphans
    ok, checks = check.checks(values, config["limits"])
    seen.update(batches=len(batches), requests=len(rec))
    return ok, checks, seen


def _breakdown(run: Run) -> dict:
    """The ten device ops that took most time, and idle time by what the
    host was doing in it (engine stage of the batch in service, else no
    batch in service); each instant is counted once."""
    red = run.trace["reduced"]
    ops = sorted(red.get("op_s", {}).items(), key=lambda kv: -kv[1])[:10]
    raw, prof = run.trace["raw"], run.trace["profile"]
    stages = ("embed", "device_step", "near_synthesis", "backend_call",
              "insert", "respond")
    host = []
    for tr in run.traces or ():
        # a coalesced waiter's spans lie over the batches it waited
        # through; its leader's batch stages already cover that time
        if any(s.name == "coalesce_attach" for s in tr.spans):
            continue
        host.extend((s.t0, s.t1, s.name) for s in tr.spans
                    if s.name in stages)
    pieces = trace.tile(sorted(set(host)))
    starts = [p[0] for p in pieces]
    idle: dict[str, float] = {}
    window_s = red["window_s"]
    for dev in raw["devices"].values():
        busy = [(a, b) for _, a, b in dev["ops"]]
        for a, b in trace.gaps(busy, 0.0, window_s):
            a = prof.to_perf_counter(raw, a)
            b = prof.to_perf_counter(raw, b)
            covered = 0.0
            j = max(0, bisect.bisect_right(starts, a) - 1)
            while j < len(pieces) and pieces[j][0] < b:
                lo, hi = max(a, pieces[j][0]), min(b, pieces[j][1])
                if hi > lo:
                    idle[pieces[j][2]] = idle.get(pieces[j][2], 0.0) + hi - lo
                    covered += hi - lo
                j += 1
            rest = (b - a) - covered
            idle["no batch in service"] = idle.get(
                "no batch in service", 0.0) + rest
    n = max(1, len(raw["devices"]))
    top_idle = sorted(((k, v / n) for k, v in idle.items()),
                      key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in top_idle]}


def run_cell(spec, seed: int, seconds: float, traced: bool, *,
             t_start: float, engine_hook=None, log=None) -> dict:
    """One run; returns the result line's object."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    run, corpus = serve_window(spec, seed, seconds, traced=traced,
                               t_start=t_start, engine_hook=engine_hook)
    run.device = device_info(spec.chips)
    t_check = time.perf_counter()
    ok, checks, seen = reference_check(run, corpus, seed)
    log(f"reference check {time.perf_counter() - t_check:.1f}s: {seen}")
    rec = run.records
    metrics = {}
    line = {"correct": ok, "attempted": len(rec),
            "failed": sum(r is None for r in rec.resp),
            "metrics": metrics, "device": dict(run.device)}
    if traced:
        if run.trace is not None and run.trace["reduced"]["devices"]:
            run.peaks = roofline.peaks(run.device["kind"],
                                       cellmod.BENCH / "peaks.json")
            red = run.trace["reduced"]
            line["device"]["busy_s"] = red["busy_s"]
            line["device"]["window_s"] = red["window_s"]
            line["breakdown"] = _breakdown(run)
        for m in spec.per_layer:
            v = spec.reader(m, per_layer=True).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in spec.end_to_end:
            v = spec.reader(m, per_layer=False).value(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if rec.due and rec.due[0] is not None:
        late = [rec.t0[i] - rec.due[i] for i in range(len(rec))]
        log(f"generator lateness ms: median "
            f"{statistics.median(late) * 1e3:.3f} max {max(late) * 1e3:.3f}")
    log(f"batches served in window: {run.batches_served}")
    errors = [e for e in rec.error if e]
    if errors:
        log(f"{len(errors)} requests failed; the first: {errors[0]}")
    line["checks"] = checks
    return line
