"""Tail-latency + coalescing benchmark for the async serving subsystem.

Six experiments on the simulated backend (DESIGN.md §12.5, §13.5, §16.6,
§17.6):

  1. **parity** — the async scheduler must reproduce the sync engine's
     results on an identical workload: same per-request hit/miss
     decisions, byte-identical answers, same hit rate. Driven in lockstep
     waves of ``max_batch`` so both paths see the same batch partitioning.
  2. **coalescing** — a duplicate-burst workload under open-loop Poisson
     arrivals, coalescing on vs off: reports backend calls, the reduction
     ratio, and coalesced-call counts.
  3. **tail latency** — open-loop Poisson at a configurable rate against a
     *blocking* backend (real sleeps): sustained QPS and p50/p95/p99 per
     path (hit / miss / coalesced).
  4. **tenancy** — a 3-tenant Zipf-skewed workload through a partitioned
     cache with DRR admission: cross-tenant isolation (an answer cached by
     one tenant must miss for another even for the byte-identical query),
     per-tenant accounting consistency, and per-tenant hit rates.
  5. **multi-turn** — record/replay conversations through the async
     scheduler with context fusion on vs off: replayed follow-up turns
     (globally unique raw texts) must convert from 0% hits stateless to
     hits under fusion, while context-hit precision clears the same >97%
     bar as stateless serving and the session store stays bounded.
  6. **near-hit** — the generative band (§17) against an exact-reuse-only
     baseline on the same workload: judged near-hits must convert, cut
     backend calls strictly beyond exact reuse at >0.9 judge precision,
     and leave every exact-hit row byte-identical.
  8. **sharded** — the mesh-backed engine (DESIGN.md §19): a large slab
     (≥1M slots; 64K in smoke) sharded over a forced-8-device CPU
     topology, driven by skewed multi-tenant Zipf traffic through the
     async scheduler. Asserts per-request decision parity against a
     single-shard engine on identical traffic, a cross-shard cache hit
     (warmed entries round-robin across shards and every query row finds
     them), and a hit-path p99 bound. Runs in a re-exec'd subprocess —
     the parent process has already initialized its single-device JAX.
  9. **resilience** — deterministic chaos (DESIGN.md §20.7): the same
     workload through the same seeded ``FaultSchedule`` (hard-error,
     brownout and latency-spike windows keyed by backend call index)
     with the resilience layer on vs off. Asserts zero stranded futures,
     availability strictly above the no-resilience baseline (degraded
     cache serving is doing real work), that the circuit breaker both
     trips and recovers, that deadline-expired rows fail fast without a
     backend call, a hit-path p99 bound on the unaffected traffic, and
     that the retry/breaker/degraded Prometheus families are served.

Output: ``name,value`` CSV rows, then a JSON metrics summary.

``--smoke`` shrinks sizes for CI and turns the parity/coalescing/tenancy
expectations into hard assertions (non-zero exit on violation), so a
scheduler or isolation regression fails the build.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys

from repro.core.types import CacheConfig
from repro.data.qa_dataset import build_corpus
from repro.context import DecayMeanFusion
from repro.generative import BandPolicy, TemplateSplice
from repro.serving import (AsyncCacheServer, CachedEngine, CircuitBreaker,
                           FaultSchedule, FaultWindow, FaultyBackend,
                           Request, ResilienceConfig, Response, RetryPolicy,
                           SchedulerConfig, ServingMetrics,
                           SimulatedLLMBackend, availability,
                           build_multi_tenant_workload,
                           build_multi_turn_workload, build_workload,
                           run_open_loop, run_sessions, run_waves)
from repro.tenancy import TenantRegistry, TenantSpec


def _emit(name: str, value) -> None:
    print(f"{name},{value}")
    sys.stdout.flush()


def make_engine(pairs, *, batch_size: int, latency_s: float = 0.0,
                block: bool = False, warm: bool = True,
                registry=None, fusion=None, judge=None,
                max_sessions: int = 4096, synthesizer=None,
                policy=None, backend=None, resilience=None) -> CachedEngine:
    by_id = {p.qa_id: p for p in pairs}

    def default_judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    if backend is None:
        backend = SimulatedLLMBackend(pairs, latency_per_call_s=latency_s,
                                      block=block)
    per_tenant = max(4096, 8 * len(pairs))
    cfg = CacheConfig(dim=384,
                      capacity=per_tenant * (len(registry) if registry
                                             else 1),
                      value_len=48, ttl=None, threshold=0.8)
    eng = CachedEngine(cfg, backend, judge=judge or default_judge,
                       batch_size=batch_size, registry=registry,
                       fusion=fusion, max_sessions=max_sessions,
                       synthesizer=synthesizer, policy=policy,
                       resilience=resilience)
    if warm:
        if registry is None:
            eng.warm(pairs)
        else:
            for name in registry.names:
                eng.warm(pairs, tenant=name)
    return eng


def bench_parity(pairs, workload, *, batch: int) -> dict:
    """Sync engine vs async scheduler on the same workload/partitioning."""
    sync_eng = make_engine(pairs, batch_size=batch)
    sync_resp = sync_eng.process(workload)

    async_eng = make_engine(pairs, batch_size=batch)

    async def drive():
        sched = SchedulerConfig(max_batch=batch, max_wait_ms=50.0,
                                coalesce=False)
        async with AsyncCacheServer(async_eng, sched) as server:
            return await run_waves(server.submit_request, workload,
                                   wave=batch)
    async_resp = asyncio.run(drive()).responses

    decisions_match = all(a.cached == b.cached
                          for a, b in zip(sync_resp, async_resp))
    answers_match = all(a.answer == b.answer
                        for a, b in zip(sync_resp, async_resp))
    sync_hits = sum(r.cached for r in sync_resp)
    async_hits = sum(r.cached for r in async_resp)
    return {
        "sync_hit_rate": sync_hits / len(workload),
        "async_hit_rate": async_hits / len(workload),
        "decisions_match": decisions_match,
        "answers_match": answers_match,
    }


def bench_coalescing(pairs, workload, *, batch: int, rate_qps: float) -> dict:
    """Duplicate-burst workload, coalescing on vs off."""
    out = {}
    for coalesce in (False, True):
        eng = make_engine(pairs, batch_size=batch)

        async def drive():
            sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0,
                                    coalesce=coalesce)
            async with AsyncCacheServer(eng, sched) as server:
                return await run_open_loop(server.submit_request, workload,
                                           rate_qps=rate_qps, seed=7)
        asyncio.run(drive())
        tag = "coalesce_on" if coalesce else "coalesce_off"
        out[f"{tag}_backend_calls"] = eng.backend.calls
        out[f"{tag}_coalesced"] = eng.metrics.coalesced_calls
    off, on = out["coalesce_off_backend_calls"], \
        out["coalesce_on_backend_calls"]
    out["backend_call_reduction_pct"] = round(100.0 * (1 - on / max(off, 1)),
                                              2)
    return out


def bench_tail_latency(pairs, workload, *, batch: int, rate_qps: float,
                       llm_latency_s: float) -> dict:
    """Open-loop Poisson against a blocking backend: real wall-clock tails."""
    eng = make_engine(pairs, batch_size=batch, latency_s=llm_latency_s,
                      block=True)
    # compile the fused serve path before the clock starts — otherwise the
    # first micro-batch's jit trace (~1s) queues behind itself and floods
    # every percentile with cold-start time — then zero the bookkeeping so
    # the warmup row doesn't appear in the reported samples/counters
    eng.serve_batch([Request(query="serve-path warmup")])
    eng.metrics = ServingMetrics()

    async def drive():
        sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0)
        async with AsyncCacheServer(eng, sched) as server:
            return await run_open_loop(server.submit_request, workload,
                                       rate_qps=rate_qps, seed=11)
    res = asyncio.run(drive())
    summary = eng.metrics.summary()
    return {
        "achieved_qps": round(res.achieved_qps, 1),
        "wall_s": round(res.wall_s, 3),
        "percentiles": summary["latency_percentiles"],
        "coalesced_calls": summary["coalesced_calls"],
    }


def bench_tenancy(pairs, *, batch: int, n_req: int, rate_qps: float) -> dict:
    """3-tenant Zipf-skewed workload through a partitioned cache (§13.5)."""
    registry = TenantRegistry((
        TenantSpec("free", share=1.0, weight=1.0),
        TenantSpec("pro", share=2.0, weight=2.0),
        TenantSpec("enterprise", share=2.0, weight=4.0),
    ))
    eng = make_engine(pairs, batch_size=batch, registry=registry)

    # isolation probe: a novel answer cached under 'free' must be invisible
    # to 'pro' even though the query bytes (hence the embedding) are equal
    probe = "what is the meaning of the tenant isolation probe"
    eng.process([Request(query=probe, tenant="free")])       # miss + insert
    again = eng.process([Request(query=probe, tenant="free")])[0]
    cross = eng.process([Request(query=probe, tenant="pro")])[0]
    isolation_ok = bool(again.cached) and not cross.cached

    workload = build_multi_tenant_workload(
        pairs, n_req, tenants=list(registry.names), skew=1.2,
        burst_prob=0.2, burst_size=4, seed=13)

    async def drive():
        sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0,
                                tenant_weights=registry.weights(),
                                max_queue_per_tenant=max(batch, n_req // 4))
        async with AsyncCacheServer(eng, sched) as server:
            return await run_open_loop(server.submit_request, workload,
                                       rate_qps=rate_qps, seed=17)
    res = asyncio.run(drive())
    served_all = (len(res.responses) == n_req
                  and all(r is not None and r.answer for r in res.responses))

    dev = eng.tenant_stats()
    summary = eng.metrics.summary()
    host = summary["tenants"]
    # accounting: device-side per-tenant lookups must sum to the global
    # counter, and host-side per-tenant lookups to the query count
    accounting_ok = (
        sum(v["lookups"] for v in dev.values()) == int(eng.stats.lookups)
        and sum(v["hits"] for v in dev.values()) == int(eng.stats.hits)
        and sum(v["lookups"] for v in host.values()) == summary["queries"])
    out = {
        "isolation_ok": isolation_ok,
        "served_all": served_all,
        "accounting_ok": accounting_ok,
    }
    for name in registry.names:
        out[f"{name}_lookups"] = dev[name]["lookups"]
        out[f"{name}_hit_rate"] = round(
            dev[name]["hits"] / max(dev[name]["lookups"], 1), 4)
    return out


def bench_multi_turn(pairs, *, batch: int, n_groups: int,
                     turns: int) -> dict:
    """Record/replay conversations through the async scheduler, context
    fusion on vs off (DESIGN.md §16.6)."""
    convs = build_multi_turn_workload(pairs, n_groups, turns=turns, seed=23)
    rec, rep = convs[:n_groups], convs[n_groups:]
    key_by_sid = {p.qa_id: p.semantic_key for p in pairs}
    for conv in convs:
        for r in conv:
            key_by_sid.setdefault(r.source_id, r.semantic_key)

    def judge(req, sid):
        return key_by_sid.get(sid, "") == req.semantic_key

    out = {}
    for tag, fusion in (("fusion_on", DecayMeanFusion(window=4)),
                        ("fusion_off", None)):
        eng = make_engine(pairs, batch_size=batch, fusion=fusion,
                          judge=judge)

        async def drive():
            sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0)
            async with AsyncCacheServer(eng, sched) as server:
                # replay only after every recording is fully served — a
                # replay's hits ARE the recording's inserts
                await run_sessions(server.submit_request, rec,
                                   concurrency=max(2, batch // 2))
                return await run_sessions(server.submit_request, rep,
                                          concurrency=max(2, batch // 2))
        res = asyncio.run(drive())
        s = eng.metrics.summary()
        m = s["categories"]["ctx/followup_repeat"]
        out[f"{tag}_followup_repeat_hit_rate"] = m["hit_rate"]
        out[f"{tag}_followup_repeat_positive_rate"] = m["positive_rate"]
        out[f"{tag}_backend_calls"] = eng.backend.calls
        if fusion is not None:
            replay_context = sum(
                r.context for r in res.responses if r is not None)
            c = s["context"]["context"]
            out["context_hit_rate"] = c["hit_rate"]
            out["context_positive_rate"] = c["positive_rate"]
            out["replay_context_rows"] = replay_context
            out["session_store"] = eng.sessions.stats()
            out["sessions_bounded"] = (
                len(eng.sessions) <= eng.sessions.max_sessions)
    return out


def bench_near_hit(pairs, workload, *, batch: int) -> dict:
    """Generative near-hit band vs exact-reuse-only baseline (§17).

    Same workload through (a) a plain exact-reuse engine and (b) a banded
    engine with a TemplateSplice synthesizer. The band engine must convert
    judged band rows into served near-hits, cut backend calls *strictly
    below* the exact-reuse baseline, keep judge-verified near precision
    high, and — because bands only touch rows the exact path would have
    missed — serve byte-identical answers on every row the baseline hit.
    """
    base = make_engine(pairs, batch_size=batch)
    base_resp = base.process(workload)

    banded = make_engine(pairs, batch_size=batch,
                         synthesizer=TemplateSplice(rival_margin=0.12),
                         policy=BandPolicy(tau_lo=0.75, tau_hi=0.8))
    band_resp = banded.process(workload)

    near = banded.metrics.near
    exact_rows_identical = all(
        b.cached and a.answer == b.answer and a.score == b.score
        for a, b in zip(base_resp, band_resp) if a.cached)
    return {
        "baseline_backend_calls": base.backend.calls,
        "band_backend_calls": banded.backend.calls,
        "calls_saved_beyond_exact": base.backend.calls - banded.backend.calls,
        "band_lookups": near.band,
        "near_hits_served": near.served,
        "near_conversion_rate": round(near.conversion_rate, 4),
        "near_precision": round(near.precision, 4),
        "synthesis_cost_usd": round(near.synthesis_cost_usd, 6),
        "exact_rows_identical": exact_rows_identical,
        "band_lo_final": round(float(banded.policy_state[0]), 4),
    }


def bench_observability(pairs, *, batch: int, n_req: int, rate_qps: float,
                        llm_latency_s: float) -> dict:
    """Observability plane end to end (DESIGN.md §18.6).

    (a) a traced run (sample rate 1.0) through the async scheduler on a
    2-tenant engine with a blocking backend: per-stage p50/p95 rows, the
    span-sum-vs-e2e invariant (the stage decomposition must reconstruct
    the measured end-to-end latency within 10% at p50/p95), and a live
    ``/metrics`` scrape validated against ``REQUIRED_FAMILIES`` plus
    per-tenant labels; (b) traced-vs-untraced sync throughput (best-of-3
    walls) bounding the tracing overhead; (c) the tracing-off path must
    start zero traces — the hot path allocates nothing.
    """
    import time as _time

    from repro.obs import EventLog, REQUIRED_FAMILIES, TraceConfig, Tracer
    from repro.serving.metrics import percentiles

    registry = TenantRegistry.uniform(["acme", "globex"])
    eng = make_engine(pairs, batch_size=batch, latency_s=llm_latency_s,
                      block=True, registry=registry)
    eng.events = EventLog(capacity=512)
    # compile before the clock starts, then zero the bookkeeping so the
    # warmup row doesn't appear in the reported traces/samples
    eng.serve_batch([Request(query="obs warmup", tenant="acme")])
    eng.metrics = ServingMetrics()
    eng.tracer = Tracer(TraceConfig(sample_rate=1.0, head=0,
                                    max_traces=8192))
    workload = build_multi_tenant_workload(
        pairs, n_req, tenants=list(registry.names), seed=31)
    scrape = {}

    async def drive():
        sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0,
                                tenant_weights=registry.weights())
        async with AsyncCacheServer(eng, sched) as server:
            try:
                port = await server.serve_metrics()
            except OSError:
                port = None               # sandboxed CI: no sockets
            res = await run_open_loop(server.submit_request, workload,
                                      rate_qps=rate_qps, seed=37)
            if port is not None:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(b"GET /metrics HTTP/1.0\r\n\r\n")
                await writer.drain()
                raw = (await reader.read()).decode()
                writer.close()
                scrape["status"] = raw.split("\r\n", 1)[0]
                scrape["body"] = raw.split("\r\n\r\n", 1)[1]
            return res

    asyncio.run(drive())
    traces = eng.tracer.traces()
    e2e = [t.e2e_s for t in traces if t.e2e_s]
    sums = [t.span_sum_s for t in traces if t.e2e_s]
    p_e2e, p_sum = percentiles(e2e), percentiles(sums)
    out = {
        "traces_retained": len(traces),
        "span_sum_p50_ratio": round(
            p_sum["p50_s"] / max(p_e2e["p50_s"], 1e-9), 4),
        "span_sum_p95_ratio": round(
            p_sum["p95_s"] / max(p_e2e["p95_s"], 1e-9), 4),
        "events_logged": len(eng.events),
        "events_bounded": len(eng.events) <= eng.events.capacity,
    }
    for stage, row in eng.tracer.stage_decomposition().items():
        out[f"stage_{stage}_p50_s"] = row["p50_s"]
        out[f"stage_{stage}_p95_s"] = row["p95_s"]
    if scrape:
        body = scrape["body"]
        missing = [f for f in REQUIRED_FAMILIES
                   if f"# TYPE {f} " not in body]
        out["scrape_ok"] = (scrape["status"].endswith("200 OK")
                            and not missing
                            and 'tenant="acme"' in body
                            and 'tenant="globex"' in body)
    else:
        out["scrape_ok"] = None           # sockets unavailable: skipped

    # (b) tracing overhead: identical sync workloads, traced vs off —
    # best-of-3 walls so timer jitter doesn't drown the comparison
    sync_wl = build_workload(pairs, max(n_req, 4 * batch), burst_prob=0.0,
                             seed=41)
    walls = {}
    for tag, tracer in (("off", Tracer(TraceConfig.off())),
                        ("on", Tracer(TraceConfig(sample_rate=1.0,
                                                  max_traces=8192)))):
        e = make_engine(pairs, batch_size=batch)
        e.tracer = tracer
        e.process(sync_wl[:batch])        # compile before the clock
        best = float("inf")
        for _ in range(3):
            t0 = _time.perf_counter()
            e.process(sync_wl)
            best = min(best, _time.perf_counter() - t0)
        walls[tag] = best
    out["untraced_wall_s"] = round(walls["off"], 4)
    out["traced_wall_s"] = round(walls["on"], 4)
    out["trace_overhead_pct"] = round(
        100.0 * (walls["on"] / walls["off"] - 1.0), 2)

    # (c) tracing off = zero per-request tracing work
    off_eng = make_engine(pairs, batch_size=batch, warm=False)
    off_eng.process(sync_wl[:batch])
    out["off_path_traces_finished"] = off_eng.tracer.finished
    return out


def bench_resilience(pairs, *, batch: int, n_req: int) -> dict:
    """Stage 9: deterministic chaos serving (DESIGN.md §20.7).

    The SAME workload runs twice through the async scheduler against two
    fresh ``FaultyBackend`` wrappers sharing one seeded ``FaultSchedule``
    (a hard-error window, a 50% brownout, a latency spike — all keyed by
    backend call index, so lockstep waves make both runs bit-replayable):

      * **off** — a plain engine: per-row containment only (§20.2). Every
        miss row whose backend call falls in a fault window resolves as a
        ``BackendError``; hits in the same batch still serve.
      * **on**  — retries with deterministic backoff (no real sleeps), a
        zero-cooldown circuit breaker (trips during the error window,
        probes every batch, recovers as soon as the window passes), and
        degraded cache serving over a ``BandPolicy.degraded_lo`` floor.

    Availability (fraction of slots answered with an error-free Response)
    must be *strictly* higher with the layer on; the deadline probe at the
    end asserts an already-expired budget never reaches the backend.
    """
    from repro.obs import REQUIRED_FAMILIES
    from repro.obs.export import MetricsExporter

    schedule = FaultSchedule(windows=(
        FaultWindow("error", 2, 7),
        FaultWindow("brownout", 8, 11, error_rate=0.5),
        FaultWindow("latency_spike", 11, 13, extra_latency_s=0.02),
    ), seed=5)
    # high paraphrase share: failed miss rows usually have a cached
    # neighbour above the degraded floor — the regime degraded serving
    # exists for (a purely-novel workload has nothing to serve from)
    workload = build_workload(pairs, n_req, paraphrase_ratio=0.9,
                              burst_prob=0.0, seed=43)
    policy = BandPolicy(tau_lo=0.70, tau_hi=0.80, degraded_lo=0.60)

    def run(resilient: bool):
        backend = FaultyBackend(SimulatedLLMBackend(pairs), schedule)
        res = None
        if resilient:
            res = ResilienceConfig(
                retry=RetryPolicy(max_attempts=3, base_backoff_s=0.001,
                                  max_backoff_s=0.002, seed=3),
                breaker=CircuitBreaker(failure_threshold=3, window=8,
                                       cooldown_s=0.0),
                sleep=lambda s: None)
        eng = make_engine(pairs, batch_size=batch, backend=backend,
                          policy=policy, resilience=res)
        # compile before the clock starts (consumes fault index 0 in both
        # runs alike), then zero the bookkeeping
        eng.serve_batch([Request(query="resilience warmup")])
        eng.metrics = ServingMetrics()

        async def drive():
            sched = SchedulerConfig(max_batch=batch, max_wait_ms=50.0,
                                    coalesce=False)
            async with AsyncCacheServer(eng, sched) as server:
                return await run_waves(server.submit_request, workload,
                                       wave=batch, return_exceptions=True)
        return eng, backend, res, asyncio.run(drive())

    eng_off, be_off, _, lr_off = run(False)
    eng_on, be_on, res_on, lr_on = run(True)

    rm = eng_on.metrics.resilience
    br = res_on.breaker
    on_slots, off_slots = lr_on.responses, lr_off.responses
    out = {
        "availability_on": round(availability(on_slots), 4),
        "availability_off": round(availability(off_slots), 4),
        "no_stranded": (
            len(on_slots) == n_req and len(off_slots) == n_req
            and all(isinstance(r, (Response, Exception)) for r in on_slots)
            and all(isinstance(r, (Response, Exception))
                    for r in off_slots)),
        "faults_injected_on": be_on.faults_injected,
        "faults_injected_off": be_off.faults_injected,
        "retries": rm.retries,
        "retry_successes": rm.retry_successes,
        "backend_failures": rm.backend_failures,
        "degraded_served": rm.degraded_served,
        "degraded_failed": rm.degraded_failed,
        "breaker_trips": br.trips,
        "breaker_recoveries": br.recoveries,
        "breaker_short_circuits": br.short_circuits,
        "breaker_state_final": br.state,
    }
    pct = eng_on.metrics.summary()["latency_percentiles"]
    out["hit_p99_s"] = pct.get("hit", {}).get("p99_s", 0.0)

    # deadline probe: an already-expired budget on a guaranteed-miss row
    # must fail fast — degraded or error, but never a backend call
    calls_before = be_on.calls_started
    expired = eng_on.process([Request(
        query="what does the deadline probe row with a spent budget do",
        deadline_ms=0.0)])[0]
    out["deadline_fast_fail"] = (
        be_on.calls_started == calls_before
        and bool(expired.error or expired.degraded)
        and rm.deadline_exhausted >= 1)

    body = MetricsExporter(eng_on).render()
    out["families_ok"] = all(f"# TYPE {f} " in body
                             for f in REQUIRED_FAMILIES)
    return out


def _sharded_child(args) -> dict:
    """Body of the sharded stage — runs in the re-exec'd 8-device child."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    smoke = args.smoke
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("data",))
    corpus = args.corpus or (40 if smoke else 400)
    batch = args.batch or (16 if smoke else 64)
    n_req = args.requests or (160 if smoke else 1000)
    rate = args.rate_qps or (400.0 if smoke else 800.0)
    capacity = args.capacity or ((1 << 16) if smoke else (1 << 21))
    pairs = build_corpus(corpus, seed=0)
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    registry = TenantRegistry((
        TenantSpec("free", weight=1.0), TenantSpec("pro", weight=2.0),
        TenantSpec("enterprise", weight=4.0), TenantSpec("batch",
                                                         weight=1.0)))
    # reduced dim keeps the full-capacity scoring GEMM tractable on the
    # forced-CPU topology; the slab's *entry count* is the scaling axis
    cfg = CacheConfig(dim=64, capacity=capacity, value_len=48, ttl=None,
                      threshold=0.8)

    def mk(mesh_, *, block=False, latency=0.0):
        eng = CachedEngine(
            cfg, SimulatedLLMBackend(pairs, latency_per_call_s=latency,
                                     block=block),
            judge=judge, batch_size=batch, registry=registry, mesh=mesh_)
        for name in registry.names:
            eng.warm(pairs, tenant=name)
        return eng

    out = {"num_shards": 4, "capacity": capacity,
           "local_capacity": capacity // 4}
    workload = build_multi_tenant_workload(
        pairs, n_req, tenants=list(registry.names), skew=1.2,
        burst_prob=0.2, burst_size=4, seed=13)

    # (a) per-request decision parity vs a single-shard engine on
    # identical traffic with identical batch partitioning
    e_sh = mk(mesh)
    e_ref = mk(None)
    r_sh = e_sh.process(workload)
    r_ref = e_ref.process(workload)
    out["platform"] = jax.default_backend()
    out["parity_decisions_match"] = all(
        a.cached == b.cached for a, b in zip(r_ref, r_sh))
    out["parity_answers_match"] = all(
        a.answer == b.answer for a, b in zip(r_ref, r_sh))
    out["hit_rate"] = round(sum(r.cached for r in r_sh) / len(r_sh), 4)
    out["entries"] = int(np.asarray(e_sh.runtime.state.valid).sum())
    out["entries_per_shard"] = np.asarray(
        e_sh.runtime.state.valid).reshape(4, -1).sum(axis=1).tolist()

    # (b) cross-shard hits: warmed entries were routed round-robin, so the
    # matched slots of known-warm queries must span >1 shard owner
    L = e_sh.cache.local_capacity
    probe = pairs[:min(len(pairs), 64)]
    emb = jnp.asarray(e_sh.embedder.embed_batch(
        [p.question for p in probe]))
    tid = jnp.zeros((len(probe),), dtype=jnp.int32)
    res, _ = e_sh.cache.lookup(e_sh.runtime, emb, e_sh._now,
                               update_counters=False, tenant_id=tid)
    hit = np.asarray(res.hit)
    owners = sorted(set(
        (np.asarray(res.index)[hit] // L).tolist()))
    out["probe_hits"] = int(hit.sum())
    out["cross_shard_hit_owners"] = owners
    out["cross_shard_hit"] = len(owners) >= 2

    # (c) the async scheduler drives the sharded step directly: open-loop
    # Poisson Zipf traffic against a blocking backend, DRR admission
    e_async = mk(mesh, block=True, latency=0.01 if smoke else 0.05)
    e_async.serve_batch([Request(query="sharded warmup",
                                 tenant=registry.names[0])])
    e_async.metrics = ServingMetrics()

    async def drive():
        sched = SchedulerConfig(max_batch=batch, max_wait_ms=2.0,
                                tenant_weights=registry.weights(),
                                max_queue_per_tenant=max(batch, n_req // 4))
        async with AsyncCacheServer(e_async, sched) as server:
            return await run_open_loop(server.submit_request, workload,
                                       rate_qps=rate, seed=17)
    res2 = asyncio.run(drive())
    out["served_all"] = (len(res2.responses) == len(workload)
                         and all(r is not None and r.answer
                                 for r in res2.responses))
    out["achieved_qps"] = round(res2.achieved_qps, 1)
    summary = e_async.metrics.summary()
    for path, pct in summary["latency_percentiles"].items():
        for key in ("p50_s", "p95_s", "p99_s"):
            out[f"{path}_{key}"] = pct[key]
    return out


def bench_sharded(args) -> dict:
    """Stage 8 parent half: re-exec this script on a forced multi-device
    CPU topology (the parent's JAX already holds its device) and collect
    the child's JSON summary, whose ``platform`` says ``cpu``."""
    import os
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # forced host devices: the child must not contend for an accelerator
    # the parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--sharded-child"]
    if args.smoke:
        cmd.append("--smoke")
    for flag, val in (("--corpus", args.corpus),
                      ("--requests", args.requests),
                      ("--batch", args.batch),
                      ("--rate-qps", args.rate_qps),
                      ("--capacity", args.capacity)):
        if val is not None:
            cmd += [flag, str(val)]
    r = subprocess.run(cmd, capture_output=True, text=True, env=env,
                       timeout=3600)
    if r.returncode != 0:
        return {"child_ok": False,
                "stderr_tail": r.stderr[-2000:] or r.stdout[-2000:]}
    for line in r.stdout.splitlines():
        if line.startswith("SHARDED-JSON "):
            out = json.loads(line[len("SHARDED-JSON "):])
            out["child_ok"] = True
            return out
    return {"child_ok": False, "stderr_tail": "no SHARDED-JSON line"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny sizes + hard assertions")
    ap.add_argument("--corpus", type=int, default=None,
                    help="QA pairs per category")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--rate-qps", type=float, default=None)
    ap.add_argument("--capacity", type=int, default=None,
                    help="sharded-stage slab slots (default 1<<21, "
                         "1<<16 in smoke)")
    ap.add_argument("--sharded-child", action="store_true",
                    help=argparse.SUPPRESS)   # internal re-exec entry
    args = ap.parse_args(argv)

    if args.sharded_child:
        print("SHARDED-JSON " + json.dumps(_sharded_child(args)))
        return 0

    corpus = args.corpus or (60 if args.smoke else 500)
    n_req = args.requests or (192 if args.smoke else 2000)
    batch = args.batch or (16 if args.smoke else 64)
    rate = args.rate_qps or (400.0 if args.smoke else 800.0)

    pairs = build_corpus(corpus, seed=0)

    # 1. parity: paper mixture, no duplicate bursts
    plain = build_workload(pairs, n_req, burst_prob=0.0, seed=1)
    parity = bench_parity(pairs, plain, batch=batch)
    for k, v in parity.items():
        _emit(f"serve/parity_{k}", v)

    # 2. coalescing: concurrent-duplicate workload
    bursty = build_workload(pairs, n_req, burst_prob=0.35, burst_size=8,
                            seed=2)
    coal = bench_coalescing(pairs, bursty, batch=batch, rate_qps=rate)
    for k, v in coal.items():
        _emit(f"serve/{k}", v)

    # 3. tail latency under Poisson load with a real-sleeping backend
    tail_req = bursty[:min(len(bursty), 96 if args.smoke else 1000)]
    tail = bench_tail_latency(pairs, tail_req, batch=batch, rate_qps=rate,
                              llm_latency_s=0.01 if args.smoke else 0.05)
    _emit("serve/achieved_qps", tail["achieved_qps"])
    for path, pct in tail["percentiles"].items():
        for key in ("p50_s", "p95_s", "p99_s"):
            _emit(f"serve/{path}_{key}", pct[key])
    print(json.dumps(tail, indent=1))

    # 4. multi-tenant: 3 tenants, skewed traffic, partitioned cache + DRR
    ten = bench_tenancy(pairs, batch=batch,
                        n_req=min(n_req, 192 if args.smoke else 1000),
                        rate_qps=rate)
    for k, v in ten.items():
        _emit(f"serve/tenancy_{k}", v)

    # 5. multi-turn sessions: record/replay, fusion on vs off
    ctx = bench_multi_turn(pairs, batch=batch,
                           n_groups=8 if args.smoke else 10, turns=3)
    for k, v in ctx.items():
        _emit(f"serve/context_{k}", v)

    # 6. generative near-hit band: judged synthesis vs exact-reuse baseline
    near_wl = build_workload(pairs, min(n_req, 256 if args.smoke else 1000),
                             paraphrase_ratio=0.8, burst_prob=0.0, seed=29)
    nh = bench_near_hit(pairs, near_wl, batch=batch)
    for k, v in nh.items():
        _emit(f"serve/near_{k}", v)

    # 7. observability: stage decomposition, span-sum invariant, tracing
    #    overhead, /metrics scrape (DESIGN.md §18.6)
    obs = bench_observability(pairs, batch=batch,
                              n_req=min(n_req, 96 if args.smoke else 500),
                              rate_qps=rate,
                              llm_latency_s=0.01 if args.smoke else 0.05)
    for k, v in obs.items():
        _emit(f"serve/obs_{k}", v)

    # 8. sharded: large slab on a forced-8-device mesh through the async
    #    scheduler (DESIGN.md §19.6) — subprocess re-exec
    shard = bench_sharded(args)
    for k, v in shard.items():
        _emit(f"shard/{k}", v)

    # 9. resilience: deterministic chaos — fault windows, deadline-budgeted
    #    retries, circuit breaker, degraded cache serving (DESIGN.md §20.7)
    fault = bench_resilience(pairs, batch=batch,
                             n_req=min(12 * batch, n_req))
    for k, v in fault.items():
        _emit(f"serve/fault_{k}", v)

    ok = True
    if not parity["decisions_match"] or not parity["answers_match"]:
        print("FAIL: async scheduler diverged from sync engine", file=sys.stderr)
        ok = False
    if parity["sync_hit_rate"] != parity["async_hit_rate"]:
        print("FAIL: hit-rate parity broken", file=sys.stderr)
        ok = False
    if coal["coalesce_on_backend_calls"] >= coal["coalesce_off_backend_calls"]:
        print("FAIL: coalescing did not reduce backend calls", file=sys.stderr)
        ok = False
    if not ten["isolation_ok"]:
        print("FAIL: cross-tenant cache leak", file=sys.stderr)
        ok = False
    if not (ten["served_all"] and ten["accounting_ok"]):
        print("FAIL: tenancy serving/accounting broken", file=sys.stderr)
        ok = False
    # multi-turn expectations are hard requirements (§16.6): fused replays
    # must convert, stateless replays must not hit at all, and context-hit
    # precision must clear the paper-grade bar
    if ctx["fusion_on_followup_repeat_hit_rate"] < 0.5:
        print("FAIL: fused follow-up replays did not convert to hits",
              file=sys.stderr)
        ok = False
    if ctx["fusion_off_followup_repeat_hit_rate"] != 0.0:
        print("FAIL: stateless cache hit an elliptical follow-up",
              file=sys.stderr)
        ok = False
    if ctx["context_positive_rate"] <= 0.97:
        print("FAIL: context-hit precision below the 97% bar",
              file=sys.stderr)
        ok = False
    if not ctx["sessions_bounded"]:
        print("FAIL: session store exceeded its LRU cap", file=sys.stderr)
        ok = False
    # near-hit band expectations are hard requirements (§17): the band must
    # convert, its savings must be strictly beyond exact reuse, the judge
    # must confirm the synthesized answers, and exact-path serving must be
    # byte-identical to a cache without bands
    if nh["near_hits_served"] <= 0:
        print("FAIL: near-hit band served nothing", file=sys.stderr)
        ok = False
    if nh["near_precision"] <= 0.9:
        print("FAIL: near-hit judge precision below the 0.9 bar",
              file=sys.stderr)
        ok = False
    if nh["band_backend_calls"] >= nh["baseline_backend_calls"]:
        print("FAIL: band did not cut backend calls beyond exact reuse",
              file=sys.stderr)
        ok = False
    if not nh["exact_rows_identical"]:
        print("FAIL: band engine diverged on exact-hit rows", file=sys.stderr)
        ok = False
    # observability expectations are hard requirements (§18.6): the stage
    # decomposition must reconstruct measured e2e latency within 10% at
    # p50/p95, tracing must cost <5% when on and NOTHING when off, and the
    # /metrics exposition must serve every required family with tenant
    # labels (skipped only when the sandbox forbids sockets)
    if not 0.9 <= obs["span_sum_p50_ratio"] <= 1.1:
        print("FAIL: span-sum p50 off by >10% from measured e2e",
              file=sys.stderr)
        ok = False
    if not 0.9 <= obs["span_sum_p95_ratio"] <= 1.1:
        print("FAIL: span-sum p95 off by >10% from measured e2e",
              file=sys.stderr)
        ok = False
    if obs["trace_overhead_pct"] >= 5.0:
        print("FAIL: tracing overhead above the 5% bound", file=sys.stderr)
        ok = False
    if obs["scrape_ok"] is False:
        print("FAIL: /metrics scrape missing families or tenant labels",
              file=sys.stderr)
        ok = False
    if obs["off_path_traces_finished"] != 0:
        print("FAIL: tracing-off engine still finished traces",
              file=sys.stderr)
        ok = False
    if not (obs["events_logged"] > 0 and obs["events_bounded"]):
        print("FAIL: event log empty or over capacity", file=sys.stderr)
        ok = False
    # sharded expectations are hard requirements (§19.6): the mesh engine
    # must make the SAME per-request decisions as a single-shard engine on
    # identical traffic, serve hits whose entries live on >1 shard, keep
    # the async scheduler fully served, and hold the hit-path p99 bound
    if not shard.get("child_ok"):
        print(f"FAIL: sharded child failed: {shard.get('stderr_tail')}",
              file=sys.stderr)
        ok = False
    else:
        if not (shard["parity_decisions_match"]
                and shard["parity_answers_match"]):
            print("FAIL: sharded engine diverged from single-shard engine",
                  file=sys.stderr)
            ok = False
        if not shard["cross_shard_hit"]:
            print("FAIL: no cross-shard cache hit (owners: "
                  f"{shard['cross_shard_hit_owners']})", file=sys.stderr)
            ok = False
        if not shard["served_all"]:
            print("FAIL: sharded async scheduler dropped requests",
                  file=sys.stderr)
            ok = False
        p99_bound = 0.5 if args.smoke else 1.0
        if shard.get("hit_p99_s", 0.0) >= p99_bound:
            print(f"FAIL: sharded hit-path p99 {shard.get('hit_p99_s')}s "
                  f"over the {p99_bound}s bound", file=sys.stderr)
            ok = False
    # resilience expectations are hard requirements (§20.7): every submitted
    # slot resolves (zero stranded futures even with the backend on fire),
    # degraded serving keeps availability STRICTLY above the no-resilience
    # baseline under the same fault schedule, the breaker both trips and
    # recovers (ending closed), expired deadlines never reach the backend,
    # the unaffected hit traffic keeps its tail, and the retry/breaker/
    # degraded metric families are served
    if not fault["no_stranded"]:
        print("FAIL: chaos run stranded or dropped futures", file=sys.stderr)
        ok = False
    if fault["availability_on"] <= fault["availability_off"]:
        print("FAIL: resilience layer did not improve availability "
              f"({fault['availability_on']} vs {fault['availability_off']})",
              file=sys.stderr)
        ok = False
    if not (fault["breaker_trips"] >= 1 and fault["breaker_recoveries"] >= 1
            and fault["breaker_state_final"] == "closed"):
        print("FAIL: breaker did not trip and recover "
              f"(trips={fault['breaker_trips']}, "
              f"recoveries={fault['breaker_recoveries']}, "
              f"state={fault['breaker_state_final']})", file=sys.stderr)
        ok = False
    if fault["degraded_served"] <= 0:
        print("FAIL: degraded mode served nothing during the outage",
              file=sys.stderr)
        ok = False
    fault_p99 = 0.5 if args.smoke else 1.0
    if fault["hit_p99_s"] >= fault_p99:
        print(f"FAIL: chaos hit-path p99 {fault['hit_p99_s']}s over the "
              f"{fault_p99}s bound", file=sys.stderr)
        ok = False
    if not fault["deadline_fast_fail"]:
        print("FAIL: expired deadline row reached the backend",
              file=sys.stderr)
        ok = False
    if not fault["families_ok"]:
        print("FAIL: resilience metric families missing from /metrics",
              file=sys.stderr)
        ok = False
    _emit("serve/ok", ok)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
