"""Benchmarks reproducing the paper's tables/figures (one function each).

fig2  — API-call frequency: traditional vs semantic caching (per category)
fig3  — average query response time: with cache vs without
fig4/table1 — cache hits + positive-hit accuracy per category
threshold_sweep — §5.3: cosine threshold 0.6..0.9 step 0.05
tenant_table — beyond-paper (DESIGN.md §13): per-tenant hit/miss/latency
               breakdown of a partitioned multi-tenant run
context_table — beyond-paper (DESIGN.md §16): multi-turn record/replay
                conversations with context fusion on vs off — follow-up
                hit conversion and context-hit precision

Each returns (rows, summary) where rows are CSV-able dicts; ``run.py``
prints them in the harness format.
"""
from __future__ import annotations

import time

from repro.core.types import CacheConfig
from repro.data.qa_dataset import (CATEGORIES, build_corpus,
                                   build_test_queries)
from repro.serving import (CachedEngine, Request, SimulatedLLMBackend,
                           build_multi_tenant_workload)
from repro.tenancy import TenantRegistry, TenantSpec

_PAPER_TABLE1 = {   # category -> (cache hits / 500, positive hits)
    "python_basics": (335, 310),
    "network_support": (335, 326),
    "order_shipping": (344, 331),
    "customer_shopping": (308, 298),
}


def _run_system(threshold: float = 0.8, n_per_category: int = 2000,
                n_queries_per_cat: int = 500, ttl: float | None = None,
                seed: int = 0):
    pairs = build_corpus(n_per_category, seed=seed)
    queries = build_test_queries(pairs, n_per_category=n_queries_per_cat,
                                 seed=seed + 1)
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    cfg = CacheConfig(dim=384, capacity=4 * n_per_category * 2, value_len=48,
                      ttl=ttl, threshold=threshold)
    eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), judge=judge,
                       batch_size=128)
    eng.warm(pairs)
    t0 = time.perf_counter()
    eng.process([Request(query=q.query, category=q.category,
                         source_id=q.source_id, semantic_key=q.semantic_key)
                 for q in queries])
    wall = time.perf_counter() - t0
    return eng.metrics.summary(), wall, len(queries)


def table1(full: bool = True):
    """Table 1 + Fig 4: hits and positive hits per category vs paper."""
    n = 2000 if full else 400
    nq = 500 if full else 100
    s, wall, nqueries = _run_system(n_per_category=n, n_queries_per_cat=nq)
    rows = []
    for cat in CATEGORIES:
        m = s["categories"][cat]
        paper_hits, paper_pos = _PAPER_TABLE1[cat]
        rows.append({
            "name": f"table1/{cat}",
            "us_per_call": 1e6 * wall / nqueries,
            "derived": (f"hits={m['cache_hits']}/{m['lookups']}"
                        f" hit_rate={m['hit_rate']:.3f}"
                        f" positive_rate={m['positive_rate']:.3f}"
                        f" paper_hits={paper_hits}/500"
                        f" paper_pos={paper_pos}"),
        })
    return rows, s


def fig2(summary=None):
    """API-call frequency: traditional = 100%; ours = miss fraction."""
    if summary is None:
        summary, _, _ = _run_system()
    rows = []
    for cat in CATEGORIES:
        m = summary["categories"][cat]
        rows.append({
            "name": f"fig2/api_calls/{cat}",
            "us_per_call": 0.0,
            "derived": (f"traditional=1.00 cached={m['api_call_fraction']:.3f}"
                        f" reduction={1 - m['api_call_fraction']:.3f}"),
        })
    return rows, summary


def fig3(summary=None):
    """Response time with vs without cache (LLM latency modeled, cache
    path measured on this host)."""
    if summary is None:
        summary, _, _ = _run_system()
    rows = [{
        "name": "fig3/latency",
        "us_per_call": summary["avg_latency_with_cache_s"] * 1e6,
        "derived": (f"with_cache_s={summary['avg_latency_with_cache_s']:.4f}"
                    f" without_cache_s={summary['avg_latency_without_cache_s']:.4f}"
                    f" speedup={summary['avg_latency_without_cache_s'] / max(summary['avg_latency_with_cache_s'], 1e-9):.2f}x"),
    }]
    return rows, summary


def threshold_sweep(full: bool = False):
    """§5.3: sweep 0.60..0.90 in 0.05 steps; 0.8 should be the knee."""
    n = 1000 if full else 500
    nq = 250 if full else 125
    rows = []
    best = None
    for thr in [0.60, 0.65, 0.70, 0.75, 0.80, 0.85, 0.90]:
        s, wall, nqueries = _run_system(threshold=thr, n_per_category=n,
                                        n_queries_per_cat=nq)
        hit = sum(s["categories"][c]["cache_hits"] for c in CATEGORIES) / \
            sum(s["categories"][c]["lookups"] for c in CATEGORIES)
        jh = sum(round(s["categories"][c]["positive_rate"]
                       * s["categories"][c]["cache_hits"]) for c in CATEGORIES)
        th = sum(s["categories"][c]["cache_hits"] for c in CATEGORIES)
        pos = jh / max(th, 1)
        # the paper's selection logic (§5.3): thresholds below the knee
        # "introduce irrelevant matches, decreasing the positive hit rate";
        # pick the highest hit rate whose precision clears the paper's
        # observed floor (92.5%)
        score = hit if pos >= 0.92 else -1.0
        if best is None or score > best[1]:
            best = (thr, score)
        rows.append({
            "name": f"sec5.3/threshold_{thr:.2f}",
            "us_per_call": 1e6 * wall / nqueries,
            "derived": f"hit_rate={hit:.3f} positive_rate={pos:.3f} "
                       f"tradeoff={score:.3f}",
        })
    rows.append({"name": "sec5.3/optimal", "us_per_call": 0.0,
                 "derived": f"best_threshold={best[0]:.2f} (paper: 0.80)"})
    return rows, {"best": best}


def tenant_table(full: bool = False):
    """Per-tenant breakdown (beyond-paper, DESIGN.md §13): one partitioned
    cache, Zipf-skewed 3-tenant traffic, per-tenant hit rate + precision +
    mean latency — the multi-tenant analogue of Table 1."""
    n = 800 if full else 250
    nq = 600 if full else 240
    pairs = build_corpus(n, seed=0)
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    registry = TenantRegistry((
        TenantSpec("free", share=1.0, weight=1.0),
        TenantSpec("pro", share=2.0, weight=2.0),
        TenantSpec("enterprise", share=2.0, weight=4.0, threshold=0.85),
    ))
    cfg = CacheConfig(dim=384, capacity=8 * n * len(registry), value_len=48,
                      ttl=None, threshold=0.8)
    eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), judge=judge,
                       batch_size=64, registry=registry)
    for name in registry.names:
        eng.warm(pairs, tenant=name)
    workload = build_multi_tenant_workload(
        pairs, nq, tenants=list(registry.names), skew=1.2, seed=2)
    t0 = time.perf_counter()
    eng.process(workload)
    wall = time.perf_counter() - t0

    s = eng.metrics.summary()
    dev = eng.tenant_stats()
    rows = []
    for name in registry.names:
        h = s["tenants"][name]
        d = dev[name]
        rows.append({
            "name": f"tenancy/{name}",
            "us_per_call": 1e6 * wall / max(nq, 1),
            "derived": (f"lookups={d['lookups']}"
                        f" hit_rate={h['hit_rate']:.3f}"
                        f" inserts={d['inserts']}"
                        f" evictions={d['evictions']}"
                        f" region_slots={d['region_slots']}"),
        })
    return rows, s


def context_table(full: bool = False):
    """Multi-turn context caching (beyond-paper, DESIGN.md §16.6).

    One dialogue state served twice (record, then replay with rephrased
    follow-ups), through the same engine with context fusion on vs off.
    The rows the session subsystem stands on: follow-up *replays* convert
    from 0% hits (stateless — their raw texts are globally unique) to
    near-100% hits (fused — their dialogue states repeat), while
    context-hit precision holds the paper-grade >97% bar.
    """
    from repro.context import DecayMeanFusion
    from repro.serving import build_multi_turn_workload, turn_levels

    n = 400 if full else 150
    n_groups, turns = 10, 3
    pairs = build_corpus(n, seed=0)
    convs = build_multi_turn_workload(pairs, n_groups, turns=turns, seed=23)
    rec, rep = convs[:n_groups], convs[n_groups:]
    key_by_sid = {p.qa_id: p.semantic_key for p in pairs}
    for conv in convs:
        for r in conv:
            key_by_sid.setdefault(r.source_id, r.semantic_key)

    def judge(req, sid):
        return key_by_sid.get(sid, "") == req.semantic_key

    rows = []
    summaries = {}
    for tag, fusion in (("fusion_on", DecayMeanFusion(window=4)),
                        ("fusion_off", None)):
        cfg = CacheConfig(dim=384, capacity=8 * n, value_len=48,
                          ttl=None, threshold=0.8)
        eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), judge=judge,
                           batch_size=32, fusion=fusion)
        eng.warm(pairs)
        t0 = time.perf_counter()
        for half in (rec, rep):           # record first, then replay
            for level in turn_levels(half):
                eng.process(level)
        wall = time.perf_counter() - t0
        s = eng.metrics.summary()
        summaries[tag] = s
        nq = sum(len(c) for c in convs)
        for cat in ("ctx/open_repeat", "ctx/followup", "ctx/followup_repeat"):
            m = s["categories"][cat]
            rows.append({
                "name": f"context/{tag}/{cat.split('/', 1)[1]}",
                "us_per_call": 1e6 * wall / nq,
                "derived": (f"hits={m['cache_hits']}/{m['lookups']}"
                            f" hit_rate={m['hit_rate']:.3f}"
                            f" positive_rate={m['positive_rate']:.3f}"),
            })
        if s["context"]:
            c = s["context"]["context"]
            rows.append({
                "name": f"context/{tag}/context_rows",
                "us_per_call": 0.0,
                "derived": (f"lookups={c['lookups']}"
                            f" hit_rate={c['hit_rate']:.3f}"
                            f" positive_rate={c['positive_rate']:.3f}"),
            })
    on = summaries["fusion_on"]["categories"]["ctx/followup_repeat"]
    off = summaries["fusion_off"]["categories"]["ctx/followup_repeat"]
    rows.append({
        "name": "context/followup_conversion",
        "us_per_call": 0.0,
        "derived": (f"fused_hit_rate={on['hit_rate']:.3f}"
                    f" stateless_hit_rate={off['hit_rate']:.3f}"
                    f" fused_positive_rate={on['positive_rate']:.3f}"),
    })
    return rows, summaries


def near_hit_table(full: bool = False):
    """Generative near-hit band (beyond-paper, DESIGN.md §17.6).

    One paraphrase-heavy workload served twice: by an exact-reuse engine
    and by the same engine with a [τ_lo, τ_hi) band + TemplateSplice
    synthesizer. The rows the generative subsystem stands on: judged band
    rows convert into served near-hits that cut backend calls strictly
    beyond exact reuse, at high judge-verified precision, while every row
    the exact path hit is served byte-identically.
    """
    from repro.generative import BandPolicy, TemplateSplice

    n = 300 if full else 100
    pairs = build_corpus(n, seed=0)
    queries = build_test_queries(pairs, n_per_category=100 if full else 60,
                                 paraphrase_ratio=0.8, seed=1)
    key_by_sid = {p.qa_id: p.semantic_key for p in pairs}

    def judge(req, sid):
        return key_by_sid.get(sid, "") == req.semantic_key

    reqs = [Request(query=q.query, category=q.category,
                    source_id=q.source_id, semantic_key=q.semantic_key)
            for q in queries]
    rows, summaries = [], {}
    resps, calls = {}, {}
    for tag, syn, pol in (
            ("band_off", None, None),
            ("band_on", TemplateSplice(rival_margin=0.12),
             BandPolicy(tau_lo=0.75, tau_hi=0.8))):
        cfg = CacheConfig(dim=384, capacity=8 * n, value_len=48,
                          ttl=None, threshold=0.8)
        eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), judge=judge,
                           batch_size=32, synthesizer=syn, policy=pol)
        eng.warm(pairs)
        t0 = time.perf_counter()
        resps[tag] = eng.process(reqs)
        wall = time.perf_counter() - t0
        s = eng.metrics.summary()
        summaries[tag] = s
        calls[tag] = eng.backend.calls
        hit_rate = sum(r.cached for r in resps[tag]) / len(reqs)
        rows.append({
            "name": f"near/{tag}/serving",
            "us_per_call": 1e6 * wall / len(reqs),
            "derived": (f"backend_calls={eng.backend.calls}"
                        f" hit_rate={hit_rate:.3f}"
                        f" cost_usd={s['total_cost_usd']:.4f}"),
        })
        if s["near"]:
            m = s["near"]
            rows.append({
                "name": f"near/{tag}/band",
                "us_per_call": 0.0,
                "derived": (f"band={m['band_lookups']}"
                            f" served={m['near_hits_served']}"
                            f" conversion={m['conversion_rate']:.3f}"
                            f" precision={m['near_precision']:.3f}"),
            })
    exact_identical = all(
        b.answer == a.answer and b.score == a.score
        for a, b in zip(resps["band_off"], resps["band_on"]) if a.cached)
    saved = calls["band_off"] - calls["band_on"]
    rows.append({
        "name": "near/calls_saved_beyond_exact",
        "us_per_call": 0.0,
        "derived": (f"saved={saved}"
                    f" exact_rows_identical={exact_identical}"),
    })
    return rows, summaries


def resilience_table(full: bool = False):
    """Resilient serving under deterministic chaos (DESIGN.md §20.7).

    One paraphrase-heavy workload served twice through the SAME seeded
    ``FaultSchedule`` — a hard-error window, a 50% brownout, a latency
    spike, all keyed by backend call index so the sync batch partitioning
    replays the faults bit-identically:

      * ``resilience_off`` — plain engine: per-row containment only; every
        miss row whose backend call faulted resolves with ``error`` set.
      * ``resilience_on``  — deadline-budgeted retries (deterministic
        backoff, no real sleeps), a zero-cooldown circuit breaker, and
        degraded cache serving above ``BandPolicy.degraded_lo``.

    The ``fault/*`` rows CI asserts on: availability on strictly above
    off, and the breaker both tripping and recovering.
    """
    from repro.generative import BandPolicy
    from repro.serving import (CircuitBreaker, FaultSchedule, FaultWindow,
                               FaultyBackend, ResilienceConfig, RetryPolicy,
                               build_workload)

    n = 300 if full else 100
    batch = 32 if full else 16
    pairs = build_corpus(n, seed=0)
    reqs = build_workload(pairs, 12 * batch, paraphrase_ratio=0.9,
                          burst_prob=0.0, seed=43)
    key_by_sid = {p.qa_id: p.semantic_key for p in pairs}

    def judge(req, sid):
        return key_by_sid.get(sid, "") == req.semantic_key

    schedule = FaultSchedule(windows=(
        FaultWindow("error", 2, 7),
        FaultWindow("brownout", 8, 11, error_rate=0.5),
        FaultWindow("latency_spike", 11, 13, extra_latency_s=0.02),
    ), seed=5)
    policy = BandPolicy(tau_lo=0.70, tau_hi=0.80, degraded_lo=0.60)

    rows, out = [], {}
    avail, engines, configs = {}, {}, {}
    for tag, resilient in (("resilience_off", False),
                           ("resilience_on", True)):
        backend = FaultyBackend(SimulatedLLMBackend(pairs), schedule)
        res = None
        if resilient:
            res = ResilienceConfig(
                retry=RetryPolicy(max_attempts=3, base_backoff_s=0.001,
                                  max_backoff_s=0.002, seed=3),
                breaker=CircuitBreaker(failure_threshold=3, window=8,
                                       cooldown_s=0.0),
                sleep=lambda s: None)
        cfg = CacheConfig(dim=384, capacity=8 * n, value_len=48,
                          ttl=None, threshold=0.8)
        eng = CachedEngine(cfg, backend, judge=judge, batch_size=batch,
                           policy=policy, resilience=res)
        eng.warm(pairs)
        eng.serve_batch([Request(query="resilience warmup")])  # fault idx 0
        t0 = time.perf_counter()
        resps = eng.process(reqs)
        wall = time.perf_counter() - t0
        avail[tag] = sum(1 for r in resps if not r.error) / len(resps)
        engines[tag], configs[tag] = eng, res
        rows.append({
            "name": f"fault/{tag}/serving",
            "us_per_call": 1e6 * wall / len(reqs),
            "derived": (f"availability={avail[tag]:.4f}"
                        f" faults_injected={backend.faults_injected}"
                        f" degraded={sum(r.degraded for r in resps)}"
                        f" errors={sum(bool(r.error) for r in resps)}"),
        })
    rm = engines["resilience_on"].metrics.resilience
    br = configs["resilience_on"].breaker
    rows.append({
        "name": "fault/availability",
        "us_per_call": 0.0,
        "derived": (f"on={avail['resilience_on']:.4f}"
                    f" off={avail['resilience_off']:.4f}"
                    f" delta={avail['resilience_on'] - avail['resilience_off']:.4f}"),
    })
    rows.append({
        "name": "fault/retries",
        "us_per_call": 0.0,
        "derived": (f"retries={rm.retries}"
                    f" retry_successes={rm.retry_successes}"
                    f" backend_failures={rm.backend_failures}"
                    f" deadline_exhausted={rm.deadline_exhausted}"),
    })
    rows.append({
        "name": "fault/breaker",
        "us_per_call": 0.0,
        "derived": (f"trips={br.trips} recoveries={br.recoveries}"
                    f" short_circuits={br.short_circuits} state={br.state}"),
    })
    rows.append({
        "name": "fault/degraded",
        "us_per_call": 0.0,
        "derived": (f"served={rm.degraded_served}"
                    f" failed={rm.degraded_failed}"
                    f" precision={rm.degraded_precision:.3f}"),
    })
    out["availability"] = avail
    out["resilience"] = rm.row()
    return rows, out


def ttl_behaviour():
    """TTL mechanism (paper §2.7): hit rate collapses after expiry."""

    def run(ttl, tick):
        pairs = build_corpus(300, seed=0)
        queries = build_test_queries(pairs, n_per_category=75, seed=1)
        cfg = CacheConfig(dim=384, capacity=4096, value_len=48, ttl=ttl,
                          threshold=0.8)
        eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), batch_size=128)
        eng.warm(pairs)
        eng.tick(tick)      # advance the clock past (or not past) the TTL
        eng.process([Request(query=q.query, category=q.category)
                     for q in queries])
        return sum(eng.metrics.per_category[c].hits for c in CATEGORIES)

    hit_fresh = run(ttl=3600.0, tick=60.0)     # within TTL
    hit_expired = run(ttl=30.0, tick=60.0)     # past TTL: warm cache useless
    rows = [{"name": "sec2.7/ttl", "us_per_call": 0.0,
             "derived": f"hits_within_ttl={hit_fresh} "
                        f"hits_after_expiry={hit_expired}"}]
    return rows, {}


def obs_table(full: bool = False):
    """Observability plane (beyond-paper, DESIGN.md §18.6).

    The ``obs/*`` stage-breakdown rows: per-stage latency quantiles from a
    fully-traced (sample rate 1.0) serving run, the span-sum-vs-e2e
    invariant, and the tracing overhead (traced vs untraced best-of-3
    walls on the identical workload — the <5% bound the serve-bench smoke
    asserts).
    """
    from repro.obs import STAGES, TraceConfig, Tracer

    n = 300 if full else 100
    pairs = build_corpus(n, seed=0)
    queries = build_test_queries(pairs, n_per_category=100 if full else 60,
                                 seed=1)
    reqs = [Request(query=q.query, category=q.category,
                    source_id=q.source_id, semantic_key=q.semantic_key)
            for q in queries]
    cfg = CacheConfig(dim=384, capacity=8 * n, value_len=48,
                      ttl=None, threshold=0.8)

    walls = {}
    engines = {}
    for tag, tracer in (("off", None),
                        ("on", Tracer(TraceConfig(sample_rate=1.0, head=0,
                                                  max_traces=65536)))):
        eng = CachedEngine(cfg, SimulatedLLMBackend(pairs), batch_size=32,
                           tracer=tracer)
        eng.warm(pairs)
        eng.process(reqs[:32])             # compile before the clock
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            eng.process(reqs)
            best = min(best, time.perf_counter() - t0)
        walls[tag] = best
        engines[tag] = eng

    rows = []
    eng = engines["on"]
    decomp = eng.tracer.stage_decomposition()
    for stage in STAGES:
        if stage not in decomp:
            continue                       # queue-side stages: async only
        r = decomp[stage]
        rows.append({
            "name": f"obs/stage/{stage}",
            "us_per_call": 1e6 * r["p50_s"],
            "derived": (f"p95_us={1e6 * r['p95_s']:.1f}"
                        f" p99_us={1e6 * r['p99_s']:.1f}"
                        f" count={r['count']}"),
        })
    traces = eng.tracer.traces()
    ratios = [t.span_sum_s / t.e2e_s for t in traces if t.e2e_s]
    rows.append({
        "name": "obs/span_sum",
        "us_per_call": 0.0,
        "derived": (f"min_ratio={min(ratios):.4f}"
                    f" max_ratio={max(ratios):.4f}"
                    f" traces={len(traces)}"),
    })
    overhead_pct = 100.0 * (walls["on"] / walls["off"] - 1.0)
    rows.append({
        "name": "obs/trace_overhead",
        "us_per_call": 1e6 * (walls["on"] - walls["off"]) / len(reqs),
        "derived": (f"traced_wall_s={walls['on']:.4f}"
                    f" untraced_wall_s={walls['off']:.4f}"
                    f" overhead_pct={overhead_pct:.2f}"),
    })
    return rows, {"decomposition": decomp, "overhead_pct": overhead_pct}


# child program for shard_table: timed local-vs-sharded fused steps on a
# forced-8-device CPU topology (the parent process is single-device)
_SHARD_CHILD = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core import SemanticCache, CacheConfig, DistributedCache

rows = []
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
B = 64
for cap in @CAPS@:
    cfg = CacheConfig(dim=64, capacity=cap, value_len=16, ttl=None,
                      threshold=0.8)
    local = SemanticCache(cfg)
    dc = DistributedCache(SemanticCache(cfg), mesh)
    mv = jnp.zeros((B, 16), jnp.int32)
    mvl = jnp.full((B,), 16, jnp.int32)
    lstep = jax.jit(lambda rt, q, t: local.step(rt, q, mv, mvl, t))
    dstep = jax.jit(lambda rt, q, t: dc.step(rt, q, mv, mvl, t))
    walls, parity = {}, True
    for tag, cache, step in (("local", local, lstep),
                             ("sharded", dc, dstep)):
        rt = cache.init()
        hits = []
        for i in range(3):                       # compile + fill
            q = jax.random.normal(jax.random.PRNGKey(i % 2), (B, 64))
            res, rt = step(rt, q, jnp.float32(i))
            hits.append(np.asarray(res.hit).copy())
        jax.block_until_ready(rt.state.keys)
        n = 10
        t0 = time.perf_counter()
        for i in range(n):
            res, rt = step(rt, jax.random.normal(
                jax.random.PRNGKey(i % 2), (B, 64)), jnp.float32(3 + i))
        jax.block_until_ready(res.score)
        walls[tag] = (time.perf_counter() - t0) / n
        if tag == "local":
            ref_hits = hits
        else:
            parity = all(np.array_equal(a, b)
                         for a, b in zip(ref_hits, hits))
    for tag in ("local", "sharded"):
        rows.append({
            "name": f"shard/step_{tag}_cap{cap}",
            "us_per_call": 1e6 * walls[tag],
            "derived": (f"batch={B} dim=64 shards="
                        f"{1 if tag == 'local' else 4} parity={parity}"
                        f" ratio={walls['sharded'] / walls['local']:.2f}"
                        f" platform={jax.default_backend()}"),
        })
print("ROWS-JSON " + json.dumps(rows))
"""


def shard_table(full: bool = False):
    """Sharded-step rows (beyond-paper, DESIGN.md §19.6).

    ``shard/*`` rows: the fused step's us/call, local single-device vs the
    4-shard ``DistributedCache`` on the same capacity, plus the hit-mask
    parity of the two paths on identical traffic. Runs in a subprocess
    on 8 forced CPU devices (``JAX_PLATFORMS=cpu``) — same machinery as
    ``tests/test_distributed.py``; the rows say ``platform=cpu``.
    """
    import json
    import os
    import subprocess
    import sys

    caps = [1 << 16] + ([1 << 20] if full else [])
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    # forced host devices: the child must not contend for an accelerator
    # the parent process already holds
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", _SHARD_CHILD.replace("@CAPS@", repr(caps))],
        capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"shard child failed:\n{r.stderr[-2000:]}")
    rows = None
    for line in r.stdout.splitlines():
        if line.startswith("ROWS-JSON "):
            rows = json.loads(line[len("ROWS-JSON "):])
    if rows is None:
        raise RuntimeError("shard child produced no ROWS-JSON line")
    return rows, {"caps": caps}
