"""Serving driver: ``python -m repro.launch.serve [--backend sim|model]``.

Runs the full GPT-Semantic-Cache serving system: warm the cache with the
QA corpus, stream the 2,000-test-query workload through the CachedEngine,
and print the paper's metrics. ``--backend model`` places a real (reduced)
architecture behind the cache; ``--backend sim`` uses the simulated LLM
API with the paper-style latency/cost model.

``--scheduler async`` routes the workload through the continuous
micro-batching scheduler (DESIGN.md §12) instead of the sync batch loop:
open-loop Poisson arrivals at ``--rate-qps`` (or closed-loop with
``--concurrency`` clients when no rate is given), with in-flight duplicate
coalescing; the summary then also carries p50/p95/p99 latency per path and
the coalesced-call count.

``--tenants N`` turns on multi-tenant serving (DESIGN.md §13): the slab is
partitioned into N per-tenant regions, traffic is a Zipf-skewed mixture
over the tenants (``--tenant-skew``; 0 = uniform), admission is
deficit-round-robin fair, and the summary carries per-tenant hit/miss/
latency breakdowns plus the device-side per-tenant counters.
"""
from __future__ import annotations

import argparse
import asyncio
import json

from repro.configs import get_arch
from repro.core.index import IVFIndex
from repro.core.policy import AdaptiveThreshold
from repro.core.types import CacheConfig
from repro.data.qa_dataset import build_corpus, build_test_queries
from repro.data.tokenizer import HashTokenizer
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (AsyncCacheServer, CachedEngine, LoadResult,
                           ModelBackend, Request, SchedulerConfig,
                           ServingMetrics, SimulatedLLMBackend,
                           build_multi_tenant_workload, run_closed_loop,
                           run_open_loop)
from repro.tenancy import TenantRegistry


def judge_for(pairs):
    """Ground-truth judge over the corpus: a hit is positive when the
    matched entry answers the request's semantic key."""
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key
    return judge


def compile_serve_path(engine: CachedEngine, tenant: str = "default") -> None:
    """Pre-trace the serve path with one request, then zero the
    bookkeeping: the one-off jit compile must not flood every reported
    end-to-end percentile."""
    engine.serve_batch([Request(query="serve-path compile warmup",
                                tenant=tenant)])
    engine.metrics = ServingMetrics()


async def serve_async(engine: CachedEngine, requests, *, max_batch: int,
                      max_wait_ms: float = 5.0, coalesce: bool = True,
                      tenant_weights: dict | None = None,
                      rate_qps: float | None = None, concurrency: int = 32,
                      metrics_port: int | None = None) -> LoadResult:
    """Drive ``requests`` through an ``AsyncCacheServer`` over ``engine``:
    open-loop Poisson arrivals at ``rate_qps``, else ``concurrency``
    closed-loop clients. Responses come back in request order."""
    sched = SchedulerConfig(max_batch=max_batch, max_wait_ms=max_wait_ms,
                            coalesce=coalesce, tenant_weights=tenant_weights)
    async with AsyncCacheServer(engine, sched) as server:
        if metrics_port is not None:
            mport = await server.serve_metrics(port=metrics_port)
            print(f"/metrics exposition on http://127.0.0.1:{mport}/metrics")
        if rate_qps:
            return await run_open_loop(server.submit_request, requests,
                                       rate_qps)
        return await run_closed_loop(server.submit_request, requests,
                                     concurrency=concurrency)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=("sim", "model"), default="sim")
    ap.add_argument("--arch", default="yi-6b",
                    help="arch for --backend model (reduced variant)")
    ap.add_argument("--corpus", type=int, default=2000,
                    help="QA pairs per category")
    ap.add_argument("--queries", type=int, default=500,
                    help="test queries per category")
    ap.add_argument("--threshold", type=float, default=0.8)
    ap.add_argument("--ttl", type=float, default=None)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--index", choices=("exact", "ivf"), default="exact",
                    help="ANN index plugin behind the cache")
    ap.add_argument("--policy", choices=("fixed", "adaptive"), default="fixed",
                    help="threshold policy plugin")
    ap.add_argument("--no-fused", dest="fused", action="store_false",
                    help="use separate lookup+insert instead of the fused "
                         "single-jit step()")
    ap.add_argument("--scheduler", choices=("sync", "async"), default="sync",
                    help="sync batch loop vs async continuous micro-batching "
                         "with in-flight coalescing (DESIGN.md §12)")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="async admission deadline per micro-batch")
    ap.add_argument("--rate-qps", type=float, default=None,
                    help="async: open-loop Poisson arrival rate; omit for "
                         "closed-loop")
    ap.add_argument("--concurrency", type=int, default=32,
                    help="async closed-loop client count")
    ap.add_argument("--no-coalesce", dest="coalesce", action="store_false",
                    help="disable in-flight duplicate coalescing")
    ap.add_argument("--tenants", type=int, default=0,
                    help="partition the cache into N tenant regions and "
                         "serve a multi-tenant workload (0 = single-tenant)")
    ap.add_argument("--tenant-skew", type=float, default=1.0,
                    help="Zipf skew of tenant popularity (0 = uniform)")
    ap.add_argument("--snapshot", default=None,
                    help="save the full CacheRuntime (slab + policy + index "
                         "state) here after serving")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="async: serve the Prometheus-style /metrics (+ "
                         "/traces, /events) exposition on this HTTP port "
                         "for the run's duration (DESIGN.md §18.4)")
    ap.add_argument("--trace-sample", type=float, default=0.0,
                    help="request-trace retention rate in [0,1] "
                         "(0 = tracing off, the default; §18.2)")
    ap.add_argument("--trace-slow-ms", type=float, default=None,
                    help="always retain traces slower than this many ms, "
                         "even when the rate sampler would drop them")
    args = ap.parse_args()
    enable_compile_cache()

    pairs = build_corpus(args.corpus, seed=0)
    queries = build_test_queries(pairs, n_per_category=args.queries, seed=1)
    judge = judge_for(pairs)

    if args.backend == "sim":
        backend = SimulatedLLMBackend(pairs)
    else:
        import jax
        from repro.models.model import Model
        config = get_arch(args.arch).reduced()
        model = Model(config)
        params = model.init_params(jax.random.PRNGKey(0))
        backend = ModelBackend(model, params,
                               HashTokenizer(vocab_size=config.vocab))

    registry = None
    if args.tenants > 0:
        registry = TenantRegistry.uniform(
            [f"tenant-{i}" for i in range(args.tenants)])
    # multi-tenant: every tenant's region must hold the warm corpus
    capacity = max(16384, 8 * args.corpus) * max(1, args.tenants)
    cfg = CacheConfig(dim=384, capacity=capacity,
                      value_len=48, ttl=args.ttl, threshold=args.threshold)
    index = IVFIndex(ncentroids=128, nprobe=16, bucket_cap=1024) \
        if args.index == "ivf" else None
    policy = AdaptiveThreshold(init=args.threshold) \
        if args.policy == "adaptive" else None
    tracer = None
    if args.trace_sample > 0.0 or args.trace_slow_ms is not None:
        from repro.obs import TraceConfig, Tracer
        tracer = Tracer(TraceConfig(
            sample_rate=args.trace_sample,
            slow_threshold_s=None if args.trace_slow_ms is None
            else args.trace_slow_ms / 1000.0))
    engine = CachedEngine(cfg, backend, judge=judge, batch_size=args.batch,
                          index=index, policy=policy,
                          use_fused_step=args.fused, registry=registry,
                          tracer=tracer)

    if registry is None:
        print(f"warming cache with {len(pairs)} QA pairs ...")
        engine.warm(pairs)
        requests = [Request(query=q.query, category=q.category,
                            source_id=q.source_id,
                            semantic_key=q.semantic_key) for q in queries]
    else:
        print(f"warming {args.tenants} tenant regions with "
              f"{len(pairs)} QA pairs each ...")
        for name in registry.names:
            engine.warm(pairs, tenant=name)
        requests = build_multi_tenant_workload(
            pairs, len(queries), tenants=list(registry.names),
            skew=args.tenant_skew, seed=1)
    if args.scheduler == "sync":
        print(f"serving {len(queries)} queries (sync batches) ...")
        engine.process(requests)
    else:
        mode = (f"open-loop {args.rate_qps:.0f} qps" if args.rate_qps
                else f"closed-loop x{args.concurrency}")
        print(f"serving {len(queries)} queries (async scheduler, {mode}) ...")
        compile_serve_path(engine, "default" if registry is None
                           else registry.names[0])
        res = asyncio.run(serve_async(
            engine, requests, max_batch=args.batch,
            max_wait_ms=args.max_wait_ms, coalesce=args.coalesce,
            tenant_weights=None if registry is None else registry.weights(),
            rate_qps=args.rate_qps, concurrency=args.concurrency,
            metrics_port=args.metrics_port))
        print(f"sustained {res.achieved_qps:.1f} qps ({res.wall_s:.2f}s wall)")
        if tracer is not None and tracer.retained:
            print("trace stage decomposition (retained traces):")
            print(json.dumps(tracer.stage_decomposition(), indent=1))
    print(json.dumps(engine.metrics.summary(), indent=1))
    if registry is not None:
        print("device-side per-tenant counters:")
        print(json.dumps(engine.tenant_stats(), indent=1))
    if args.snapshot:
        engine.save_cache(args.snapshot)
        print(f"runtime snapshot (slab+policy+index state) -> {args.snapshot}")


if __name__ == "__main__":
    main()
