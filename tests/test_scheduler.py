"""Async serving subsystem tests (DESIGN.md §12): continuous micro-batch
scheduler, in-flight coalescing, backpressure, async-vs-sync equivalence,
partial-batch padding hygiene, and the extended serving metrics."""
import asyncio
import json
import time

import numpy as np
import pytest

from repro.core.types import CacheConfig
from repro.data.qa_dataset import build_corpus, build_test_queries
from repro.serving import (AsyncCacheServer, BackendError, Batcher,
                           CachedEngine, FaultSchedule, FaultWindow,
                           FaultyBackend, Request, Response, SchedulerConfig,
                           SimulatedLLMBackend, build_workload,
                           run_closed_loop, run_open_loop, run_waves)
from repro.serving.engine import PAD_REQUEST


@pytest.fixture(scope="module")
def pairs():
    return build_corpus(120, seed=0)


# Mutually dissimilar novel queries (share almost no n-grams), so each one
# is guaranteed to miss independently — numbered variants of one template
# would legitimately hit each other's fresh inserts at threshold 0.8.
DISTINCT_QUERIES = [
    "why is the sky blue at noon",
    "best sourdough starter feeding schedule",
    "how tall is mount kilimanjaro",
    "difference between alligators and crocodiles",
    "what causes aurora borealis displays",
    "recommend a jazz album from 1959",
    "do tides depend on the moon",
    "boiling point of ethanol at altitude",
    "who invented the mechanical clock",
    "explain photosynthesis light reactions",
    "how many strings does a cello have",
    "what year did the berlin wall fall",
]


def make_engine(pairs, *, batch_size=16, judge=True, latency_s=0.0,
                block=False, **kw):
    by_id = {p.qa_id: p for p in pairs}

    def _judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    cfg = kw.pop("config", CacheConfig(dim=384, capacity=4096, value_len=48,
                                       ttl=None, threshold=0.8))
    backend = SimulatedLLMBackend(pairs, latency_per_call_s=latency_s,
                                  block=block)
    return CachedEngine(cfg, backend, judge=_judge if judge else None,
                        batch_size=batch_size, **kw)


class TestCoalescing:
    def test_concurrent_identical_misses_one_backend_call(self, pairs):
        eng = make_engine(pairs)
        q = "what is the airspeed velocity of an unladen swallow"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for _ in range(16)))

        responses = asyncio.run(herd())
        # one leader miss, fifteen waiters: ONE backend call total
        assert eng.backend.calls == 1
        assert len({r.answer for r in responses}) == 1
        assert sum(r.coalesced for r in responses) == 15
        assert eng.metrics.coalesced_calls == 15
        # only the leader performed a lookup
        assert int(eng.stats.lookups) == 1

    def test_coalesce_off_pays_per_duplicate(self, pairs):
        eng = make_engine(pairs)
        q = "tell me about the warranty on the quantum flux capacitor"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0,
                                    coalesce=False)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for _ in range(8)))

        responses = asyncio.run(herd())
        # all 8 land in one micro-batch; the fused peek runs before any
        # insert, so every duplicate misses and pays a backend call
        assert eng.backend.calls == 8
        assert eng.metrics.coalesced_calls == 0
        assert len({r.answer for r in responses}) == 1

    def test_coalesced_hits_inherit_cached_flag(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs)
        q = pairs[0].question         # byte-identical to a warm entry -> hit

        async def herd():
            sched = SchedulerConfig(max_batch=4, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for _ in range(6)))

        responses = asyncio.run(herd())
        assert eng.backend.calls == 0
        assert all(r.cached for r in responses)
        assert sum(r.coalesced for r in responses) == 5


class TestBackpressure:
    def test_full_queue_forces_oldest_deadline_flush(self, pairs):
        eng = make_engine(pairs)
        eng.serve_batch([Request(query="compile warmup")])  # pre-trace jit
        # the deadline (2.5s) is far beyond the test's fast path: only the
        # full-queue backpressure flush can serve the first batches quickly.
        # The last ragged group has no submitter pushing behind it, so it
        # legitimately waits out the deadline — that's the deadline path.
        sched = SchedulerConfig(max_batch=16, max_queue=4,
                                max_wait_ms=2_500.0, coalesce=False)
        reqs = [Request(query=q) for q in DISTINCT_QUERIES]
        calls_before = eng.backend.calls
        done_at: list[float] = []

        async def flood():
            async with AsyncCacheServer(eng, sched) as server:
                t0 = time.perf_counter()

                async def timed(r):
                    resp = await server.submit_request(r)
                    done_at.append(time.perf_counter() - t0)
                    return resp

                return await asyncio.gather(*(timed(r) for r in reqs))

        responses = asyncio.run(flood())
        assert len(responses) == 12
        assert all(r.answer for r in responses)
        assert eng.backend.calls - calls_before == 12
        # >= 8 responses (two forced flushes of 4) landed before the 2.5s
        # admission deadline could have fired even once
        assert sorted(done_at)[7] < 2.0, sorted(done_at)
        # ... and the ragged remainder was flushed by the deadline
        assert max(done_at) < 6.0, sorted(done_at)

    def test_stop_drains_queue(self, pairs):
        eng = make_engine(pairs)
        sched = SchedulerConfig(max_batch=64, max_wait_ms=10_000.0)
        reqs = [Request(query=f"drain question {i}") for i in range(5)]

        async def submit_then_stop():
            server = AsyncCacheServer(eng, sched)
            await server.start()
            tasks = [asyncio.create_task(server.submit_request(r))
                     for r in reqs]
            await asyncio.sleep(0.05)   # all queued, none flushed (64/10s)
            await server.stop()         # drain must serve them
            return await asyncio.gather(*tasks)

        responses = asyncio.run(submit_then_stop())
        assert len(responses) == 5 and all(r.answer for r in responses)

    def test_submit_after_stop_raises(self, pairs):
        eng = make_engine(pairs)

        async def go():
            server = AsyncCacheServer(eng)
            await server.start()
            await server.stop()
            with pytest.raises(RuntimeError):
                await server.submit("too late")

        asyncio.run(go())

    def test_restart_after_stop(self, pairs):
        eng = make_engine(pairs)
        sched = SchedulerConfig(max_batch=4, max_wait_ms=5.0)

        async def go():
            server = AsyncCacheServer(eng, sched)
            await server.start()
            r1 = await server.submit(DISTINCT_QUERIES[0])
            await server.stop()
            await server.start()          # drained scheduler restarts cleanly
            r2 = await server.submit(DISTINCT_QUERIES[0])
            await server.stop()
            return r1, r2

        r1, r2 = asyncio.run(go())
        assert not r1.cached and r2.cached     # second run reuses the slab
        assert r1.answer == r2.answer


class TestAsyncSyncEquivalence:
    def test_same_decisions_and_answers(self, pairs):
        queries = build_test_queries(pairs, n_per_category=24, seed=5)
        reqs = [Request(query=q.query, category=q.category,
                        source_id=q.source_id, semantic_key=q.semantic_key)
                for q in queries]
        batch = 16

        sync_eng = make_engine(pairs, batch_size=batch)
        sync_eng.warm(pairs)
        sync_resp = sync_eng.process(reqs)

        async_eng = make_engine(pairs, batch_size=batch)
        async_eng.warm(pairs)

        async def drive():
            sched = SchedulerConfig(max_batch=batch, max_wait_ms=50.0,
                                    coalesce=False)
            async with AsyncCacheServer(async_eng, sched) as server:
                # lockstep waves of max_batch reproduce the sync engine's
                # batch partitioning exactly
                return await run_waves(server.submit_request, reqs,
                                       wave=batch)

        async_resp = asyncio.run(drive()).responses
        assert len(async_resp) == len(sync_resp)
        for s, a in zip(sync_resp, async_resp):
            assert s.cached == a.cached
            assert s.answer == a.answer
        # aggregate parity: hit rate, backend spend, device counters
        assert sync_eng.backend.calls == async_eng.backend.calls
        assert int(sync_eng.stats.lookups) == int(async_eng.stats.lookups)
        assert int(sync_eng.stats.hits) == int(async_eng.stats.hits)
        s_sum = sync_eng.metrics.summary()
        a_sum = async_eng.metrics.summary()
        for cat, row in s_sum["categories"].items():
            assert a_sum["categories"][cat]["hit_rate"] == row["hit_rate"]

    def test_closed_loop_serves_everything(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs)
        wl = build_workload(pairs, 60, burst_prob=0.2, burst_size=3, seed=9)

        async def drive():
            async with AsyncCacheServer(eng) as server:
                return await run_closed_loop(server.submit_request, wl,
                                             concurrency=8)

        res = asyncio.run(drive())
        assert len(res.responses) == 60
        assert all(r is not None and r.answer for r in res.responses)
        assert eng.metrics.queries + eng.metrics.coalesced_calls == 60


class TestBatcherPadding:
    """Satellite: padded rows never touch metrics or the slab."""

    def test_pad_shapes(self):
        b = Batcher(batch_size=8)
        padded, n_valid = b.pad([Request(query="x")] * 3)
        assert len(padded) == 8 and n_valid == 3
        assert all(r is PAD_REQUEST for r in padded[3:])
        full, n = b.pad([Request(query="y")] * 8)
        assert len(full) == 8 and n == 8

    def test_partial_batch_counters_clean(self, pairs):
        eng = make_engine(pairs, batch_size=8)
        n = 11                        # not a multiple of 8 -> one padded batch
        reqs = [Request(query=q, category="python_basics")
                for q in DISTINCT_QUERIES[:n]]
        responses = eng.process(reqs)
        assert len(responses) == n
        # ServingMetrics: exactly n queries, no __pad__ category
        s = eng.metrics.summary()
        assert s["queries"] == n
        assert "__pad__" not in s["categories"]
        assert s["categories"]["python_basics"]["lookups"] == n
        # device counters: pads neither looked up nor inserted
        assert int(eng.stats.lookups) == n
        assert int(eng.stats.inserts) == n        # all novel -> all inserted
        assert int(np.sum(np.asarray(eng.state.valid))) == n
        # the cost model charged n backend calls, not the padded 16
        assert s["baseline_cost_usd"] == pytest.approx(
            n * eng.backend.cost_per_call_usd)
        # second pass: every real row is served from cache, pads never poison
        responses2 = eng.process(reqs)
        assert all(r.cached for r in responses2)
        assert int(eng.stats.lookups) == 2 * n

    def test_padded_and_exact_batches_share_one_compiled_step(self, pairs):
        eng = make_engine(pairs, batch_size=8)
        eng.process([Request(query=f"trace probe a{i}") for i in range(8)])
        traces = eng._step_jit._cache_size()
        eng.process([Request(query=f"trace probe b{i}") for i in range(3)])
        assert eng._step_jit._cache_size() == traces


class TestServingMetricsExtensions:
    def test_percentiles_and_coalesced_in_summary(self, pairs):
        eng = make_engine(pairs)
        eng.process([Request(query=f"metrics probe {i}") for i in range(6)])
        eng.metrics.record_coalesced(2)
        eng.metrics.record_latency("coalesced", 0.001)
        s = eng.metrics.summary()
        # new keys ride along ...
        assert s["coalesced_calls"] == 2
        pct = s["latency_percentiles"]
        assert set(pct) >= {"miss", "coalesced"}
        for row in pct.values():
            assert row["p50_s"] <= row["p95_s"] <= row["p99_s"]
        # ... and the paper-table rows are unchanged
        for key in ("categories", "queries", "total_cost_usd",
                    "baseline_cost_usd", "cost_saving_pct",
                    "avg_latency_with_cache_s",
                    "avg_latency_without_cache_s"):
            assert key in s

    def test_percentile_math(self):
        from repro.serving.metrics import percentiles
        xs = [float(i) for i in range(1, 101)]
        p = percentiles(xs)
        assert p["count"] == 100
        assert p["p50_s"] == pytest.approx(
            float(np.percentile(xs, 50)), abs=1e-9)
        assert p["p95_s"] == pytest.approx(
            float(np.percentile(xs, 95)), abs=1e-9)
        assert p["p99_s"] == pytest.approx(
            float(np.percentile(xs, 99)), abs=1e-9)
        assert percentiles([])["count"] == 0


class TestCoalescedPathSplit:
    """Satellite (§18.5): a coalesced waiter's end-to-end latency files
    under its OWN "coalesced" path bucket — folding N near-zero waiter
    latencies into the leader's hit/miss path would skew those paths'
    percentiles exactly when coalescing works best."""

    def test_waiters_never_pollute_leader_path(self, pairs):
        eng = make_engine(pairs)
        q = "one novel question sixteen clients ask at once"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for _ in range(16)))

        asyncio.run(herd())
        pct = eng.metrics.summary()["latency_percentiles"]
        # exactly one leader miss; all fifteen waiters in "coalesced"
        assert pct["miss"]["count"] == 1
        assert pct["coalesced"]["count"] == 15
        assert "hit" not in pct
        assert eng.metrics.latency_samples["miss"].count == 1
        assert eng.metrics.latency_samples["coalesced"].count == 15

    def test_split_holds_per_tenant(self, pairs):
        from repro.tenancy import TenantRegistry
        eng = make_engine(pairs,
                          registry=TenantRegistry.uniform(["acme", "globex"]),
                          config=CacheConfig(dim=384, capacity=4096,
                                             value_len=48, ttl=None,
                                             threshold=0.8))
        q = "identical question from both tenants"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q, tenant=t)
                      for t in ("acme", "globex") for _ in range(4)))

        asyncio.run(herd())
        tenants = eng.metrics.summary()["tenants"]
        for name in ("acme", "globex"):       # coalescing never crosses
            row = tenants[name]               # tenants: one leader each
            assert row["latency_percentiles"]["miss"]["count"] == 1
            assert row["latency_percentiles"]["coalesced"]["count"] == 3
            assert row["coalesced_calls"] == 3

    def test_scheduler_traces_split_leader_vs_waiter(self, pairs):
        from repro.obs import TraceConfig, Tracer
        eng = make_engine(pairs, tracer=Tracer(
            TraceConfig(sample_rate=1.0, head=0)))
        q = "a herd question for trace attribution"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q, explain=True) for _ in range(6)))

        responses = asyncio.run(herd())
        assert eng.tracer.retained == 6
        by_stage = {}
        for t in eng.tracer.traces():
            names = tuple(s.name for s in t.spans)
            by_stage.setdefault("coalesce_attach" in names, []).append(t)
        leader_traces, waiter_traces = by_stage[False], by_stage[True]
        assert len(leader_traces) == 1 and len(waiter_traces) == 5
        lt = leader_traces[0]
        # the leader's trace carries the queue-side spans AND the engine's
        # contiguous stage spans; its span sum reconstructs its e2e
        lnames = [s.name for s in lt.spans]
        top = [s.name for s in lt.spans if s.parent is None]
        assert top[:3] == ["queue_wait", "batch_form", "dispatch"]
        assert {"embed", "device_step", "respond"} <= set(lnames)
        assert lt.span_sum_s == pytest.approx(lt.e2e_s, rel=0.10)
        # a waiter's whole life is attach -> respond, annotated with its
        # leader, and its why record is demoted leader attribution
        for wt in waiter_traces:
            assert [s.name for s in wt.spans] == \
                ["coalesce_attach", "respond"]
            assert wt.meta["leader"]
            assert wt.span_sum_s == pytest.approx(wt.e2e_s, rel=0.10)
        whys = {r.why["decision"] for r in responses}
        assert whys == {"miss", "coalesced"}
        w = next(r.why for r in responses if r.why["decision"] == "coalesced")
        assert w["leader_decision"] == "miss"
        assert w["coalesced_into"]


    def test_queue_wait_children_tile_it(self, pairs):
        from repro.obs import TraceConfig, Tracer
        eng = make_engine(pairs, batch_size=8, latency_s=0.03, block=True,
                          tracer=Tracer(TraceConfig(sample_rate=1.0,
                                                    head=0)))

        async def staggered():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=2.0)
            async with AsyncCacheServer(eng, sched) as server:
                tasks = []
                for q in DISTINCT_QUERIES:
                    tasks.append(asyncio.create_task(server.submit(q)))
                    await asyncio.sleep(0.01)
                return await asyncio.gather(*tasks)

        asyncio.run(staggered())
        busy = []
        for t in eng.tracer.traces():
            qw = [s for s in t.spans if s.name == "queue_wait"]
            kids = [s for s in t.spans if s.parent == "queue_wait"]
            assert len(qw) == 1
            assert [s.name for s in kids] == ["engine_busy", "batching"]
            eb, bt = kids
            # exact tiling: arrival -> loop free -> flush
            assert eb.t0 == qw[0].t0 and eb.t1 == bt.t0
            assert bt.t1 == qw[0].t1 and eb.t1 >= eb.t0
            busy.append(eb.duration_s)
        # requests that arrived during a 30 ms backend call waited for it
        assert max(busy) > 0.005 and min(busy) == 0.0

    def test_dispatch_and_resolve_close_the_trace(self, pairs):
        from repro.obs import TraceConfig, Tracer
        eng = make_engine(pairs, tracer=Tracer(
            TraceConfig(sample_rate=1.0, head=0)))
        eng.warm(pairs[:20])

        async def serve():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=2.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for q in
                      [pairs[i].question for i in range(4)]
                      + DISTINCT_QUERIES[:3]))

        asyncio.run(serve())
        traces = eng.tracer.traces()
        assert len(traces) == 7
        for t in traces:
            top = [s for s in t.spans if s.parent is None]
            assert [s.name for s in top] == [
                "queue_wait", "batch_form", "dispatch", "embed",
                "device_step", "near_synthesis", "backend_call", "insert",
                "respond", "resolve"]
            for a, b in zip(top, top[1:]):
                assert a.t1 == b.t0            # contiguous, in order
            assert all(s.t1 >= s.t0 for s in top)
            # e2e is measured to the batch's last future set: resolve's end
            assert t.span_sum_s == pytest.approx(t.e2e_s, rel=1e-9)
            assert t.meta["ready_t"] <= top[-1].t1
        # one dispatch and one resolve per batch, shared by its rows
        per_batch = {name: {(s.t0, s.t1) for t in traces for s in t.spans
                            if s.name == name}
                     for name in ("embed", "dispatch", "resolve")}
        assert len(per_batch["dispatch"]) == len(per_batch["embed"]) \
            == len(per_batch["resolve"])


class TestTCPServer:
    def test_json_lines_roundtrip(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs)
        known = pairs[0].question

        async def client():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                try:
                    port = await server.serve_tcp("127.0.0.1", 0)
                except OSError as exc:       # sandboxed CI without sockets
                    pytest.skip(f"cannot bind loopback: {exc}")
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                for i in range(4):
                    writer.write(json.dumps(
                        {"id": i, "query": known}).encode() + b"\n")
                writer.write(b"not json\n")
                await writer.drain()
                lines = [json.loads(await reader.readline())
                         for _ in range(5)]
                writer.close()
                return lines

        lines = asyncio.run(client())
        answers = [l for l in lines if "answer" in l]
        errors = [l for l in lines if "error" in l]
        assert len(answers) == 4 and len(errors) == 1
        assert all(l["cached"] for l in answers)
        assert sum(l["coalesced"] for l in answers) >= 3
        # client-supplied ids are echoed, so pipelined (and possibly
        # reordered) responses stay correlatable
        assert sorted(l["id"] for l in answers) == [0, 1, 2, 3]


# every backend call faults — used to exercise the §20.2 failure domain
ALL_ERRORS = FaultSchedule((FaultWindow("error", 0, 10_000),))


class TestFailureDomainSplit:
    def test_only_failed_rows_reject_in_a_mixed_batch(self, pairs):
        # regression (§20.2): a throwing backend used to fail the WHOLE
        # admission batch; now the hit row of the same flush serves
        # normally and only the true-miss row rejects
        eng = make_engine(pairs)
        eng.warm(pairs)
        eng.backend = FaultyBackend(eng.backend, ALL_ERRORS)
        hit_q = pairs[0].question
        miss_q = DISTINCT_QUERIES[0]

        async def drive():
            sched = SchedulerConfig(max_batch=4, max_wait_ms=20.0,
                                    coalesce=False)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(server.submit(hit_q),
                                            server.submit(miss_q),
                                            return_exceptions=True)

        r_hit, r_miss = asyncio.run(drive())
        assert isinstance(r_hit, Response)
        assert r_hit.cached and r_hit.error == "" and r_hit.answer
        assert isinstance(r_miss, BackendError)
        assert "injected error" in str(r_miss)
        assert eng.metrics.resilience.backend_failures == 1

    def test_waiters_inherit_leader_failure_and_state_unwinds(self, pairs):
        # a failed leader must reject its coalesced waiters too — and leave
        # no pending entry, leader embedding, or LSH bucket behind
        eng = make_engine(pairs)
        eng.backend = FaultyBackend(eng.backend, ALL_ERRORS)
        q = DISTINCT_QUERIES[1]

        async def drive():
            sched = SchedulerConfig(max_batch=4, max_wait_ms=10.0,
                                    coalesce_sim=0.9)
            server = AsyncCacheServer(eng, sched)
            async with server:
                results = await asyncio.gather(
                    *(server.submit(q) for _ in range(5)),
                    return_exceptions=True)
            return results, server.scheduler

        results, sched = asyncio.run(drive())
        assert len(results) == 5
        assert all(isinstance(r, BackendError) for r in results)
        assert eng.backend.calls_started == 1    # ONE failed call for all 5
        assert sched._pending == {}
        assert sched._leader_emb == {}
        assert sched._sim_buckets == {}


class TestShutdownUnderFire:
    def test_stop_mid_execute_resolves_every_future(self, pairs):
        eng = make_engine(pairs, latency_s=0.15, block=True, batch_size=4)
        eng.serve_batch([Request(query="compile warmup")])

        async def drive():
            sched = SchedulerConfig(max_batch=4, max_wait_ms=1.0)
            server = AsyncCacheServer(eng, sched)
            await server.start()
            tasks = [asyncio.create_task(server.submit(q))
                     for q in DISTINCT_QUERIES[:8]]
            await asyncio.sleep(0.05)     # first batch is mid-execute now
            await server.stop()           # drain: serve the backlog, exit
            results = await asyncio.gather(*tasks, return_exceptions=True)
            return results, server.scheduler

        results, sched = asyncio.run(drive())
        assert len(results) == 8
        # drain semantics: every accepted request is SERVED, none stranded
        assert all(isinstance(r, Response) for r in results)
        assert sched._pending == {}

    def test_stop_with_inflight_waiters_strands_nothing(self, pairs):
        eng = make_engine(pairs, latency_s=0.15, block=True, batch_size=4)
        eng.serve_batch([Request(query="compile warmup")])
        q = DISTINCT_QUERIES[2]

        async def drive():
            sched = SchedulerConfig(max_batch=4, max_wait_ms=1.0)
            server = AsyncCacheServer(eng, sched)
            await server.start()
            tasks = [asyncio.create_task(server.submit(q))
                     for _ in range(6)]
            await asyncio.sleep(0.05)     # leader mid-execute, 5 attached
            await server.stop()
            with pytest.raises(RuntimeError, match="not running"):
                await server.submit("too late")
            results = await asyncio.gather(*tasks, return_exceptions=True)
            return results, server.scheduler

        results, sched = asyncio.run(drive())
        assert all(isinstance(r, Response) for r in results)
        assert sum(r.coalesced for r in results) == 5
        assert sched._pending == {}
