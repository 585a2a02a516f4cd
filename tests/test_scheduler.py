"""Async serving subsystem tests (DESIGN.md §12): continuous micro-batch
scheduler, in-flight coalescing, backpressure, async-vs-sync equivalence,
partial-batch padding hygiene, and the extended serving metrics."""
import asyncio
import dataclasses
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
        queue_side = ["queue_wait", "batch_form", "dispatch", "embed",
                      "device_step"]
        for t in traces:
            top = [s for s in t.spans if s.parent is None]
            if t.meta["release"] == "early":
                # a hit released at its peek: resolved while its batch's
                # misses were at the backend
                assert t.meta["path"] == "hit"
                assert [s.name for s in top] == queue_side + ["resolve"]
            else:
                assert [s.name for s in top] == queue_side + [
                    "near_synthesis", "backend_call", "insert", "respond",
                    "resolve"]
            for a, b in zip(top, top[1:]):
                assert a.t1 == b.t0            # contiguous, in order
            assert all(s.t1 >= s.t0 for s in top)
            # e2e is measured to the row's last future set: resolve's end
            assert t.span_sum_s == pytest.approx(t.e2e_s, rel=1e-9)
            assert t.meta["ready_t"] <= top[-1].t1
        # one dispatch per batch and one resolve per group resolved
        # together (a batch that released its hits has two), shared by
        # its rows
        per_batch = {name: {(s.t0, s.t1) for t in traces for s in t.spans
                            if s.name == name}
                     for name in ("embed", "dispatch", "resolve")}
        releasing = {(s.t0, s.t1) for t in traces for s in t.spans
                     if s.name == "embed" and t.meta["release"] == "early"}
        assert releasing
        assert len(per_batch["dispatch"]) == len(per_batch["embed"]) \
            == len(per_batch["resolve"]) - len(releasing)


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


class SlowBackend:
    """A backend that sleeps ``delay_s`` in every call before answering
    (or failing, with ``fail``), and records when each call returned."""

    def __init__(self, inner, delay_s: float = 0.3, fail: bool = False):
        self.inner = inner
        self.delay_s = delay_s
        self.fail = fail
        self.returned: list[float] = []

    def generate(self, queries, keys):
        time.sleep(self.delay_s)
        try:
            if self.fail:
                raise RuntimeError("backend down")
            return self.inner.generate(queries, keys)
        finally:
            self.returned.append(time.perf_counter())

    def __getattr__(self, name):
        return getattr(self.inner, name)


def mixed_requests(pairs, n_hits=3, n_misses=2):
    """Cached questions (hits) first, then distinct novel ones (misses)."""
    return ([Request(query=pairs[i].question, category=pairs[i].category,
                     source_id=pairs[i].qa_id,
                     semantic_key=pairs[i].semantic_key)
             for i in range(n_hits)]
            + [Request(query=q) for q in DISTINCT_QUERIES[:n_misses]])


def slow_engine(pairs, *, fail=False, **kw):
    eng = make_engine(pairs, **kw)
    eng.warm(pairs)
    eng.serve_batch([Request(query="compile warmup")],
                    record_path_latency=False)
    eng.backend = SlowBackend(eng.backend, fail=fail)
    return eng


def response_fields(r: Response) -> dict:
    d = dataclasses.asdict(r)
    del d["latency_s"], d["trace_id"]
    return d


class TestEarlyHitRelease:
    """A batch's hits are answered at its peek when its misses go to the
    backend (DESIGN.md §12.1); every future is still set exactly once."""

    def test_hits_and_waiters_done_before_the_backend_returns(self, pairs):
        eng = slow_engine(pairs)
        reqs = mixed_requests(pairs)
        done_at: dict[int, float] = {}

        async def drive():
            sched = SchedulerConfig(max_batch=16, max_wait_ms=20.0)
            server = AsyncCacheServer(eng, sched)
            async with server:
                async def timed(k, r):
                    resp = await server.submit_request(r)
                    done_at[k] = time.perf_counter()
                    return resp

                # each hit also has a duplicate that coalesces into it
                subs = list(enumerate(reqs + reqs[:3]))
                out = await asyncio.gather(*(timed(k, r) for k, r in subs))
            return out, server.scheduler.batches_served

        responses, batches = asyncio.run(drive())
        assert batches == 1
        (returned,) = eng.backend.returned
        hit_rows = [0, 1, 2, 5, 6, 7]
        for k in hit_rows:
            assert responses[k].cached and done_at[k] < returned
        assert sum(responses[k].coalesced for k in hit_rows) == 3
        for k in (3, 4):
            assert not responses[k].cached and done_at[k] >= returned
        assert eng.metrics.hits_released_early == 3
        assert eng.metrics.latency_samples["hit"].count == 3
        assert eng.metrics.latency_samples["coalesced"].count == 3
        assert eng.metrics.latency_samples["miss"].count == 2

    def test_early_responses_equal_the_sync_path(self, pairs):
        reqs = mixed_requests(pairs, n_hits=4, n_misses=3)
        sync_eng = make_engine(pairs)
        sync_eng.warm(pairs)
        want = sync_eng.process(reqs)

        eng = make_engine(pairs)
        eng.warm(pairs)
        got = []
        full = eng.serve_batch(
            reqs, on_hits_ready=lambda rows, rs: got.append((rows, rs)))
        (rows, early), = got
        assert rows == [0, 1, 2, 3]
        for i, r in zip(rows, early):
            assert response_fields(r) == response_fields(want[i])
        # the returned list still holds every row, as the sync path's
        assert [response_fields(r) for r in full] == \
            [response_fields(r) for r in want]

    def test_slab_and_stats_match_the_path_without_release(self, pairs):
        import jax
        reqs = mixed_requests(pairs, n_hits=4, n_misses=3)
        engines = []
        for callback in (None, lambda rows, rs: None):
            eng = make_engine(pairs)
            eng.warm(pairs)
            eng.serve_batch(reqs, on_hits_ready=callback)
            eng.serve_batch(reqs)       # the misses now hit
            engines.append(eng)
        plain, released = engines
        assert released.metrics.hits_released_early == 4
        for a, b in zip(jax.tree.leaves(plain.runtime),
                        jax.tree.leaves(released.runtime)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # every count and rate; the wall times differ run to run
        timed = ("latency_percentiles", "avg_latency_with_cache_s")
        a, b = plain.metrics.summary(), released.metrics.summary()
        assert {k: v for k, v in a.items() if k not in timed} == \
            {k: v for k, v in b.items() if k not in timed}

    def test_all_hit_batch_releases_nothing(self, pairs):
        eng = slow_engine(pairs)
        reqs = mixed_requests(pairs, n_hits=4, n_misses=0)
        calls = []
        eng.serve_batch(reqs, on_hits_ready=lambda *a: calls.append(a))

        async def drive():
            sched = SchedulerConfig(max_batch=16, max_wait_ms=20.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit_request(r) for r in reqs))

        assert all(r.cached for r in asyncio.run(drive()))
        assert calls == [] and eng.backend.returned == []
        assert eng.metrics.hits_released_early == 0

    def test_backend_failure_after_release_rejects_only_misses(self, pairs):
        eng = slow_engine(pairs, fail=True)
        reqs = mixed_requests(pairs)
        done_at: dict[int, float] = {}

        async def drive():
            sched = SchedulerConfig(max_batch=16, max_wait_ms=20.0)
            server = AsyncCacheServer(eng, sched)
            async with server:
                async def timed(k, r):
                    try:
                        return await server.submit_request(r)
                    finally:
                        done_at[k] = time.perf_counter()

                subs = list(enumerate(reqs + reqs))
                out = await asyncio.gather(
                    *(timed(k, r) for k, r in subs), return_exceptions=True)
            return out, server.scheduler

        results, sched = asyncio.run(drive())
        (returned,) = eng.backend.returned
        for k in (0, 1, 2, 5, 6, 7):
            assert isinstance(results[k], Response) and results[k].cached
            assert done_at[k] < returned
        for k in (3, 4, 8, 9):
            assert isinstance(results[k], BackendError)
        assert sched._pending == {}
        # one latency sample per future: none was resolved twice
        s = eng.metrics.latency_samples
        assert (s["hit"].count, s["coalesced"].count, s["error"].count) \
            == (3, 3, 4)

    def test_serve_batch_raising_after_release_fails_only_the_rest(
            self, pairs):
        eng = slow_engine(pairs)
        reqs = mixed_requests(pairs)

        def judge(req, sid):            # runs after the release
            raise ValueError("judge down")

        eng.judge = judge

        async def drive():
            sched = SchedulerConfig(max_batch=16, max_wait_ms=20.0)
            server = AsyncCacheServer(eng, sched)
            async with server:
                out = await asyncio.gather(
                    *(server.submit_request(r) for r in reqs + reqs),
                    return_exceptions=True)
            return out, server.scheduler

        results, sched = asyncio.run(drive())
        for k in (0, 1, 2, 5, 6, 7):
            assert isinstance(results[k], Response) and results[k].cached
        for k in (3, 4, 8, 9):
            assert isinstance(results[k], ValueError)
        assert sched._pending == {}

    def test_key_of_a_released_hit_can_lead_again(self, pairs):
        # once its hit is released, a leader's key is free: a duplicate
        # arriving during the batch's backend call leads the next batch,
        # and the end of the first batch leaves its waiters alone
        eng = slow_engine(pairs)
        reqs = mixed_requests(pairs, n_hits=1, n_misses=1)

        async def drive():
            sched = SchedulerConfig(max_batch=16, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                first = [asyncio.create_task(server.submit_request(r))
                         for r in reqs]
                await asyncio.wait_for(asyncio.shield(first[0]), 5.0)
                assert not first[1].done()      # its miss at the backend
                again = [asyncio.create_task(server.submit_request(reqs[0]))
                         for _ in range(3)]
                return await asyncio.gather(*first, *again)

        responses = asyncio.run(drive())
        assert all(r.answer for r in responses)
        assert [r.cached for r in responses] == [True, False, True, True,
                                                 True]
        assert [r.coalesced for r in responses[2:]] == [False, True, True]
        assert eng.metrics.hits_released_early == 1


class TestEmbedMemoCounters:
    """The embedder's feature-token memo, as the serve path counts it:
    lookups and memo hits in ``ServingMetrics``, on ``/metrics`` and on
    each served row's trace."""

    def test_warmed_engine_answers_every_lookup_from_the_memo(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs)
        eng.process([Request(query=p.question) for p in pairs[:40]])
        m = eng.metrics
        assert m.embed_features > 40
        assert m.embed_feature_memo_hits == m.embed_features

    def test_unseen_words_miss_the_memo(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs)
        eng.process([Request(query=q) for q in DISTINCT_QUERIES])
        m = eng.metrics
        assert 0 < m.embed_feature_memo_hits < m.embed_features

    def test_metrics_export_both_counters(self, pairs):
        from repro.obs.export import MetricsExporter
        eng = make_engine(pairs)
        eng.warm(pairs[:20])
        eng.process([Request(query=q) for q in
                     [pairs[0].question] + DISTINCT_QUERIES[:2]])
        rows = dict(ln.rsplit(" ", 1) for ln in
                    MetricsExporter(eng).render().splitlines()
                    if ln and not ln.startswith("#"))
        assert float(rows["repro_embed_features_total"]) == \
            eng.metrics.embed_features > 0
        assert float(rows["repro_embed_feature_memo_hits_total"]) == \
            eng.metrics.embed_feature_memo_hits > 0

    def test_each_served_row_carries_its_counts(self, pairs):
        from repro.obs import TraceConfig, Tracer
        eng = make_engine(pairs, tracer=Tracer(
            TraceConfig(sample_rate=1.0, head=0)))
        eng.warm(pairs[:20])
        known = [pairs[i].question for i in range(4)]

        async def serve():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=2.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q) for q in known + DISTINCT_QUERIES[:3]))

        asyncio.run(serve())
        traces = eng.tracer.traces()
        assert len(traces) == 7
        for t in traces:
            n, hits = t.meta["embed_features"], t.meta["embed_memo_hits"]
            assert 0 <= hits <= n and n > 0
            if t.meta["path"] == "hit":
                assert hits == n                # warmed: every token known
        assert sum(t.meta["embed_features"] for t in traces) == \
            eng.metrics.embed_features
        assert sum(t.meta["embed_memo_hits"] for t in traces) == \
            eng.metrics.embed_feature_memo_hits
