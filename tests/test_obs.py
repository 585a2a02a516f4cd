"""Observability subsystem tests (DESIGN.md §18): request tracing with
retention sampling, decision attribution (``why`` records + ``explain``),
the metrics export plane (event ring, Prometheus exposition, /metrics
endpoint), the bounded latency reservoirs, and the additive wire
discipline on the TCP front-end."""
import asyncio
import glob
import json
import os
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.types import CacheConfig
from repro.data.qa_dataset import build_corpus
from repro.generative import BandPolicy, TemplateSplice
from repro.obs import (NULL_TRACE, REQUIRED_FAMILIES, STAGES, EventLog,
                       MetricsExporter, RequestTrace, StageClock,
                       TraceConfig, Tracer, compile_log, effective_edges,
                       prometheus_text)
from repro.serving import (AsyncCacheServer, CachedEngine, Request,
                           SchedulerConfig, SimulatedLLMBackend)
from repro.serving.metrics import (LATENCY_BUCKETS_S, LatencyReservoir,
                                   NearHitMetrics, ServingMetrics,
                                   percentiles)
from repro.tenancy import TenantRegistry, TenantSpec


@pytest.fixture(scope="module")
def pairs():
    return build_corpus(80, seed=0)


def make_engine(pairs, *, batch_size=8, latency_s=0.0, **kw):
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    cfg = kw.pop("config", CacheConfig(dim=384, capacity=4096, value_len=48,
                                       ttl=None, threshold=0.8))
    backend = SimulatedLLMBackend(pairs, latency_per_call_s=latency_s)
    return CachedEngine(cfg, backend, judge=judge,
                        batch_size=batch_size, **kw)


def collect_all() -> Tracer:
    return Tracer(TraceConfig(sample_rate=1.0, head=0))


def finished_trace(tracer, e2e_s=0.0):
    t = tracer.start()
    tracer.finish(t, e2e_s=e2e_s)
    return t


# --------------------------------------------------------------------- #
# tracer: retention sampling (§18.2)
# --------------------------------------------------------------------- #
class TestTracerRetention:
    def test_head_always_retained(self):
        tr = Tracer(TraceConfig(sample_rate=0.0, head=3))
        kept = [finished_trace(tr) for _ in range(10)]
        assert tr.finished == 10
        assert tr.retained == 3
        assert [t.trace_id for t in tr.traces()] == \
            [t.trace_id for t in kept[:3]]

    def test_rate_sampling_is_deterministic(self):
        tr = Tracer(TraceConfig(sample_rate=0.25, head=0, max_traces=1024))
        for _ in range(100):
            finished_trace(tr)
        # counter-accumulator, no RNG: exactly one in four, every run
        assert tr.retained == 25
        tr2 = Tracer(TraceConfig(sample_rate=0.25, head=0, max_traces=1024))
        for _ in range(100):
            finished_trace(tr2)
        assert [t.trace_id for t in tr2.traces()] == \
            [t.trace_id for t in tr.traces()]

    def test_slow_outliers_kept_despite_zero_rate(self):
        tr = Tracer(TraceConfig(sample_rate=0.0, head=0,
                                slow_threshold_s=0.5))
        finished_trace(tr, e2e_s=0.01)
        slow = finished_trace(tr, e2e_s=0.75)
        finished_trace(tr, e2e_s=0.1)
        assert tr.retained == 1
        assert tr.traces()[0].trace_id == slow.trace_id

    def test_ring_keeps_most_recent(self):
        tr = Tracer(TraceConfig(sample_rate=1.0, head=0, max_traces=4))
        kept = [finished_trace(tr) for _ in range(10)]
        assert tr.retained == 10            # retention counter is total ...
        assert [t.trace_id for t in tr.traces()] == \
            [t.trace_id for t in kept[-4:]]  # ... ring holds the tail

    def test_off_allocates_nothing(self):
        tr = Tracer(TraceConfig.off())
        assert not tr.collecting
        t = tr.start()
        assert t is NULL_TRACE and not t
        assert t.trace_id == ""
        t.add("embed", 0.0, 1.0)            # all hooks are no-ops
        t.annotate(row=3)
        assert t.spans == [] and t.meta == {}
        assert tr.stage_clock("embed") is None
        tr.finish(t, e2e_s=1.0)
        assert tr.finished == tr.retained == 0

    def test_drain_clears_ring(self):
        tr = collect_all()
        finished_trace(tr)
        finished_trace(tr)
        out = tr.drain()
        assert len(out) == 2 and all("trace_id" in d for d in out)
        assert tr.traces() == [] and tr.drain() == []

    def test_config_validation(self):
        with pytest.raises(ValueError):
            TraceConfig(sample_rate=1.5)
        with pytest.raises(ValueError):
            TraceConfig(head=-1)
        with pytest.raises(ValueError):
            TraceConfig(max_traces=0)
        with pytest.raises(ValueError):
            TraceConfig(slow_threshold_s=-0.1)

    def test_stage_decomposition_orders_canonically(self):
        tr = collect_all()
        t = tr.start()
        t.add("respond", 0.0, 0.1)
        t.add("embed", 0.1, 0.3)
        t.add("zz_custom", 0.3, 0.4)
        tr.finish(t)
        d = tr.stage_decomposition()
        assert list(d) == ["embed", "respond", "zz_custom"]
        assert d["embed"]["count"] == 1
        assert d["embed"]["p50_s"] == pytest.approx(0.2, abs=1e-6)
        assert d["embed"]["total_s"] == pytest.approx(0.2, abs=1e-6)


class TestStageClockAndTrace:
    def test_clock_spans_are_contiguous(self):
        clock = StageClock("embed")
        for name in ("device_step", "respond"):
            time.sleep(0.001)
            clock.enter(name)
        time.sleep(0.001)
        clock.close()
        spans = clock.spans
        assert [s.name for s in spans] == ["embed", "device_step", "respond"]
        for a, b in zip(spans, spans[1:]):
            assert a.t1 == b.t0            # no gaps, no overlaps
        assert all(s.duration_s > 0 for s in spans)

    def test_trace_round_trip(self):
        t = RequestTrace("rt-test")
        t.add("embed", 1.0, 1.5)
        t.add("embed", 2.0, 2.25)
        t.add("respond", 3.0, 3.1)
        t.annotate(path="hit", row=0)
        t.e2e_s = 0.85
        assert t.span_sum_s == pytest.approx(0.85)
        assert t.stage_seconds() == pytest.approx(
            {"embed": 0.75, "respond": 0.1})
        d = t.to_dict()
        assert d["trace_id"] == "rt-test"
        assert d["e2e_s"] == pytest.approx(0.85)
        assert d["meta"] == {"path": "hit", "row": 0}
        assert [s["name"] for s in d["spans"]] == \
            ["embed", "embed", "respond"]
        json.dumps(d)                      # JSON-able for /traces

    def test_child_spans_are_excluded_from_span_sum(self):
        t = RequestTrace("rt-child")
        t.add("device_step", 1.0, 1.5)
        t.add("peek_sync", 1.2, 1.5, parent="device_step")
        t.add("insert", 1.5, 1.75)
        t.add("step_sync", 1.6, 1.7, parent="insert")
        assert t.span_sum_s == pytest.approx(0.75)
        d = t.to_dict()
        assert [s.get("parent") for s in d["spans"]] == \
            [None, "device_step", None, "insert"]

    def test_clock_children_nest_in_the_open_stage(self):
        clock = StageClock("device_step")
        with clock.child("peek_sync") as sp:
            time.sleep(0.001)
        clock.enter("insert")
        clock.close()
        names = [(s.name, s.parent) for s in clock.spans]
        assert names == [("device_step", None),
                         ("peek_sync", "device_step"), ("insert", None)]
        step = clock.spans[0]
        assert step.t0 <= sp.t0 < sp.t1 <= step.t1


# --------------------------------------------------------------------- #
# engine integration: sync serve path (§18.1)
# --------------------------------------------------------------------- #
class TestEngineTracing:
    def test_sync_process_traces_every_row(self, pairs):
        eng = make_engine(pairs, tracer=collect_all())
        eng.warm(pairs[:20])
        reqs = [Request(query=pairs[i].question, category=pairs[i].category,
                        source_id=pairs[i].qa_id,
                        semantic_key=pairs[i].semantic_key)
                for i in range(6)]
        eng.process(reqs)
        assert eng.tracer.retained == len(reqs)
        for t in eng.tracer.traces():
            assert set(s.name for s in t.spans) <= set(STAGES)
            assert t.meta["path"] in ("hit", "near", "miss")
            assert t.e2e_s is not None and t.e2e_s > 0
            # contiguous engine spans tile the batch wall time: the span
            # sum reconstructs the measured e2e (the serve-bench invariant)
            assert t.span_sum_s == pytest.approx(t.e2e_s, rel=0.10)
        decomp = eng.tracer.stage_decomposition()
        assert {"embed", "device_step", "respond"} <= set(decomp)

    def test_tracing_off_by_default_and_allocation_free(self, pairs):
        eng = make_engine(pairs)               # no tracer argument
        assert not eng.tracer.collecting
        eng.process([Request(query="off-path probe")])
        assert eng.tracer.finished == 0
        assert eng.tracer.traces() == []

    def test_ready_t_marks_when_each_answer_was_known(self, pairs):
        eng = make_engine(pairs, tracer=collect_all())
        eng.warm(pairs[:20])
        reqs = [Request(query=pairs[i].question,
                        semantic_key=pairs[i].semantic_key,
                        source_id=pairs[i].qa_id) for i in range(3)]
        reqs += [Request(query=q) for q in (
            "entirely unrelated question about submarines",
            "best sourdough starter feeding schedule")]
        eng.process(reqs)
        paths = set()
        for t in eng.tracer.traces():
            end = {s.name: s.t1 for s in t.spans}
            want = "peek_sync" if t.meta["path"] == "hit" else "backend_call"
            assert t.meta["ready_t"] == end[want]
            paths.add(t.meta["path"])
            # the children the fused path records, inside their stages
            par = {s.name: s.parent for s in t.spans if s.parent}
            assert par == {"peek_sync": "device_step",
                           "step_sync": "insert", "fetch": "respond",
                           "policy": "respond"}
        assert paths == {"hit", "miss"}

    def test_reference_path_records_its_own_syncs(self, pairs):
        eng = make_engine(pairs, tracer=collect_all(), use_fused_step=False)
        eng.warm(pairs[:10])
        eng.process([Request(query=pairs[0].question),
                     Request(query="how tall is mount kilimanjaro")])
        for t in eng.tracer.traces():
            par = {s.name: s.parent for s in t.spans if s.parent}
            assert par == {"peek_sync": "device_step", "fetch": "respond",
                           "policy": "respond"}
            assert t.span_sum_s == pytest.approx(t.e2e_s, rel=1e-9)

    def test_tracing_off_makes_no_profiler_annotation(self, pairs,
                                                      monkeypatch):
        def refuse(*a, **kw):
            raise AssertionError("TraceAnnotation made with tracing off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        eng = make_engine(pairs)
        eng.warm(pairs[:10])
        eng.process([Request(query=pairs[0].question),
                     Request(query="why is the sky blue at noon")])

        async def serve():
            async with AsyncCacheServer(eng, SchedulerConfig(
                    max_batch=8, max_wait_ms=1.0)) as server:
                return await asyncio.gather(
                    *(server.submit(pairs[i].question) for i in range(4)))

        assert len(asyncio.run(serve())) == 4
        # and the same serve path does make them once the tracer collects
        eng.tracer = collect_all()
        with pytest.raises(AssertionError, match="tracing off"):
            eng.process([Request(query=pairs[0].question)])

    def test_profile_holds_host_stages_on_its_clock(self, pairs, tmp_path):
        eng = make_engine(pairs, tracer=collect_all())
        eng.warm(pairs[:20])
        batch = [Request(query=pairs[0].question),
                 Request(query="do tides depend on the moon")]
        eng.process(batch)                     # compile outside the profile
        jax.profiler.start_trace(str(tmp_path))
        eng.process(batch)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True)[0]
        data = jax.profiler.ProfileData.from_file(path)
        stages = ["serve.embed", "serve.device_step", "serve.near_synthesis",
                  "serve.backend_call", "serve.insert", "serve.respond"]
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = [e for e in line.events
                           if e.name.startswith("serve.")]
                    if evs:
                        break
                else:
                    continue
                break
        else:
            pytest.fail("no serve.* events on any host plane")
        top = [e for e in evs if e.name in stages]
        assert [e.name for e in top] == stages
        window = None
        for plane in data.planes:
            if plane.name == "Task Environment":
                with warnings.catch_warnings():
                    # jax's stats type lacks a __module__; harmless
                    warnings.simplefilter("ignore", DeprecationWarning)
                    stats = dict(plane.stats)
                window = (int(stats["profile_stop_time"])
                          - int(stats["profile_start_time"]))
        assert window is not None
        for a, b in zip(top, top[1:]):
            assert a.start_ns + a.duration_ns <= b.start_ns + 1e5
        assert 0 <= top[0].start_ns
        assert top[-1].start_ns + top[-1].duration_ns <= window
        names = {e.name for e in evs}
        assert {"serve.peek_sync", "serve.step_sync", "serve.fetch",
                "serve.policy"} <= names


# --------------------------------------------------------------------- #
# compile counter and device program names
# --------------------------------------------------------------------- #
class TestCompilesAndPrograms:
    def test_compile_counter_new_shape_once_repeat_never(self):
        log = compile_log()
        assert compile_log() is log            # one listener per process
        f = jax.jit(lambda x: x * 3.0 + 1.0)
        n0 = log.lowerings
        t0 = time.perf_counter()
        f(np.ones(7, np.float32))
        assert log.lowerings == n0 + 1
        f(np.ones(7, np.float32))
        assert log.lowerings == n0 + 1
        f(np.ones(9, np.float32))
        assert log.lowerings == n0 + 2
        spans = log.spans(t0, time.perf_counter())
        assert [s.kind for s in spans].count("lowering") == 2
        assert all(t0 <= s.t0 <= s.t1 for s in spans)

    def test_engine_programs_are_named(self, pairs):
        eng = make_engine(pairs)
        b = eng.batcher.batch_size
        emb = jnp.zeros((b, 384), jnp.float32)
        now = jnp.float32(0.0)
        text = eng._peek_jit.lower(eng.runtime, emb, now, None, None,
                                   None).as_text()
        assert "jit_peek" in text
        peek = eng._peek_jit(eng.runtime, emb, now, None, None, None)
        text = eng._step_jit.lower(
            eng.runtime, emb, jnp.zeros((b, 48), jnp.int32),
            jnp.zeros((b,), jnp.int32), now, jnp.zeros((b,), jnp.int32),
            peek, jnp.ones((b,), bool), None, None, None).as_text()
        assert "jit_step" in text and "lambda" not in text

    def test_metrics_export_the_compile_count(self, pairs):
        eng = make_engine(pairs)
        text = MetricsExporter(eng).render()
        row = [ln for ln in text.splitlines()
               if ln.startswith("repro_xla_compiles_total ")]
        assert int(row[0].split()[1]) == compile_log().lowerings


# --------------------------------------------------------------------- #
# decision attribution (§18.3)
# --------------------------------------------------------------------- #
class TestExplain:
    def test_explain_hit_record(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs[:20])
        lookups0 = int(eng.stats.lookups)
        why = eng.explain(pairs[0].question)
        assert why["decision"] == "hit"
        assert why["dry_run"] is True
        assert why["effective_threshold"] == pytest.approx(0.8)
        assert why["threshold_source"] == "policy"
        assert why["band"] is None             # band-less policy
        assert why["score"] >= why["effective_threshold"]
        assert why["matched_source_id"] == pairs[0].qa_id
        assert why["topk"], "top-k neighbours must be attributed"
        assert why["topk"][0]["score"] == pytest.approx(why["score"])
        assert all(t["slot"] >= 0 for t in why["topk"])
        # pure peek: no counters moved, nothing inserted
        assert int(eng.stats.lookups) == lookups0

    def test_explain_miss_record(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs[:20])
        why = eng.explain("entirely unrelated question about submarines")
        assert why["decision"] == "miss"
        assert why["score"] < why["effective_threshold"]

    def test_tenant_threshold_override_attributed(self, pairs):
        registry = TenantRegistry((TenantSpec(name="acme", threshold=0.95),
                                   TenantSpec(name="globex")))
        eng = make_engine(pairs, registry=registry,
                          config=CacheConfig(dim=384, capacity=4096,
                                             value_len=48, ttl=None,
                                             threshold=0.8))
        eng.warm(pairs[:10], tenant="acme")
        why = eng.explain(pairs[0].question, tenant="acme")
        assert why["threshold_source"] == "tenant"
        assert why["effective_threshold"] == pytest.approx(0.95)
        assert why["tenant"] == "acme"
        why_g = eng.explain(pairs[0].question, tenant="globex")
        assert why_g["threshold_source"] == "policy"
        assert why_g["effective_threshold"] == pytest.approx(0.8)

    def test_band_edges_attributed(self, pairs):
        eng = make_engine(pairs, policy=BandPolicy(tau_lo=0.7, tau_hi=0.8),
                          synthesizer=TemplateSplice())
        eng.warm(pairs[:10])
        why = eng.explain(pairs[0].question)
        assert why["band"] == {"lo": pytest.approx(0.7),
                               "hi": pytest.approx(0.8),
                               "lo_source": "policy"}

    def test_effective_edges_tenant_band_lo(self, pairs):
        registry = TenantRegistry(
            (TenantSpec(name="acme", threshold=0.9, band_lo=0.8),
             TenantSpec(name="globex")))
        policy = BandPolicy(tau_lo=0.7, tau_hi=0.85)
        partition = registry.partition(1024)
        edges = effective_edges(policy, policy.init_state(), partition, 0)
        assert edges == {"threshold": pytest.approx(0.9),
                         "threshold_source": "tenant",
                         "band": {"lo": pytest.approx(0.8),
                                  "hi": pytest.approx(0.9),
                                  "lo_source": "tenant"}}
        edges_g = effective_edges(policy, policy.init_state(), partition, 1)
        assert edges_g["threshold_source"] == "policy"
        assert edges_g["band"]["lo_source"] == "policy"

    def test_request_explain_opt_in_per_row(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs[:20])
        reqs = [Request(query=pairs[0].question, explain=True),
                Request(query=pairs[1].question)]
        r_opt, r_plain = eng.process(reqs)
        assert r_opt.why is not None
        assert r_opt.why["decision"] == "hit"
        assert r_opt.why["session_fused"] is False
        assert r_plain.why is None and r_plain.trace_id == ""

    def test_explain_responses_forces_every_row(self, pairs):
        eng = make_engine(pairs, explain_responses=True)
        eng.warm(pairs[:20])
        rs = eng.process([Request(query=pairs[0].question),
                          Request(query="novel submarine question")])
        assert rs[0].why["decision"] == "hit"
        assert rs[1].why["decision"] == "miss"


# --------------------------------------------------------------------- #
# event ring + Prometheus exposition (§18.4)
# --------------------------------------------------------------------- #
class TestEventLog:
    def test_bounded_ring_with_total_count(self):
        log = EventLog(capacity=4)
        for i in range(10):
            log.emit("step", n=i)
        assert len(log) == 4
        assert log.emitted == 10
        assert [e["n"] for e in log.events()] == [6, 7, 8, 9]
        assert [e["seq"] for e in log.events()] == [6, 7, 8, 9]

    def test_jsonl_and_drain(self):
        log = EventLog(capacity=8)
        log.emit("a", x=1)
        log.emit("b", y="two")
        lines = log.to_jsonl().splitlines()
        assert [json.loads(ln)["kind"] for ln in lines] == ["a", "b"]
        drained = log.drain()
        assert len(drained) == 2 and len(log) == 0
        assert log.to_jsonl() == ""

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            EventLog(capacity=0)

    def test_engine_emits_serve_events_with_stats_delta(self, pairs):
        eng = make_engine(pairs, events=EventLog(capacity=16))
        eng.warm(pairs[:10])
        eng.process([Request(query=pairs[0].question),
                     Request(query="a brand new submarine question")])
        evs = [e for e in eng.events.events() if e["kind"] == "serve_batch"]
        assert evs, "serve_batch events must be emitted"
        ev = evs[-1]
        assert ev["rows"] == 2
        assert ev["hits"] == 1 and ev["backend_calls"] == 1
        assert ev["stats_delta"]["lookups"] == 2
        assert ev["stats_delta"]["inserts"] == 1


def scrape_families(text: str) -> set:
    fams = set()
    for line in text.splitlines():
        if line.startswith("# TYPE "):
            fams.add(line.split()[2])
    return fams


def histogram_rows(text: str, family: str) -> dict:
    """path-label -> [(le, cumulative_count)] parsed off the exposition."""
    out: dict[str, list] = {}
    for line in text.splitlines():
        if not line.startswith(family + "_bucket{"):
            continue
        labels = line[line.index("{") + 1:line.index("}")]
        kv = dict(p.split("=", 1) for p in labels.split(","))
        path = kv.get("path", "").strip('"')
        le = kv["le"].strip('"')
        val = float(line.rsplit(" ", 1)[1])
        out.setdefault(path, []).append(
            (float("inf") if le == "+Inf" else float(le), val))
    return out


class TestPrometheusExposition:
    def test_required_families_always_present(self):
        # even a freshly-constructed stack (no traffic, no cache stats)
        # emits every contractual family — scrapers must never see a
        # family appear/disappear between scrapes
        text = prometheus_text(ServingMetrics())
        fams = scrape_families(text)
        missing = [f for f in REQUIRED_FAMILIES if f not in fams]
        assert not missing, missing

    def test_engine_scrape_histogram_invariants(self, pairs):
        eng = make_engine(pairs, tracer=collect_all())
        eng.warm(pairs[:10])
        eng.process([Request(query=pairs[i].question) for i in range(4)])
        text = MetricsExporter(eng).render()
        assert "# TYPE repro_latency_seconds histogram" in text
        hist = histogram_rows(text, "repro_latency_seconds")
        assert "hit" in hist
        for path, rows in hist.items():
            les = [le for le, _ in rows]
            counts = [c for _, c in rows]
            assert les == sorted(les) and les[-1] == float("inf")
            assert counts == sorted(counts), "buckets must be cumulative"
            # the +Inf bucket equals the series _count
            count_line = [ln for ln in text.splitlines()
                          if ln.startswith("repro_latency_seconds_count")
                          and f'path="{path}"' in ln]
            assert float(count_line[0].rsplit(" ", 1)[1]) == counts[-1]
        # device plane + trace plane ride along on a live engine
        assert "repro_slab_hits_total" in text
        assert "repro_trace_stage_seconds" in text
        assert 'stage="device_step"' in text

    def test_per_tenant_labels(self, pairs):
        registry = TenantRegistry.uniform(["acme", "globex"])
        eng = make_engine(pairs, registry=registry)
        eng.warm(pairs[:10], tenant="acme")
        eng.warm(pairs[:10], tenant="globex")
        eng.process([Request(query=pairs[0].question, tenant="acme"),
                     Request(query=pairs[1].question, tenant="globex")])
        eng.metrics.record_latency("hit", 0.002, tenant="acme")
        text = MetricsExporter(eng).render()
        assert 'repro_tenant_lookups_total{tenant="acme"}' in text
        assert 'repro_tenant_lookups_total{tenant="globex"}' in text
        assert 'repro_tenant_slab_inserts_total{tenant="acme"}' in text
        assert 'tenant="acme",path="hit",quantile="0.5"' in text

    def test_label_escaping(self):
        m = ServingMetrics()
        m.record_batch(['weird"cat\n'], [0], [0], judged=None,
                       cache_time_s=0.0, llm_time_s=0.0, llm_cost=0.0,
                       baseline_cost=0.0, baseline_time=0.0)
        text = prometheus_text(m)
        assert 'category="weird\\"cat\\n"' in text


# --------------------------------------------------------------------- #
# bounded latency reservoirs (§18.5, satellite: no unbounded buffers)
# --------------------------------------------------------------------- #
class TestLatencyReservoir:
    def test_memory_stays_bounded_under_sustained_load(self):
        res = LatencyReservoir(cap=64)
        n = 10_000
        for i in range(n):
            res.add(i / n)
        assert len(res) == 64, "reservoir must not grow past cap"
        assert res.count == n                 # exact scalars keep counting
        assert res.total_s == pytest.approx(sum(i / n for i in range(n)))
        assert res.summary()["count"] == n    # true stream length reported
        assert sum(c for _, c in res.bucket_rows()) == n

    def test_small_stream_is_exact(self):
        res = LatencyReservoir(cap=2048)
        xs = [0.001 * i for i in range(1, 101)]
        for x in xs:
            res.add(x)
        assert res.summary() == {**percentiles(xs), "count": 100}

    def test_reservoir_percentiles_track_distribution(self):
        res = LatencyReservoir(cap=256, seed=7)
        for i in range(20_000):
            res.add((i % 1000) / 1000.0)      # uniform on [0, 1)
        s = res.summary()
        assert abs(s["p50_s"] - 0.5) < 0.15   # statistical, seeded -> stable
        assert s["p95_s"] > s["p50_s"]

    def test_bucket_rows_shape(self):
        res = LatencyReservoir()
        res.add(0.0001)                        # first bucket
        res.add(100.0)                         # +Inf bucket
        rows = res.bucket_rows()
        assert len(rows) == len(LATENCY_BUCKETS_S) + 1
        assert rows[0] == (LATENCY_BUCKETS_S[0], 1)
        assert rows[-1] == (float("inf"), 1)

    def test_cap_validation(self):
        with pytest.raises(ValueError):
            LatencyReservoir(cap=0)

    def test_serving_metrics_buffers_are_bounded(self):
        # regression: record_latency used to append to an unbounded list
        m = ServingMetrics()
        for i in range(5000):
            m.record_latency("hit", 0.001, tenant="acme")
        res = m.latency_samples["hit"]
        assert isinstance(res, LatencyReservoir)
        assert len(res) <= res.cap < 5000
        t_res = m.per_tenant["acme"].latency_samples["hit"]
        assert len(t_res) <= t_res.cap < 5000
        assert m.summary()["latency_percentiles"]["hit"]["count"] == 5000


# --------------------------------------------------------------------- #
# summary() edge cases (satellite: zero-division / empty-path hygiene)
# --------------------------------------------------------------------- #
class TestSummaryEdgeCases:
    def test_fresh_metrics_summary_is_all_zeros(self):
        s = ServingMetrics().summary()
        assert s["queries"] == 0
        assert s["categories"] == {} and s["tenants"] == {}
        assert s["context"] == {} and s["near"] == {}
        assert s["latency_percentiles"] == {}
        assert s["avg_latency_with_cache_s"] == 0.0
        assert s["avg_latency_without_cache_s"] == 0.0

    def test_zero_sample_percentiles(self):
        assert percentiles([]) == {"count": 0, "p50_s": 0.0,
                                   "p95_s": 0.0, "p99_s": 0.0}
        assert LatencyReservoir().summary()["count"] == 0

    def test_unknown_path_names_open_fresh_reservoirs(self):
        m = ServingMetrics()
        m.record_latency("some_future_path", 0.01)
        row = m.summary()["latency_percentiles"]["some_future_path"]
        assert row["count"] == 1 and row["p50_s"] == pytest.approx(0.01)

    def test_tenant_with_only_coalesced_traffic_no_zero_division(self):
        m = ServingMetrics()
        m.record_coalesced(3, tenant="idle")
        row = m.summary()["tenants"]["idle"]
        assert row["lookups"] == 0 and row["hit_rate"] == 0.0
        assert row["coalesced_calls"] == 3

    def test_near_metrics_judged_zero_precision(self):
        nm = NearHitMetrics(band=5, served=2, judged=0)
        assert nm.precision == 0.0
        assert nm.row()["near_precision"] == 0.0
        # via the full record_batch path: band rows but nothing judged
        m = ServingMetrics()
        m.record_batch(["c"], [0], [0], judged=[0], cache_time_s=0.0,
                       llm_time_s=0.0, llm_cost=0.0, baseline_cost=0.0,
                       baseline_time=0.0, nears=[1], near_served=[0])
        assert m.summary()["near"]["near_precision"] == 0.0
        assert m.summary()["near"]["band_lookups"] == 1


# --------------------------------------------------------------------- #
# wire discipline: additive observability keys (§18 + server docstring)
# --------------------------------------------------------------------- #
class TestWireDiscipline:
    BASE_KEYS = {"answer", "cached", "score", "latency_s", "coalesced",
                 "id"}

    def run_client(self, eng, lines):
        async def client():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                try:
                    port = await server.serve_tcp("127.0.0.1", 0)
                except OSError as exc:       # sandboxed CI without sockets
                    pytest.skip(f"cannot bind loopback: {exc}")
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                for obj in lines:
                    writer.write(json.dumps(obj).encode() + b"\n")
                await writer.drain()
                out = [json.loads(await reader.readline())
                       for _ in range(len(lines))]
                writer.close()
                return out

        return asyncio.run(client())

    def test_non_opt_in_payload_is_byte_identical_shape(self, pairs):
        # the engine runs with tracing + events + attribution fully on;
        # a client that did not ask must still get exactly the
        # pre-observability payload keys — nothing rides along uninvited
        eng = make_engine(pairs, tracer=collect_all(),
                          events=EventLog(capacity=64))
        eng.warm(pairs[:10])
        out = self.run_client(eng, [
            {"id": 0, "query": pairs[0].question},
            {"id": 1, "query": pairs[1].question, "explain": False}])
        by_id = {o["id"]: o for o in out}
        for o in by_id.values():
            assert set(o) == self.BASE_KEYS
        # and the exact serialized line is reconstructible from those
        # keys alone: no observability value leaks into the bytes
        line = json.dumps(by_id[0])
        assert "why" not in line and "trace_id" not in line

    def test_explain_opt_in_rides_per_line(self, pairs):
        eng = make_engine(pairs, tracer=collect_all())
        eng.warm(pairs[:10])
        out = self.run_client(eng, [
            {"id": 0, "query": pairs[0].question, "explain": True},
            {"id": 1, "query": pairs[1].question}])
        by_id = {o["id"]: o for o in out}
        assert set(by_id[0]) == self.BASE_KEYS | {"why", "trace_id"}
        assert by_id[0]["why"]["decision"] == "hit"
        assert by_id[0]["trace_id"].startswith("rt-")
        assert set(by_id[1]) == self.BASE_KEYS

    def test_explain_without_tracer_has_empty_trace_id(self, pairs):
        eng = make_engine(pairs)              # tracing off
        eng.warm(pairs[:10])
        out = self.run_client(eng, [
            {"id": 0, "query": pairs[0].question, "explain": True}])
        assert out[0]["why"]["decision"] == "hit"
        assert out[0]["trace_id"] == ""


# --------------------------------------------------------------------- #
# /metrics endpoint (§18.4): dedicated listener + main-port GET sniff
# --------------------------------------------------------------------- #
async def http_get(port: int, path: str) -> tuple[str, str]:
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    writer.write(f"GET {path} HTTP/1.1\r\nHost: x\r\n\r\n".encode())
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.decode().partition("\r\n\r\n")
    return head, body


class TestMetricsEndpoint:
    def test_dedicated_listener_serves_all_routes(self, pairs):
        eng = make_engine(pairs, tracer=collect_all(),
                          events=EventLog(capacity=64))
        eng.warm(pairs[:10])

        async def go():
            async with AsyncCacheServer(eng) as server:
                try:
                    port = await server.serve_metrics()
                except OSError as exc:
                    pytest.skip(f"cannot bind loopback: {exc}")
                await server.submit(pairs[0].question)
                return {
                    "metrics": await http_get(port, "/metrics"),
                    "traces": await http_get(port, "/traces"),
                    "events": await http_get(port, "/events"),
                    "missing": await http_get(port, "/nope"),
                }

        out = asyncio.run(go())
        head, body = out["metrics"]
        assert head.startswith("HTTP/1.1 200 OK")
        assert "text/plain; version=0.0.4" in head
        fams = scrape_families(body)
        assert all(f in fams for f in REQUIRED_FAMILIES)
        head, body = out["traces"]
        assert head.startswith("HTTP/1.1 200 OK")
        traces = [json.loads(ln) for ln in body.splitlines()]
        assert traces and all("spans" in t for t in traces)
        head, body = out["events"]
        assert head.startswith("HTTP/1.1 200 OK")
        assert any(json.loads(ln)["kind"] == "serve_batch"
                   for ln in body.splitlines())
        assert out["missing"][0].startswith("HTTP/1.1 404")

    def test_main_port_sniffs_http_scrape(self, pairs):
        eng = make_engine(pairs)
        eng.warm(pairs[:10])

        async def go():
            async with AsyncCacheServer(eng) as server:
                try:
                    port = await server.serve_tcp("127.0.0.1", 0)
                except OSError as exc:
                    pytest.skip(f"cannot bind loopback: {exc}")
                # JSON-lines clients are unaffected ...
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", port)
                writer.write(json.dumps(
                    {"query": pairs[0].question}).encode() + b"\n")
                await writer.drain()
                resp = json.loads(await reader.readline())
                writer.close()
                # ... while a GET on the same port returns the exposition
                return resp, await http_get(port, "/metrics")

        resp, (head, body) = asyncio.run(go())
        assert resp["cached"] is True
        assert head.startswith("HTTP/1.1 200 OK")
        assert "repro_queries_total" in body
