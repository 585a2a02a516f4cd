"""Multi-tenant cache namespaces (DESIGN.md §13): partition map, isolation,
one-compiled-step acceptance, per-tenant accounting, DRR admission fairness,
tenant-scoped coalescing, checkpointing, and the multi-tenant loadgen."""
import asyncio
import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CacheConfig, SemanticCache
from repro.data.qa_dataset import build_corpus
from repro.serving import (AsyncCacheServer, CachedEngine, Request, Response,
                           SchedulerConfig, ServingMetrics,
                           SimulatedLLMBackend, build_multi_tenant_workload,
                           coalesce_key, normalize_query, tenant_rng,
                           zipf_weights)
from repro.tenancy import (NO_OVERRIDE, PartitionMap, TenancyState,
                           TenantRegistry, TenantSpec)


@pytest.fixture(scope="module")
def pairs():
    return build_corpus(80, seed=0)


def mk_registry(*specs):
    return TenantRegistry(tuple(specs))


def mk_cache(capacity=256, dim=32, registry=None, **kw):
    kw.setdefault("ttl", None)
    cfg = CacheConfig(dim=dim, capacity=capacity, value_len=8, **kw)
    part = registry.partition(capacity) if registry else None
    return SemanticCache(cfg, partition=part), cfg


def corpus(rng, n, dim):
    k1, k2 = jax.random.split(rng)
    emb = jax.random.normal(k1, (n, dim))
    vals = jax.random.randint(k2, (n, 8), 0, 100)
    return emb, vals, jnp.full((n,), 8)


def mk_engine(pairs, registry, *, batch_size=16, capacity=None, **kw):
    by_id = {p.qa_id: p for p in pairs}

    def judge(req, sid):
        return sid >= 0 and sid in by_id and \
            by_id[sid].semantic_key == req.semantic_key

    cfg = CacheConfig(
        dim=384,
        capacity=capacity or 2048 * (len(registry) if registry else 1),
        value_len=48, ttl=None, threshold=0.8)
    return CachedEngine(cfg, SimulatedLLMBackend(pairs), judge=judge,
                        batch_size=batch_size, registry=registry, **kw)


# --------------------------------------------------------------------- #
# registry + partition map
# --------------------------------------------------------------------- #
class TestPartitionMap:
    def test_shares_quotas_cover_slab_exactly(self):
        reg = mk_registry(TenantSpec("a", share=2.0),
                          TenantSpec("b", share=1.0),
                          TenantSpec("c", quota=100))
        part = reg.partition(1000)
        assert part.sizes[part.index("c")] == 100
        a, b = part.sizes[part.index("a")], part.sizes[part.index("b")]
        assert a + b == 900 and abs(a - 2 * b) <= 2
        # contiguous, ordered, exact cover (enforced by PartitionMap too)
        assert sum(part.sizes) == part.capacity == 1000
        assert part.starts == (0, part.sizes[0],
                               part.sizes[0] + part.sizes[1])
        owner = part.slot_owner()
        for t, (s, z) in enumerate(zip(part.starts, part.sizes)):
            assert (owner[s:s + z] == t).all()

    def test_allocation_is_order_independent(self):
        """Regression: a quota tenant declared after a share tenant must
        not starve it to zero slots — slot reservation counts every unsized
        tenant, wherever it appears in the declaration order."""
        ab = mk_registry(TenantSpec("a", share=1.0),
                         TenantSpec("b", quota=100)).partition(100)
        ba = mk_registry(TenantSpec("b", quota=100),
                         TenantSpec("a", share=1.0)).partition(100)
        assert ab.sizes[ab.index("a")] == ba.sizes[ba.index("a")] == 1
        assert ab.sizes[ab.index("b")] == ba.sizes[ba.index("b")] == 99

    def test_thresholds_and_weights_round_trip(self):
        reg = mk_registry(TenantSpec("a", threshold=0.9, weight=3.0),
                          TenantSpec("b"))
        part = reg.partition(64)
        assert part.thresholds == (0.9, NO_OVERRIDE)
        assert reg.weights() == {"a": 3.0, "b": 1.0}

    def test_validation(self):
        with pytest.raises(ValueError):
            mk_registry(TenantSpec("a"), TenantSpec("a"))      # dup name
        with pytest.raises(ValueError):
            TenantSpec("x", share=0.0)
        with pytest.raises(ValueError):
            TenantSpec("x", threshold=1.5)
        with pytest.raises(ValueError):
            mk_registry(TenantSpec("a"), TenantSpec("b")).partition(1)
        with pytest.raises(ValueError):
            PartitionMap(names=("a",), starts=(1,), sizes=(3,),
                         thresholds=(-1.0,), capacity=4)       # gap at 0

    def test_partitioned_cache_rejects_lru(self):
        reg = TenantRegistry.uniform(["a", "b"])
        with pytest.raises(ValueError, match="ring"):
            mk_cache(registry=reg, eviction="lru")


# --------------------------------------------------------------------- #
# core isolation + accounting (raw SemanticCache)
# --------------------------------------------------------------------- #
class TestIsolation:
    def test_identical_query_cached_by_a_misses_for_b(self):
        """Acceptance criterion: cosine similarity 1.0 across tenants is
        still a miss — other tenants' entries are invisible, not merely
        sub-threshold."""
        reg = TenantRegistry.uniform(["a", "b"])
        c, cfg = mk_cache(registry=reg)
        rt = c.init()
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 8, cfg.dim)
        ta = jnp.zeros((8,), jnp.int32)
        tb = jnp.ones((8,), jnp.int32)
        _, rt = c.step(rt, emb, vals, lens, 0.0, tenant_id=ta)
        res_a, rt = c.lookup(rt, emb, 1.0, tenant_id=ta)
        assert bool(res_a.hit.all())
        np.testing.assert_allclose(np.asarray(res_a.score), 1.0, atol=1e-5)
        res_b, rt = c.lookup(rt, emb, 1.0, tenant_id=tb)
        assert not bool(res_b.hit.any())
        # the B rows saw an empty region: score is -inf, not ~1.0
        assert bool((np.asarray(res_b.score) == -np.inf).all())

    def test_adversarial_identical_queries_in_one_batch(self, pairs):
        """Same bytes, different tenants, same micro-batch: each tenant
        pays its own miss, then hits only its own region's entry."""
        reg = TenantRegistry.uniform(["a", "b"])
        eng = mk_engine(pairs, reg, batch_size=8)
        q = "is there a student discount on the tenancy test plan"
        batch = [Request(query=q, tenant="a"), Request(query=q, tenant="b")]
        first = eng.process(batch)
        assert [r.cached for r in first] == [False, False]
        again = eng.process(batch)
        assert [r.cached for r in again] == [True, True]
        # each hit resolved inside its own region
        part = eng.cache.partition
        owner = part.slot_owner()
        valid = np.asarray(eng.state.valid)
        assert valid[owner == 0].sum() == 1 and valid[owner == 1].sum() == 1

    def test_per_tenant_threshold_override(self):
        reg = mk_registry(TenantSpec("lax"),
                          TenantSpec("strict", threshold=0.99))
        c, cfg = mk_cache(registry=reg, threshold=0.8)
        rt = c.init()
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 4, cfg.dim)
        for tid in (jnp.zeros((4,), jnp.int32), jnp.ones((4,), jnp.int32)):
            rt = c.insert(rt, emb, vals, lens, 0.0, tenant_id=tid)
        # perturb so cosine lands between 0.8 and 0.99
        noisy = emb + 0.25 * jax.random.normal(jax.random.PRNGKey(1),
                                               emb.shape)
        res_l, rt = c.lookup(rt, noisy, 1.0,
                             tenant_id=jnp.zeros((4,), jnp.int32))
        res_s, rt = c.lookup(rt, noisy, 1.0,
                             tenant_id=jnp.ones((4,), jnp.int32))
        score = np.asarray(res_l.score)
        assert (score > 0.8).all() and (score < 0.99).all(), score
        assert bool(res_l.hit.all())        # cache-wide 0.8 applies
        assert not bool(res_s.hit.any())    # 0.99 override applies

    def test_ring_eviction_stays_inside_own_region(self):
        reg = mk_registry(TenantSpec("small", quota=16), TenantSpec("big"))
        c, cfg = mk_cache(capacity=64, registry=reg)
        rt = c.init()
        bemb, bvals, blens = corpus(jax.random.PRNGKey(0), 8, cfg.dim)
        big = jnp.ones((8,), jnp.int32)
        rt = c.insert(rt, bemb, bvals, blens, 0.0, tenant_id=big)
        # flood 'small' with 48 distinct rows through its 16-slot region
        small = jnp.zeros((8,), jnp.int32)
        for i in range(6):
            semb, svals, slens = corpus(jax.random.PRNGKey(10 + i), 8,
                                        cfg.dim)
            rt = c.insert(rt, semb, svals, slens, 1.0 + i, tenant_id=small)
        # big's entries are untouched by the neighbour's churn
        res, rt = c.lookup(rt, bemb, 10.0, tenant_id=big)
        assert bool(res.hit.all())
        owner = reg.partition(64).slot_owner()
        valid = np.asarray(rt.state.valid)
        assert valid[owner == 0].sum() == 16      # region full, wrapped
        assert valid[owner == 1].sum() == 8
        t = rt.tenancy
        assert int(t.inserts[0]) == 48
        assert int(t.evictions[0]) == 32          # 48 inserts - 16 slots
        assert int(t.evictions[1]) == 0

    def test_partitioned_cache_requires_tenant_id(self):
        reg = TenantRegistry.uniform(["a", "b"])
        c, cfg = mk_cache(registry=reg)
        rt = c.init()
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 4, cfg.dim)
        with pytest.raises(ValueError, match="tenant_id"):
            c.lookup(rt, emb, 0.0)
        with pytest.raises(ValueError, match="tenant_id"):
            c.insert(rt, emb, vals, lens, 0.0)

    def test_unpartitioned_cache_ignores_tenancy(self):
        c, cfg = mk_cache()
        rt = c.init()
        assert rt.tenancy is None
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 4, cfg.dim)
        res, rt = c.step(rt, emb, vals, lens, 0.0)
        assert rt.tenancy is None and int(res.hit.sum()) == 0

    def test_empty_region_tenant_is_structural_miss(self):
        """Satellite: a tenant whose region has zero live slots gets
        (-inf, -1, no hit) — not an arbitrary slot with a masked score."""
        reg = TenantRegistry.uniform(["seeded", "empty"])
        c, cfg = mk_cache(registry=reg)
        rt = c.init()
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 4, cfg.dim)
        rt = c.insert(rt, emb, vals, lens, 0.0,
                      tenant_id=jnp.zeros((4,), jnp.int32))
        res, rt = c.lookup(rt, emb, 1.0,
                           tenant_id=jnp.ones((4,), jnp.int32))
        assert bool((np.asarray(res.score) == -np.inf).all())
        assert not bool(res.hit.any())
        assert bool((np.asarray(res.topk_index) == -1).all())
        assert bool((np.asarray(res.topk_score) == -np.inf).all())

    def test_ivf_index_under_tenancy_matches_exact(self):
        """The interval operands flow through ANY Index plugin: IVF with
        full probing agrees with exact search on a partitioned cache —
        isolation included (a cosine-1.0 duplicate in the other region is
        invisible on both paths)."""
        from repro.core.index import IVFIndex
        reg = TenantRegistry.uniform(["a", "b"])
        cap, dim = 128, 32
        cfg = CacheConfig(dim=dim, capacity=cap, value_len=8, ttl=None)
        part = reg.partition(cap)
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 8, dim)
        ta = jnp.zeros((8,), jnp.int32)
        tb = jnp.ones((8,), jnp.int32)
        probe = emb + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                               emb.shape)
        results = {}
        for name, index in (
                ("exact", None),
                ("ivf", IVFIndex(ncentroids=4, nprobe=4, bucket_cap=cap,
                                 topk=4))):
            c = SemanticCache(cfg, index=index, partition=part)
            rt = c.init()
            rt = c.insert(rt, emb, vals, lens, 0.0, tenant_id=ta)
            rt = c.refit(rt, 0.0, jax.random.PRNGKey(2))
            res_a, rt = c.lookup(rt, probe, 1.0, tenant_id=ta)
            res_b, rt = c.lookup(rt, probe, 1.0, tenant_id=tb)
            results[name] = (res_a, res_b)
        ex_a, ex_b = results["exact"]
        iv_a, iv_b = results["ivf"]
        np.testing.assert_array_equal(np.asarray(ex_a.index),
                                      np.asarray(iv_a.index))
        np.testing.assert_allclose(np.asarray(ex_a.score),
                                   np.asarray(iv_a.score), rtol=1e-5,
                                   atol=1e-5)
        # tenant b sees nothing on either path: cross-tenant isolation
        for res in (ex_b, iv_b):
            assert bool((np.asarray(res.score) == -np.inf).all())
            assert not bool(res.hit.any())


# --------------------------------------------------------------------- #
# one compiled program + padding hygiene (engine)
# --------------------------------------------------------------------- #
class TestCompiledStepSharing:
    def test_no_recompile_across_tenant_mixes(self, pairs):
        """Acceptance criterion: the tenant_id vector is traced, so every
        tenant mix shares ONE compiled fused step."""
        reg = TenantRegistry.uniform(["a", "b", "c"])
        eng = mk_engine(pairs, reg, batch_size=8)
        eng.process([Request(query=f"probe a{i}", tenant="a")
                     for i in range(8)])
        traces = eng._step_jit._cache_size()
        assert traces == 1
        eng.process([Request(query=f"probe m{i}",
                             tenant=["a", "b", "c"][i % 3])
                     for i in range(8)])
        eng.process([Request(query=f"probe c{i}", tenant="c")
                     for i in range(3)])      # padded partial batch
        assert eng._step_jit._cache_size() == traces
        assert eng._peek_jit._cache_size() == 1

    # mutually dissimilar (share almost no n-grams): numbered variants of
    # one template would legitimately hit each other at threshold 0.8
    DISTINCT = [
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
    ]

    def test_padded_mixed_batch_counters_clean(self, pairs):
        reg = TenantRegistry.uniform(["a", "b"])
        eng = mk_engine(pairs, reg, batch_size=8)
        tenants = ["a", "b", "a", "b", "a", "b", "a", "b", "a", "b", "a"]
        reqs = [Request(query=q, category="python_basics", tenant=t)
                for q, t in zip(self.DISTINCT, tenants)]  # 11: one padded
        responses = eng.process(reqs)
        assert len(responses) == 11
        s = eng.metrics.summary()
        assert s["queries"] == 11
        assert "__pad__" not in s["categories"]
        # host-side per-tenant == device-side per-tenant == request counts
        dev = eng.tenant_stats()
        assert dev["a"]["lookups"] == 6 and dev["b"]["lookups"] == 5
        assert s["tenants"]["a"]["lookups"] == 6
        assert s["tenants"]["b"]["lookups"] == 5
        assert int(eng.stats.lookups) == 11
        assert dev["a"]["inserts"] == 6 and dev["b"]["inserts"] == 5
        assert int(np.sum(np.asarray(eng.state.valid))) == 11
        # second pass: all hits, each within its own tenant
        again = eng.process(reqs)
        assert all(r.cached for r in again)
        dev = eng.tenant_stats()
        assert dev["a"]["hits"] == 6 and dev["b"]["hits"] == 5

    def test_fused_and_separate_paths_agree_with_tenants(self, pairs):
        reg = TenantRegistry.uniform(["a", "b"])
        wl = build_multi_tenant_workload(pairs, 48, tenants=["a", "b"],
                                         skew=0.5, seed=3)
        results = {}
        for fused in (True, False):
            eng = mk_engine(pairs, reg, batch_size=16, use_fused_step=fused)
            for t in ("a", "b"):
                eng.warm(pairs[:40], tenant=t)
            resp = eng.process(wl)
            results[fused] = (
                [(r.answer, r.cached, round(r.score, 5)) for r in resp],
                eng.tenant_stats())
        assert results[True] == results[False]

    def test_engine_rejects_region_smaller_than_batch(self, pairs):
        reg = mk_registry(TenantSpec("tiny", quota=4), TenantSpec("rest"))
        with pytest.raises(ValueError, match="region"):
            mk_engine(pairs, reg, batch_size=16, capacity=4096)

    def test_engine_rejects_oversized_admission_batch(self, pairs):
        """Regression: a mis-aligned scheduler max_batch could hand a
        partitioned engine more rows than a region holds — the per-tenant
        ring would silently collide slots, so serve_batch fails loudly."""
        reg = TenantRegistry.uniform(["a", "b"])
        eng = mk_engine(pairs, reg, batch_size=8)
        with pytest.raises(ValueError, match="max_batch"):
            eng.serve_batch([Request(query=f"q{i}", tenant="a")
                             for i in range(9)])
        # single-tenant engines keep accepting oversized batches (they
        # just retrace): the guard is tenancy-only
        eng1 = mk_engine(pairs, None, batch_size=8)
        assert len(eng1.serve_batch(
            [Request(query=f"q{i}") for i in range(9)])) == 9


# --------------------------------------------------------------------- #
# checkpointing
# --------------------------------------------------------------------- #
class TestTenancyCheckpoint:
    def test_roundtrip_restores_tenancy_and_partition(self, pairs, tmp_path):
        reg = mk_registry(TenantSpec("a", share=2.0),
                          TenantSpec("b", threshold=0.9))
        eng = mk_engine(pairs, reg, batch_size=8)
        eng.warm(pairs[:30], tenant="a")
        eng.process([Request(query=p.question, tenant="a")
                     for p in pairs[:8]])
        eng.process([Request(query="b tenant novel question", tenant="b")])
        path = os.path.join(str(tmp_path), "tenancy.npz")
        eng.save_cache(path)

        eng2 = mk_engine(pairs, reg, batch_size=8)
        eng2.load_cache(path)
        for a, b in zip(jax.tree_util.tree_leaves(eng.runtime.tenancy),
                        jax.tree_util.tree_leaves(eng2.runtime.tenancy)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert eng2.tenant_stats() == eng.tenant_stats()
        # restored engine serves tenant-a hits, tenant-b still isolated
        hit = eng2.process([Request(query=pairs[0].question, tenant="a")])[0]
        miss = eng2.process([Request(query=pairs[0].question, tenant="b")])[0]
        assert hit.cached and not miss.cached

    def test_partition_mismatch_rejected(self, pairs, tmp_path):
        reg = TenantRegistry.uniform(["a", "b"])
        eng = mk_engine(pairs, reg, batch_size=8)
        path = os.path.join(str(tmp_path), "part.npz")
        eng.save_cache(path)
        other = mk_registry(TenantSpec("a", share=3.0), TenantSpec("b"))
        eng2 = mk_engine(pairs, other, batch_size=8)
        with pytest.raises(ValueError, match="partition"):
            eng2.load_cache(path)


# --------------------------------------------------------------------- #
# scheduler: DRR fairness, per-tenant backpressure, tenant coalescing
# --------------------------------------------------------------------- #
class _FakeEngine:
    """Duck-typed stand-in recording batch compositions; the scheduler only
    touches ``serve_batch``, ``metrics``, ``tracer`` and (optionally)
    ``registry``."""

    def __init__(self, delay_s=0.0):
        from repro.obs import Tracer
        self.metrics = ServingMetrics()
        self.registry = None
        self.tracer = Tracer()          # collection off, like the engine's
        self.delay_s = delay_s          # default
        self.batches: list[list[str]] = []

    def serve_batch(self, batch, record_path_latency=True, traces=None,
                    on_hits_ready=None):
        self.batches.append([r.tenant for r in batch])
        if self.delay_s:
            time.sleep(self.delay_s)
        return [Response(answer=f"ok:{r.query}", cached=False, score=0.0,
                         latency_s=0.0) for r in batch]


class TestDRRFairness:
    def test_flooding_tenant_cannot_monopolize_batches(self):
        """One tenant floods 64 requests; a second tenant's 8 arrive after.
        DRR must interleave: the mouse finishes within a couple of batches
        instead of queueing behind the whole flood."""
        eng = _FakeEngine(delay_s=0.02)
        sched = SchedulerConfig(max_batch=8, max_wait_ms=1000.0,
                                coalesce=False)
        done_order: list[str] = []

        async def drive():
            async with AsyncCacheServer(eng, sched) as server:
                async def timed(r):
                    await server.submit_request(r)
                    done_order.append(r.tenant)
                hog = [asyncio.create_task(timed(
                    Request(query=f"hog {i}", tenant="hog")))
                    for i in range(64)]
                await asyncio.sleep(0.015)   # first batch dispatched, rest queued
                mouse = [asyncio.create_task(timed(
                    Request(query=f"mouse {i}", tenant="mouse")))
                    for i in range(8)]
                await asyncio.gather(*hog, *mouse)

        asyncio.run(drive())
        assert len(done_order) == 72
        # every mouse request completed before the last 16 hog requests
        last_mouse = max(i for i, t in enumerate(done_order) if t == "mouse")
        hogs_after = sum(1 for t in done_order[last_mouse + 1:]
                         if t == "hog")
        assert hogs_after >= 16, (last_mouse, hogs_after)
        # contended batches are split, not hog-only
        mixed = [b for b in eng.batches if "mouse" in b]
        assert mixed and all(b.count("mouse") <= 5 for b in mixed)

    def test_weights_bias_the_split(self):
        """Weight-3 tenant takes ~3x the slots of a weight-1 tenant while
        both are backlogged."""
        eng = _FakeEngine(delay_s=0.02)
        sched = SchedulerConfig(max_batch=8, max_wait_ms=1000.0,
                                coalesce=False,
                                tenant_weights={"vip": 3.0, "std": 1.0})

        async def drive():
            async with AsyncCacheServer(eng, sched) as server:
                tasks = [asyncio.create_task(server.submit_request(
                    Request(query=f"v{i}", tenant="vip")))
                    for i in range(32)]
                tasks += [asyncio.create_task(server.submit_request(
                    Request(query=f"s{i}", tenant="std")))
                    for i in range(32)]
                await asyncio.gather(*tasks)

        asyncio.run(drive())
        contended = [b for b in eng.batches
                     if "vip" in b and "std" in b and len(b) == 8]
        assert contended
        vip = sum(b.count("vip") for b in contended)
        std = sum(b.count("std") for b in contended)
        assert vip >= 2 * std, (vip, std)

    def test_per_tenant_backpressure_forces_flush(self):
        """A tenant at its own queue bound blocks and forces flushes; the
        run completes (no deadlock) with bounded per-tenant residency."""
        eng = _FakeEngine()
        sched = SchedulerConfig(max_batch=4, max_queue=1024,
                                max_queue_per_tenant=4,
                                max_wait_ms=5_000.0, coalesce=False)

        async def drive():
            async with AsyncCacheServer(eng, sched) as server:
                await asyncio.gather(*(server.submit_request(
                    Request(query=f"q{i}", tenant="x")) for i in range(16)))

        asyncio.run(drive())
        assert sum(len(b) for b in eng.batches) == 16
        # forced flushes kept batches at/below the per-tenant bound
        assert all(len(b) <= 4 for b in eng.batches)


class TestTenantCoalescing:
    def test_normalize_query(self):
        assert normalize_query("  How  do I\tSort a LIST \n") == \
            "how do i sort a list"
        r1 = Request(query="How  Do I sort", tenant="t")
        r2 = Request(query="how do i sort ", tenant="t")
        r3 = Request(query="how do i sort", tenant="u")
        assert coalesce_key(r1) == coalesce_key(r2)
        assert coalesce_key(r1) != coalesce_key(r3)

    def test_trivially_different_duplicates_coalesce(self, pairs):
        """Satellite regression: whitespace/case variants share one leader
        (one backend call), the first step toward embedding-similarity
        coalescing."""
        eng = mk_engine(pairs, None, batch_size=8)
        variants = ["what is the WARRANTY on the doodad",
                    "  what is the warranty on the doodad ",
                    "What is the Warranty  on the doodad"]

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0)
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(v) for v in variants * 4))

        responses = asyncio.run(herd())
        assert eng.backend.calls == 1
        assert sum(r.coalesced for r in responses) == 11
        assert len({r.answer for r in responses}) == 1

    def test_identical_queries_do_not_coalesce_across_tenants(self, pairs):
        reg = TenantRegistry.uniform(["a", "b"])
        eng = mk_engine(pairs, reg, batch_size=8)
        q = "do identical cross tenant questions stay separate"

        async def herd():
            sched = SchedulerConfig(max_batch=8, max_wait_ms=5.0,
                                    tenant_weights=reg.weights())
            async with AsyncCacheServer(eng, sched) as server:
                return await asyncio.gather(
                    *(server.submit(q, tenant=t)
                      for t in ("a", "b", "a", "b")))

        responses = asyncio.run(herd())
        # one leader per tenant -> 2 backend calls, 2 coalesced waiters
        assert eng.backend.calls == 2
        assert sum(r.coalesced for r in responses) == 2
        dev = eng.tenant_stats()
        assert dev["a"]["lookups"] == 1 and dev["b"]["lookups"] == 1


# --------------------------------------------------------------------- #
# loadgen: per-(seed, tenant) streams
# --------------------------------------------------------------------- #
class TestMultiTenantLoadgen:
    def test_tenant_rng_is_stable_and_per_tenant(self):
        a1 = [tenant_rng(7, "acme").random() for _ in range(1)][0]
        a2 = tenant_rng(7, "acme").random()
        b = tenant_rng(7, "globex").random()
        assert a1 == a2 and a1 != b
        assert tenant_rng(8, "acme").random() != a1

    def test_zipf_weights(self):
        w = zipf_weights(4, skew=1.0)
        assert w[0] > w[1] > w[2] > w[3]
        assert abs(sum(w) - 1.0) < 1e-9
        assert zipf_weights(3, skew=0.0) == pytest.approx([1 / 3] * 3)

    def test_adding_a_tenant_never_perturbs_another_stream(self, pairs):
        """Satellite: tenant A's request sequence is a pure function of
        (seed, tenant, n_requests) — growing the tenant set changes only
        the interleaving, never what an existing tenant asks."""
        wl_ab = build_multi_tenant_workload(
            pairs, 240, tenants=["a", "b"], skew=1.0, seed=5)
        wl_abc = build_multi_tenant_workload(
            pairs, 240, tenants=["a", "b", "c"], skew=1.0, seed=5)
        for t in ("a", "b"):
            seq2 = [r.query for r in wl_ab if r.tenant == t]
            seq3 = [r.query for r in wl_abc if r.tenant == t]
            k = min(len(seq2), len(seq3))
            assert k > 10
            assert seq2[:k] == seq3[:k]

    def test_skew_concentrates_traffic(self, pairs):
        wl = build_multi_tenant_workload(
            pairs, 400, tenants=["big", "mid", "tail"], skew=1.5, seed=2)
        counts = {t: sum(r.tenant == t for r in wl)
                  for t in ("big", "mid", "tail")}
        assert counts["big"] > counts["mid"] > counts["tail"]
        assert len(wl) == 400

    def test_bursts_stay_within_tenant(self, pairs):
        wl = build_multi_tenant_workload(
            pairs, 200, tenants=["a", "b"], skew=0.0, burst_prob=1.0,
            burst_size=4, seed=9)
        # consecutive identical queries always share a tenant
        for r1, r2 in zip(wl, wl[1:]):
            if r1.query == r2.query:
                assert r1.tenant == r2.tenant


# --------------------------------------------------------------------- #
# runtime pytree integration
# --------------------------------------------------------------------- #
class TestTenancyRuntime:
    def test_tenancy_state_is_pytree_leaf_group(self):
        reg = TenantRegistry.uniform(["a", "b"])
        c, cfg = mk_cache(registry=reg)
        rt = c.init()
        assert isinstance(rt.tenancy, TenancyState)
        leaves, treedef = jax.tree_util.tree_flatten(rt)
        rt2 = jax.tree_util.tree_unflatten(treedef, leaves)
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 4, cfg.dim)
        jitted = jax.jit(lambda r, q, v, l, t, tid: c.step(
            r, q, v, l, t, tenant_id=tid))
        _, rt2 = jitted(rt2, emb, vals, lens, jnp.float32(0.0),
                        jnp.zeros((4,), jnp.int32))
        assert int(rt2.tenancy.inserts[0]) == 4
        assert int(rt2.tenancy.inserts[1]) == 0

    def test_counted_lookup_matches_peek_commit_accounting(self):
        """peek -> commit must account per-tenant identically to a counted
        lookup (the engine's fused path vs the reference path)."""
        reg = TenantRegistry.uniform(["a", "b"])
        c, cfg = mk_cache(registry=reg)
        emb, vals, lens = corpus(jax.random.PRNGKey(0), 6, cfg.dim)
        tid = jnp.asarray([0, 1, 0, 1, 0, 1], jnp.int32)

        def prime():
            rt = c.init()
            return c.insert(rt, emb, vals, lens, 0.0, tenant_id=tid)

        _, rt_a = c.lookup(prime(), emb, 1.0, tenant_id=tid)
        rt = prime()
        peek, _ = c.lookup(rt, emb, 1.0, update_counters=False,
                           tenant_id=tid)
        _, rt_b = c.commit(rt, peek, 1.0, tenant_id=tid)
        for f in ("lookups", "hits"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rt_a.tenancy, f)),
                np.asarray(getattr(rt_b.tenancy, f)))
        assert int(rt_a.tenancy.lookups.sum()) == 6
