"""Decision parity of the sharded step vs the local step (DESIGN.md §19.6).

The tentpole contract: `DistributedCache.step` runs the SAME
`SemanticCache` body under `shard_map` with communication seams swapped
in, so on identical traffic it must make identical decisions — same
hit/near/miss masks, same served values/provenance, same counters, and a
bitwise-identical set of slab keys (entry *placement* differs by design:
shard-major round-robin vs the single global ring).

Everything runs in subprocesses on a forced >1-device CPU topology (see
tests/test_distributed.py); CI exports
XLA_FLAGS=--xla_force_host_platform_device_count=8 for the whole file.
"""
from test_distributed import run_with_devices

# Shared harness: drives identical multi-tenant band+fusion traffic
# through a local SemanticCache and a 4-shard DistributedCache, then
# asserts decision/counter/key parity. ``@INDEX@`` is substituted so the
# exact-index and sharded-IVF suites are literally the same program.
PARITY_HARNESS = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import SemanticCache, CacheConfig, DistributedCache
    from repro.context.fusion import DecayMeanFusion
    from repro.generative.policy import BandPolicy
    from repro.tenancy.registry import TenantRegistry, TenantSpec

    cfg = CacheConfig(dim=32, capacity=256, value_len=8, ttl=None,
                      threshold=0.8, topk=4)
    reg = TenantRegistry((
        TenantSpec(name="acme"),
        TenantSpec(name="zen", threshold=0.85, band_lo=0.65)))
    part = reg.partition(cfg.capacity)
    pol = BandPolicy(tau_lo=0.70, tau_hi=0.80)
    fus = DecayMeanFusion(window=3)
    index = @INDEX@
    make = lambda: SemanticCache(cfg, policy=pol, partition=part,
                                 fusion=fus, index=index)
    local = make()
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    dc = DistributedCache(make(), mesh)
    assert dc.num_shards == 4
    lrt, drt = local.init(), dc.init()

    lstep = jax.jit(lambda rt, q, mv, mvl, t, sid, valid, tid, w, wl:
                    local.step(rt, q, mv, mvl, t, source_id=sid,
                               valid=valid, tenant_id=tid, window=w,
                               window_len=wl))
    dstep = jax.jit(lambda rt, q, mv, mvl, t, sid, valid, tid, w, wl:
                    dc.step(rt, q, mv, mvl, t, source_id=sid,
                            valid=valid, tenant_id=tid, window=w,
                            window_len=wl))

    B, D, W = 16, 32, 3
    rng = np.random.default_rng(7)
    inserted = []          # queries already admitted, for paraphrase traffic
    for r in range(6):
        fresh = rng.standard_normal((B, D)).astype(np.float32)
        q = fresh.copy()
        if inserted:                      # paraphrase half the batch
            pool = np.concatenate(inserted)
            pick = rng.integers(0, len(pool), size=B // 2)
            q[: B // 2] = pool[pick] + \\
                0.05 * rng.standard_normal((B // 2, D)).astype(np.float32)
        mv = rng.integers(0, 99, size=(B, 8)).astype(np.int32)
        mvl = np.full((B,), 8, dtype=np.int32)
        sid = np.arange(r * B, (r + 1) * B, dtype=np.int32)
        valid = np.ones((B,), dtype=bool)
        valid[-2:] = r % 2 == 0           # exercise pad rows
        tid = rng.integers(0, 2, size=B).astype(np.int32)
        w = rng.standard_normal((B, W, D)).astype(np.float32)
        wl = rng.integers(0, W + 1, size=B).astype(np.int32)
        args = [jnp.asarray(a) for a in
                (q, mv, mvl, np.float32(r), sid, valid, tid, w, wl)]
        lres, lrt = lstep(lrt, *args)
        dres, drt = dstep(drt, *args)

        hit = np.asarray(lres.hit)
        np.testing.assert_array_equal(np.asarray(dres.hit), hit,
                                      err_msg=f"hit mask, round {r}")
        np.testing.assert_array_equal(np.asarray(dres.near),
                                      np.asarray(lres.near),
                                      err_msg=f"near mask, round {r}")
        np.testing.assert_allclose(np.asarray(dres.score),
                                   np.asarray(lres.score), atol=1e-5)
        np.testing.assert_array_equal(np.asarray(dres.values)[hit],
                                      np.asarray(lres.values)[hit])
        np.testing.assert_array_equal(np.asarray(dres.source_id)[hit],
                                      np.asarray(lres.source_id)[hit])
        # near-hit payloads: same neighbour sets (by provenance + score),
        # though under different global slot ids
        lpay = local.gather_topk(lrt, lres)
        dpay = dc.gather_topk(drt, dres)
        ls = np.sort(np.asarray(lpay["source_id"]), axis=1)
        ds = np.sort(np.asarray(dpay["source_id"]), axis=1)
        np.testing.assert_array_equal(ds, ls,
                                      err_msg=f"topk neighbours, round {r}")
        inserted.append(q[~hit & valid])

    # replicated stats: one global workload, counted once
    for f in ("lookups", "hits", "misses", "inserts"):
        assert int(getattr(drt.stats, f)) == int(getattr(lrt.stats, f)), f
    # sharded tenancy counters reduce to the local ones exactly
    red = drt.tenancy.reduced()
    for f in ("lookups", "hits", "inserts", "evictions"):
        np.testing.assert_array_equal(np.asarray(getattr(red, f)),
                                      np.asarray(getattr(lrt.tenancy, f)),
                                      err_msg=f)
    # the slabs hold the SAME entries (bitwise keys), placed differently
    lk = np.asarray(lrt.state.keys)[np.asarray(lrt.state.valid)]
    dk = np.asarray(drt.state.keys)[np.asarray(drt.state.valid)]
    assert sorted(r.tobytes() for r in lk) == \\
        sorted(r.tobytes() for r in dk)
    assert len(dk) == int(drt.stats.inserts)
    print("PARITY-OK", len(dk))
"""


class TestShardedParity:
    def test_full_feature_parity_exact_index(self):
        """Tenancy + per-tenant overrides + band policy + context fusion,
        exact index, 4-shard mesh: bitwise decision/key parity."""
        out = run_with_devices(PARITY_HARNESS.replace("@INDEX@", "None"))
        assert "PARITY-OK" in out

    def test_full_feature_parity_sharded_ivf(self):
        """The ExactIndex-only restriction is gone: a *leafy* IVF index
        runs per-shard over local slot ids. With nprobe == ncentroids the
        probe is exhaustive, so IVF must reproduce the exact-index
        decisions bit for bit — same parity suite, same assertions."""
        out = run_with_devices(PARITY_HARNESS.replace(
            "@INDEX@",
            "__import__('repro.core.index', fromlist=['IVFIndex'])"
                  ".IVFIndex(ncentroids=4, nprobe=4, bucket_cap=256, "
                  "topk=4, kmeans_iters=2)"))
        assert "PARITY-OK" in out

    def test_round_robin_balance_under_masked_inserts(self):
        """Regression for the raw-row-index routing bug: with insert masks
        selecting rows {0,4,8,12} of a 16-row batch on 4 shards, the old
        `(n_inserts + row) % num_shards` rule sends EVERY masked-in row of
        every batch to the same shard (row ≡ 0 mod 4 and n_inserts grows
        by 4 per batch). Routing by the cumulative count of masked-in rows
        keeps the shards balanced."""
        out = run_with_devices("""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core import SemanticCache, CacheConfig, \\
                DistributedCache
            cfg = CacheConfig(dim=16, capacity=128, value_len=4, ttl=None)
            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("data",))
            dc = DistributedCache(SemanticCache(cfg), mesh)
            rt = dc.init()
            ins = jax.jit(lambda rt, q, v, vl, t, m:
                          dc.insert(rt, q, v, vl, t, mask=m))
            mask = np.zeros((16,), dtype=bool)
            mask[::4] = True                     # adversarial: rows 0,4,8,12
            v = jnp.zeros((16, 4), jnp.int32)
            vl = jnp.full((16,), 4, jnp.int32)
            for b in range(8):
                q = jax.random.normal(jax.random.PRNGKey(b), (16, 16))
                rt = ins(rt, q, v, vl, jnp.float32(b), jnp.asarray(mask))
            per_shard = np.asarray(rt.state.valid).reshape(4, -1).sum(axis=1)
            assert int(rt.state.n_inserts) == 32
            assert (per_shard == 8).all(), per_shard   # 32 inserts / 4 shards
            print("BALANCE-OK", per_shard.tolist())
        """)
        assert "BALANCE-OK" in out

    def test_reshard_on_load(self, tmp_path):
        """Checkpoint round-trips across shard counts (§19.5): a snapshot
        taken single-device restores onto a 4-shard mesh (and back), keeps
        serving the same hits, preserves per-tenant accounting, and the
        strict path refuses the layout mismatch."""
        out = run_with_devices(f"""
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.types import CacheConfig
            from repro.data.qa_dataset import build_corpus
            from repro.serving import (CachedEngine, Request,
                                       SimulatedLLMBackend)
            from repro.tenancy.registry import TenantRegistry

            pairs = build_corpus(80, seed=0)
            reg = TenantRegistry.uniform(("acme", "zen"))
            cfg = CacheConfig(dim=384, capacity=512, value_len=48,
                              ttl=None, threshold=0.8)
            mk = lambda mesh: CachedEngine(
                cfg, SimulatedLLMBackend(pairs), batch_size=8,
                registry=reg, mesh=mesh)

            e1 = mk(None)
            e1.warm(pairs[:40], tenant="acme")
            e1.warm(pairs[40:], tenant="zen")
            reqs = [Request(query=p.question, tenant="acme",
                            source_id=p.qa_id) for p in pairs[:8]]
            assert all(r.cached for r in e1.process(reqs))
            snap = {str(tmp_path / "snap")!r}
            e1.save_cache(snap)
            stats1 = e1.tenant_stats()

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((4,), ("data",))
            e2 = mk(mesh)
            try:
                e2.load_cache(snap, reshard=False)
                raise AssertionError("strict load accepted a 1->4 restore")
            except ValueError as err:
                assert "shard" in str(err)
            e2.load_cache(snap)                    # reshard 1 -> 4
            # entries really are spread over the 4 shard slices now
            per_shard = np.asarray(
                e2.runtime.state.valid).reshape(4, -1).sum(axis=1)
            assert (per_shard > 0).all(), per_shard
            assert all(r.cached for r in e2.process(reqs))
            stats2 = e2.tenant_stats()
            for t in ("acme", "zen"):
                assert stats2[t]["inserts"] == stats1[t]["inserts"], t

            # and back down: 4-shard snapshot onto a single device
            snap2 = {str(tmp_path / "snap4")!r}
            e2.save_cache(snap2)
            e3 = mk(None)
            e3.load_cache(snap2)                   # reshard 4 -> 1
            assert all(r.cached for r in e3.process(reqs))
            assert e3.tenant_stats()["zen"]["inserts"] == \\
                stats1["zen"]["inserts"]
            print("RESHARD-OK")
        """)
        assert "RESHARD-OK" in out
