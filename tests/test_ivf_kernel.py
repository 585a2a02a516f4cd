"""Fused Pallas IVF search (DESIGN.md §15) vs the jnp oracles.

Three-way parity — fused kernel vs jnp IVF vs the exact oracle on
fully-probed configs — plus the visibility contract on every edge the
serving path produces: int8 slabs, per-row tenancy intervals, empty-region
tenants, B > block_b, all-dead buckets, and recycled-slot duplicates.
Runs on CPU (kernel in interpret mode) and under REPRO_PALLAS_INTERPRET=1,
where ops.ivf_topk and IVFIndex(backend='auto') dispatch to the kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.index import (ExactIndex, ExactState, IVFIndex, IVFState,
                              _absorb_serial, dedup_candidates)
from repro.kernels import ops, ref
from repro.kernels.ivf_topk import ivf_topk_pallas


def _unit(rng, shape):
    x = jax.random.normal(rng, shape)
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def _slab_int8(keys):
    """The cache slab's uniform symmetric quantization (store.insert)."""
    return jnp.clip(jnp.round(keys * 127.0), -127, 127).astype(jnp.int8)


def _fitted(ivf, keys, valid, seed=2):
    return ivf.fit(keys, valid, jax.random.PRNGKey(seed))


def _near_queries(keys, b, noise_seed=1, noise=0.05):
    q = keys[:b] + noise * jax.random.normal(jax.random.PRNGKey(noise_seed),
                                             (b, keys.shape[1]))
    return q / jnp.linalg.norm(q, axis=1, keepdims=True)


def _check(expected, got, rtol=1e-5, atol=1e-5):
    (rs, ri), (ps, pi) = expected, got
    np.testing.assert_allclose(np.asarray(rs), np.asarray(ps),
                               rtol=rtol, atol=atol)
    np.testing.assert_array_equal(np.asarray(ri), np.asarray(pi))


class TestKernelVsOracle:
    """ivf_topk_pallas (interpret) vs ref.ivf_topk_ref on shared candidate
    sets — the kernel's numerical contract, independent of the index."""

    @pytest.mark.parametrize("b,n,m,d,k", [
        (1, 64, 16, 16, 1),
        (4, 100, 48, 32, 4),      # non-multiple M
        (7, 300, 130, 64, 2),     # M > block_m: merge across candidate tiles
        (20, 256, 96, 48, 4),     # B > block_b: row blocks
        (33, 512, 256, 128, 8),   # B and M both cross blocks
    ])
    @pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
    def test_matches_oracle(self, b, n, m, d, k, dtype):
        r = jax.random.PRNGKey(b * 7919 + m)
        k1, k2, k3, k4 = jax.random.split(r, 4)
        q = _unit(k1, (b, d))
        keys = _unit(k2, (n, d))
        if dtype == "bf16":
            keys = keys.astype(jnp.bfloat16)
        elif dtype == "int8":
            keys = _slab_int8(keys)
        cand = jax.random.randint(k3, (b, m), 0, n, dtype=jnp.int32)
        visible = jax.random.bernoulli(k4, 0.8, (b, m))
        visible = dedup_candidates(cand, visible)
        cand = jnp.where(visible, cand, -1)
        exp = ref.ivf_topk_ref(q, keys, cand, k)
        got = ivf_topk_pallas(q, keys, cand, k=k, interpret=True)
        tol = 2e-2 if dtype == "bf16" else 1e-5
        _check(exp, got, rtol=tol, atol=tol)

    def test_all_masked_rows_return_empty_contract(self):
        q = _unit(jax.random.PRNGKey(0), (5, 16))
        keys = _unit(jax.random.PRNGKey(1), (64, 16))
        cand = jnp.full((5, 24), -1, jnp.int32)  # nothing visible anywhere
        s, i = ivf_topk_pallas(q, keys, cand, k=3, interpret=True)
        assert np.all(np.asarray(s) == -np.inf)
        assert np.all(np.asarray(i) == -1)
        _check(ref.ivf_topk_ref(q, keys, cand, 3), (s, i))


class TestThreeWayParity:
    """Fused IVF == jnp IVF == exact oracle when every bucket is probed and
    capacity holds the whole slab (recall is exactly 1 by construction)."""

    @pytest.mark.parametrize("dtype", ["f32", "int8"])
    def test_fully_probed_equals_exact(self, dtype):
        d, n, b, k = 32, 300, 12, 4
        keys = _unit(jax.random.PRNGKey(0), (n, d))
        valid = jax.random.bernoulli(jax.random.PRNGKey(5), 0.9, (n,))
        q = _near_queries(keys, b)
        slab = _slab_int8(keys) if dtype == "int8" else keys
        st = _fitted(IVFIndex(ncentroids=8, nprobe=8, bucket_cap=512,
                              topk=k), keys, valid)
        exact = ExactIndex(topk=k, backend="jnp").search(
            ExactState(), q, slab, valid)
        for backend in ("jnp", "pallas"):
            ivf = IVFIndex(ncentroids=8, nprobe=8, bucket_cap=512, topk=k,
                           backend=backend)
            got = ivf.search(st, q, slab, valid)
            # candidate *order* differs (bucket-major vs slot-major) so
            # equal-score permutations are legal; compare as sorted sets
            np.testing.assert_array_equal(np.sort(np.asarray(got[1]), 1),
                                          np.sort(np.asarray(exact[1]), 1))
            np.testing.assert_allclose(np.sort(np.asarray(got[0]), 1),
                                       np.sort(np.asarray(exact[0]), 1),
                                       rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
    @pytest.mark.parametrize("with_interval", [False, True])
    def test_backend_parity_partial_probe(self, dtype, with_interval):
        """The acceptance sweep: fused vs jnp IVF, bit-for-bit ids and
        1e-5 scores, across slab dtypes x interval/no-interval."""
        d, n, b, k = 48, 400, 20, 4        # b=20 > block_b=8
        keys = _unit(jax.random.PRNGKey(7), (n, d))
        valid = jax.random.bernoulli(jax.random.PRNGKey(8), 0.85, (n,))
        q = _near_queries(keys, b, noise_seed=9)
        slab = keys
        if dtype == "bf16":
            slab = keys.astype(jnp.bfloat16)
        elif dtype == "int8":
            slab = _slab_int8(keys)
        interval = None
        if with_interval:
            starts = jnp.where(jnp.arange(b) % 2 == 0, 0, n // 2
                               ).astype(jnp.int32)
            sizes = jnp.full((b,), n // 2, jnp.int32)
            # every 5th row: empty region (the §14.4 contract edge)
            sizes = jnp.where(jnp.arange(b) % 5 == 4, 0, sizes)
            interval = (starts, sizes)
        st = _fitted(IVFIndex(ncentroids=8, nprobe=4, bucket_cap=64,
                              topk=k), keys, valid)
        ivf_j = IVFIndex(ncentroids=8, nprobe=4, bucket_cap=64, topk=k,
                         backend="jnp")
        ivf_p = IVFIndex(ncentroids=8, nprobe=4, bucket_cap=64, topk=k,
                         backend="pallas")
        exp = ivf_j.search(st, q, slab, valid, interval=interval)
        got = ivf_p.search(st, q, slab, valid, interval=interval)
        tol = 2e-2 if dtype == "bf16" else 1e-5
        _check(exp, got, rtol=tol, atol=tol)
        if with_interval:
            # interval restriction actually bites on both paths
            ids = np.asarray(got[1])
            st_, sz = np.asarray(interval[0]), np.asarray(interval[1])
            for row in range(b):
                hits = ids[row][ids[row] >= 0]
                assert ((hits >= st_[row]) & (hits < st_[row] + sz[row])).all()
            empty = np.arange(b) % 5 == 4
            assert np.all(np.asarray(got[0])[empty] == -np.inf)
            assert np.all(ids[empty] == -1)


class TestEdgeCases:
    def _base(self, d=24, n=128, b=6):
        keys = _unit(jax.random.PRNGKey(0), (n, d))
        valid = jnp.ones((n,), bool)
        q = _near_queries(keys, b)
        return keys, valid, q

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_all_dead_buckets(self, backend):
        """Pre-refit index (or fully expired slab): every bucket slot
        invalid -> every row returns exactly (-inf, -1)."""
        keys, valid, q = self._base()
        ivf = IVFIndex(ncentroids=4, nprobe=4, bucket_cap=32, topk=3,
                       backend=backend)
        st = ivf.init(type("C", (), {"dim": keys.shape[1]})())
        s, i = ivf.search(st, q, keys, valid)
        assert np.all(np.asarray(s) == -np.inf)
        assert np.all(np.asarray(i) == -1)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_empty_region_tenant(self, backend):
        """A tenant with a zero-size region sees an empty cache even when
        the slab is full and every bucket is live."""
        keys, valid, q = self._base()
        ivf = IVFIndex(ncentroids=4, nprobe=4, bucket_cap=64, topk=2,
                       backend=backend)
        st = _fitted(ivf, keys, valid)
        b = q.shape[0]
        starts = jnp.zeros((b,), jnp.int32)
        sizes = jnp.zeros((b,), jnp.int32)
        s, i = ivf.search(st, q, keys, valid, interval=(starts, sizes))
        assert np.all(np.asarray(s) == -np.inf)
        assert np.all(np.asarray(i) == -1)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_per_row_dense_valid(self, backend):
        """(B, N) per-row aliveness composes with the candidate gather on
        both backends identically."""
        keys, _, q = self._base()
        b, n = q.shape[0], keys.shape[0]
        valid2d = jax.random.bernoulli(jax.random.PRNGKey(3), 0.7, (b, n))
        ivf = IVFIndex(ncentroids=4, nprobe=4, bucket_cap=64, topk=3,
                       backend=backend)
        st = _fitted(ivf, keys, jnp.ones((n,), bool))
        s, i = ivf.search(st, q, keys, valid2d)
        ids, vis = np.asarray(i), np.asarray(valid2d)
        for row in range(b):
            for slot in ids[row][ids[row] >= 0]:
                assert vis[row, slot]


class TestDuplicateCandidates:
    """Satellite regression: a slot recycled across buckets must occupy at
    most one of the k result rows (previously documented as 'harmless' —
    it wasn't: it wasted top-k slots on copies of one entry)."""

    def _dup_state(self, d, n):
        """Hand-built index where slot 5 appears in BOTH buckets."""
        keys = _unit(jax.random.PRNGKey(0), (n, d))
        buckets = jnp.full((2, 4), -1, jnp.int32)
        bucket_valid = jnp.zeros((2, 4), bool)
        buckets = buckets.at[0, :3].set(jnp.array([5, 1, 2]))
        buckets = buckets.at[1, :3].set(jnp.array([5, 3, 4]))  # stale pointer
        bucket_valid = bucket_valid.at[0, :3].set(True)
        bucket_valid = bucket_valid.at[1, :3].set(True)
        centroids = _unit(jax.random.PRNGKey(1), (2, d))
        return keys, IVFState(centroids=centroids, buckets=buckets,
                              bucket_valid=bucket_valid)

    @pytest.mark.parametrize("backend", ["jnp", "pallas"])
    def test_recycled_slot_fills_one_result_row(self, backend):
        d, n = 16, 8
        keys, st = self._dup_state(d, n)
        # query = slot 5's key: without dedup its two occurrences would
        # take result rows 1 AND 2 with identical (maximal) scores
        q = keys[5:6]
        ivf = IVFIndex(ncentroids=2, nprobe=2, bucket_cap=4, topk=3,
                       backend=backend)
        s, i = ivf.search(st, q, keys, jnp.ones((n,), bool))
        ids = np.asarray(i)[0]
        real = ids[ids >= 0]
        assert len(set(real.tolist())) == len(real), ids
        assert real[0] == 5
        assert np.count_nonzero(real == 5) == 1

    def test_absorb_recycling_end_to_end(self):
        """Force the duplicate through the real lifecycle: absorb indexes a
        slot near centroid A, the slot is recycled (new key near centroid
        B) and absorbed again — both buckets now reference it; search with
        both buckets probed returns it once."""
        d, n = 16, 32
        centroids = jnp.eye(2, d, dtype=jnp.float32)         # orthogonal
        st = IVFState(centroids=centroids,
                      buckets=jnp.full((2, 8), -1, jnp.int32),
                      bucket_valid=jnp.zeros((2, 8), bool))
        ivf = IVFIndex(ncentroids=2, nprobe=2, bucket_cap=8, topk=4,
                       backend="jnp")
        slot = jnp.array([7])
        key_a = jnp.eye(1, d, dtype=jnp.float32)              # -> bucket 0
        key_b = jnp.zeros((1, d)).at[0, 1].set(1.0)           # -> bucket 1
        st = ivf.absorb(st, slot, key_a, jnp.array([True]))
        st = ivf.absorb(st, slot, key_b, jnp.array([True]))   # recycled
        assert int(jnp.sum((st.buckets == 7) & st.bucket_valid)) == 2
        keys = jnp.zeros((n, d)).at[7].set(key_b[0])          # live key = b
        for backend in ("jnp", "pallas"):
            s, i = IVFIndex(ncentroids=2, nprobe=2, bucket_cap=8, topk=4,
                            backend=backend).search(
                st, key_b, keys, jnp.ones((n,), bool).at[0].set(True))
            ids = np.asarray(i)[0]
            assert np.count_nonzero(ids == 7) == 1, (backend, ids)

    def test_dedup_keeps_first_visible_occurrence(self):
        cand = jnp.array([[5, 7, 5, 9, -1, 5],
                          [1, 1, 1, 1, 1, 1]], jnp.int32)
        vis = jnp.array([[False, True, True, True, False, True],
                         [True, False, True, True, True, True]])
        out = np.asarray(dedup_candidates(cand, vis))
        # row 0: first occurrence of 5 is invisible -> position 2 survives
        assert out[0].tolist() == [False, True, True, True, False, False]
        # row 1: only the first visible 1 survives
        assert out[1].tolist() == [True, False, False, False, False, False]


class TestAbsorbVectorized:
    """Satellite parity: the sort-by-centroid vectorized absorb must equal
    the serial fori_loop scatter bit-for-bit, including bucket overflow
    (clamped tail, last writer wins) and masked-out rows."""

    @pytest.mark.parametrize("seed", range(6))
    def test_parity_random(self, seed):
        d, n, c, cap, b = 24, 200, 6, 8, 32
        r = jax.random.PRNGKey(seed)
        k1, k2, k3, k4, k5 = jax.random.split(r, 5)
        ivf = IVFIndex(ncentroids=c, nprobe=2, bucket_cap=cap, topk=2)
        centroids = _unit(k1, (c, d))
        # random pre-fill levels, incl. full and empty buckets
        fill = jax.random.randint(k2, (c,), 0, cap + 1)
        col = jnp.arange(cap)[None, :]
        bucket_valid = col < fill[:, None]
        buckets = jnp.where(bucket_valid,
                            jax.random.randint(k3, (c, cap), 0, n), -1
                            ).astype(jnp.int32)
        st = IVFState(centroids=centroids, buckets=buckets,
                      bucket_valid=bucket_valid)
        new_keys = jax.random.normal(k4, (b, d))
        slots = jax.random.randint(k5, (b,), 0, n)
        mask = jax.random.bernoulli(jax.random.PRNGKey(seed + 100), 0.7, (b,))

        got = ivf.absorb(st, slots, new_keys, mask)
        qn = new_keys / jnp.maximum(
            jnp.linalg.norm(new_keys, axis=1, keepdims=True), 1e-12)
        assign = jnp.argmax(jnp.einsum("bd,cd->bc", qn, centroids), axis=-1)
        exp_b, exp_v = _absorb_serial(st.buckets, st.bucket_valid, assign,
                                      slots, mask, cap)
        np.testing.assert_array_equal(np.asarray(got.buckets),
                                      np.asarray(exp_b))
        np.testing.assert_array_equal(np.asarray(got.bucket_valid),
                                      np.asarray(exp_v))

    def test_single_bucket_overflow_last_writer_wins(self):
        d, n, cap = 8, 64, 2
        centroids = jnp.eye(1, d, dtype=jnp.float32)
        st = IVFState(centroids=centroids,
                      buckets=jnp.full((1, cap), -1, jnp.int32),
                      bucket_valid=jnp.zeros((1, cap), bool))
        ivf = IVFIndex(ncentroids=1, nprobe=1, bucket_cap=cap, topk=1)
        keys = jnp.tile(jnp.eye(1, d, dtype=jnp.float32), (4, 1))
        slots = jnp.array([10, 11, 12, 13])
        got = ivf.absorb(st, slots, keys, jnp.ones((4,), bool))
        # fill order 10, 11; 12 and 13 clamp onto the tail; 13 wins
        np.testing.assert_array_equal(np.asarray(got.buckets[0]), [10, 13])
        assert bool(jnp.all(got.bucket_valid))


class TestOpsDispatch:
    """REPRO_PALLAS_INTERPRET=1 must route ops.ivf_topk — and the whole
    IVFIndex(backend='auto') search — through the interpret-mode kernel and
    still match the oracle; this is what the CPU CI kernel job exercises."""

    @pytest.fixture(autouse=True)
    def _interpret(self, monkeypatch):
        monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")

    def test_ops_ivf_topk(self):
        q = _unit(jax.random.PRNGKey(0), (4, 32))
        keys = _unit(jax.random.PRNGKey(1), (96, 32))
        cand = jax.random.randint(jax.random.PRNGKey(2), (4, 40), 0, 96,
                                  dtype=jnp.int32)
        vis = jax.random.bernoulli(jax.random.PRNGKey(3), 0.8, (4, 40))
        vis = dedup_candidates(cand, vis)
        cand = jnp.where(vis, cand, -1)
        _check(ref.ivf_topk_ref(q, keys, cand, 3),
               ops.ivf_topk(q, keys, cand, k=3))

    def test_auto_backend_search_matches_jnp(self):
        d, n, b = 32, 200, 5
        keys = _unit(jax.random.PRNGKey(0), (n, d))
        valid = jnp.ones((n,), bool)
        q = _near_queries(keys, b)
        st = _fitted(IVFIndex(ncentroids=4, nprobe=2, bucket_cap=64,
                              topk=3), keys, valid)
        auto = IVFIndex(ncentroids=4, nprobe=2, bucket_cap=64, topk=3)
        jnp_ = IVFIndex(ncentroids=4, nprobe=2, bucket_cap=64, topk=3,
                        backend="jnp")
        _check(jnp_.search(st, q, keys, valid),
               auto.search(st, q, keys, valid))

    def test_jnp_backend_runs_no_kernel(self, monkeypatch):
        """backend='jnp' pins the centroid probe as well as the candidate
        stage: the on-chip reference must not touch a Pallas kernel."""
        def refuse(*a, **kw):
            raise AssertionError("a Pallas kernel ran under backend='jnp'")
        monkeypatch.setattr(ops, "cosine_topk_pallas", refuse)
        monkeypatch.setattr(ops, "ivf_topk_pallas", refuse)
        d, n, b = 32, 200, 5
        keys = _unit(jax.random.PRNGKey(0), (n, d))
        valid = jnp.ones((n,), bool)
        index = IVFIndex(ncentroids=4, nprobe=2, bucket_cap=64, topk=3,
                         backend="jnp")
        st = _fitted(index, keys, valid)
        _, ids = index.search(st, _near_queries(keys, b), keys, valid)
        assert np.all(np.asarray(ids)[:, 0] >= 0)

    def test_unknown_backend_rejected(self):
        q = _unit(jax.random.PRNGKey(0), (2, 32))
        with pytest.raises(ValueError, match="unknown backend"):
            ops.cosine_topk(q, q, jnp.ones((2,), bool), k=1, backend="gpu")
