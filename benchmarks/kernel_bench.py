"""Kernel microbenchmarks: the scoring hot-spot at cache sizes from 4k to
512k entries (jnp/XLA path on this CPU host; the Pallas kernel is the TPU
target, validated in interpret mode by tests — interpret timings are
Python-bound and not meaningful, so we benchmark the oracle the kernel
replaces and report the analytic TPU-side expectation)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cosine_topk import quantize_keys


def _time(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def cosine_topk_scaling():
    rows = []
    d, b, k = 384, 32, 4
    f = jax.jit(lambda q, kk, v: ref.cosine_topk_ref(q, kk, v, k))
    fq = jax.jit(lambda q, kk, sc, v: ref.quant_cosine_topk_ref(q, kk, sc, v, k))
    for n in (4096, 32768, 131072, 524288):
        rng = jax.random.PRNGKey(n)
        kq, kk_ = jax.random.split(rng)
        q = jax.random.normal(kq, (b, d))
        q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
        keys = jax.random.normal(kk_, (n, d))
        keys = keys / jnp.linalg.norm(keys, axis=1, keepdims=True)
        valid = jnp.ones((n,), bool)
        t = _time(f, q, keys, valid)
        kq8, sc = quantize_keys(keys)
        tq = _time(fq, q, kq8, sc, valid)
        # TPU expectation: GEMM flops / MXU peak + slab HBM read
        flops = 2 * b * n * d
        mxu_s = flops / 197e12
        hbm_s = n * d * 4 / 819e9
        hbm_q = n * d * 1 / 819e9
        rows.append({
            "name": f"kernel/cosine_topk_n{n}",
            "us_per_call": t * 1e6,
            "derived": (f"cpu_f32_us={t*1e6:.0f} cpu_int8_us={tq*1e6:.0f} "
                        f"tpu_roofline_us={max(mxu_s, hbm_s)*1e6:.1f} "
                        f"tpu_int8_roofline_us={max(mxu_s, hbm_q)*1e6:.1f}"),
        })
    return rows, {}


def masked_lookup_scaling():
    """Per-row-masked (tenancy) lookup: interval operands vs a dense (B, N)
    mask (DESIGN.md §14).

    On TPU the interval kernel builds the visibility mask from iota in VMEM,
    so per-row masking adds exactly 8 bytes/row of operand traffic (start +
    size, int32) — O(B), independent of slab size — where a dense bool mask
    adds B*N bytes of HBM traffic on the lookup's memory-bound axis. This
    CPU host times the two jnp oracles (same contract as the kernels) and
    reports the operand-bytes ratio the kernel avoids.
    """
    rows = []
    d, b, k, tenants = 384, 32, 4, 8
    f_dense = jax.jit(lambda q, kk, m: ref.cosine_topk_ref(q, kk, m, k))
    f_intv = jax.jit(lambda q, kk, v, st, sz: ref.cosine_topk_interval_ref(
        q, kk, v, st, sz, k))
    for n in (32768, 131072, 524288):
        rng = jax.random.PRNGKey(n)
        kq, kk_, kt = jax.random.split(rng, 3)
        q = jax.random.normal(kq, (b, d))
        q = q / jnp.linalg.norm(q, axis=1, keepdims=True)
        keys = jax.random.normal(kk_, (n, d))
        keys = keys / jnp.linalg.norm(keys, axis=1, keepdims=True)
        valid = jnp.ones((n,), bool)
        # uniform tenant partition: contiguous equal regions, random mix
        region = n // tenants
        tid = jax.random.randint(kt, (b,), 0, tenants, dtype=jnp.int32)
        starts, sizes = tid * region, jnp.full((b,), region, jnp.int32)
        dense = (jnp.arange(n, dtype=jnp.int32)[None, :] >= starts[:, None]) \
            & (jnp.arange(n, dtype=jnp.int32)[None, :]
               < (starts + sizes)[:, None])
        t_dense = _time(f_dense, q, keys, dense)
        t_intv = _time(f_intv, q, keys, valid, starts, sizes)
        mask_bytes = b * n            # (B, N) bool materialized in HBM
        intv_bytes = 2 * b * 4       # (B,) start + (B,) size, int32
        rows.append({
            "name": f"kernel/masked_lookup_n{n}",
            "us_per_call": t_intv * 1e6,
            "derived": (f"cpu_interval_us={t_intv*1e6:.0f} "
                        f"cpu_dense_mask_us={t_dense*1e6:.0f} "
                        f"mask_operand_bytes={mask_bytes} "
                        f"interval_operand_bytes={intv_bytes} "
                        f"hbm_traffic_saved={mask_bytes/intv_bytes:.0f}x"),
        })
    return rows, {}


def fused_ivf_bench():
    """Fused IVF candidate kernel vs the jnp gather path (DESIGN.md §15).

    On this CPU host we time the jnp IVF search (the path the kernel
    replaces; interpret-mode kernel timings are Python-bound and not
    meaningful) and report the *analytic per-lookup HBM operand bytes* of
    the candidate stage on TPU, per path:

      jnp gather path:  the (B, M, d) gathered-candidate tensor
                        materializes in HBM — slab rows are read by the
                        gather (slab dtype), the gathered tensor is written
                        (f32 after dequant) and re-read by the einsum:
                        B*M*d * (s + 4 + 4) bytes.
      fused kernel:     each candidate's aligned HBM row group (T rows,
                        T * s = 32 bytes of sublane per row index) streams
                        HBM -> VMEM once and is scored from VMEM; the
                        (B, M, d) tensor never exists in HBM:
                        B*M*d * 32 bytes (+ O(B*M) id operands, counted).

    s = slab itemsize. The headline row is the int8 slab, where
    fused/jnp = 32/9; the f32 slab row (32/12) is reported alongside. The
    TPU cannot DMA a single slab row, so the kernel overfetches T-fold.
    Masked-candidate DMA skip and dedup only lower the fused side; the
    analytic numbers ignore both (worst case for the kernel).
    """
    from repro.core.index import IVFIndex

    b, d, nprobe, cap, c = 128, 768, 8, 128, 64   # the §15 default config
    n = c * cap                                   # slab fully bucketable
    m = nprobe * cap
    rng = jax.random.PRNGKey(0)
    keys = jax.random.normal(rng, (n, d))
    keys = keys / jnp.linalg.norm(keys, axis=1, keepdims=True)
    keys8 = jnp.clip(jnp.round(keys * 127.0), -127, 127).astype(jnp.int8)
    valid = jnp.ones((n,), bool)
    queries = keys[:b] + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                  (b, d))
    queries = queries / jnp.linalg.norm(queries, axis=1, keepdims=True)
    ivf = IVFIndex(ncentroids=c, nprobe=nprobe, bucket_cap=cap, topk=4,
                   backend="jnp")
    st = ivf.fit(keys, valid, jax.random.PRNGKey(2))

    rows = []
    id_bytes = 2 * b * m * 4          # cand ids: SMEM + VMEM copies
    for label, slab, s_item in (("int8", keys8, 1), ("f32", keys, 4)):
        f = jax.jit(lambda q, kk: ivf.search(st, q, kk, valid))
        t = _time(f, queries, slab)
        jnp_bytes = b * m * d * (s_item + 4 + 4)
        fused_bytes = b * m * d * 32 + id_bytes     # T-row groups
        name = ("kernel/ivf_fused_default" if label == "int8"
                else f"kernel/ivf_fused_{label}")
        rows.append({
            "name": name,
            "us_per_call": t * 1e6,
            "derived": (f"slab={label} cpu_jnp_us={t*1e6:.0f} "
                        f"jnp_gather_bytes={jnp_bytes} "
                        f"fused_bytes={fused_bytes} "
                        f"fused_over_jnp={fused_bytes/jnp_bytes:.3f} "
                        f"B={b} d={d} nprobe={nprobe} cap={cap}"),
        })
    return rows, {}


def ivf_crossover(full: bool = True):
    """Exact-vs-IVF wall-clock crossover over slab size N (DESIGN.md §15.5).

    Exact scoring is one dense (B, d) x (d, N) GEMM — unbeatable while the
    slab fits the arithmetic budget; IVF's probe + gather only pays off
    once N is large enough that scoring everything costs more than probing
    nprobe/C of it. This sweep times both jnp paths on the host (same
    contract as the kernels) and reports IVF recall@1 at each point."""
    from repro.core.index import ExactIndex, ExactState, IVFIndex

    d, b, c_frac = 384, 32, 64
    sizes = (4096, 16384, 65536) + ((262144,) if full else ())
    rows = []
    for n in sizes:
        rng = jax.random.PRNGKey(n)
        keys = jax.random.normal(rng, (n, d))
        keys = keys / jnp.linalg.norm(keys, axis=1, keepdims=True)
        valid = jnp.ones((n,), bool)
        queries = keys[:b] + 0.05 * jax.random.normal(
            jax.random.PRNGKey(1), (b, d))
        queries = queries / jnp.linalg.norm(queries, axis=1, keepdims=True)
        c = min(512, max(16, n // c_frac))
        ivf = IVFIndex(ncentroids=c, nprobe=8,
                       bucket_cap=max(128, 2 * n // c), topk=1,
                       backend="jnp")
        st = ivf.fit(keys, valid, jax.random.PRNGKey(2))
        fi = jax.jit(lambda q: ivf.search(st, q, keys, valid))
        fe = jax.jit(lambda q: ExactIndex(topk=1, backend="jnp").search(
            ExactState(), q, keys, valid))
        t_ivf = _time(fi, queries)
        t_ex = _time(fe, queries)
        _, i_ivf = fi(queries)
        _, i_ex = fe(queries)
        recall = float(jnp.mean((i_ivf[:, 0] == i_ex[:, 0]
                                 ).astype(jnp.float32)))
        rows.append({
            "name": f"kernel/ivf_crossover_n{n}",
            "us_per_call": t_ivf * 1e6,
            "derived": (f"ivf_us={t_ivf*1e6:.0f} exact_us={t_ex*1e6:.0f} "
                        f"speedup={t_ex/t_ivf:.2f}x recall@1={recall:.3f} "
                        f"ncentroids={c}"),
        })
    return rows, {}


def hnsw_vs_exact():
    """Paper-faithful HNSW vs the TPU-native exact scoring (DESIGN.md §3)."""
    import numpy as np
    from repro.core.hnsw import HNSWIndex
    d, n, nq = 384, 8192, 64
    rng = np.random.default_rng(0)
    keys = rng.normal(size=(n, d)).astype(np.float32)
    keys /= np.linalg.norm(keys, axis=1, keepdims=True)
    queries = keys[:nq] + 0.05 * rng.normal(size=(nq, d)).astype(np.float32)

    idx = HNSWIndex(dim=d, max_elements=n, m=16, ef_construction=100,
                    ef_search=64)
    t0 = time.perf_counter()
    for v in keys:
        idx.add(v)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ids_h, _ = idx.search_batch(queries, 1)
    search_s = (time.perf_counter() - t0) / nq

    f = jax.jit(lambda q, kk, v: ref.cosine_topk_ref(q, kk, v, 1))
    qj = jnp.asarray(queries / np.linalg.norm(queries, axis=1, keepdims=True))
    kj = jnp.asarray(keys)
    valid = jnp.ones((n,), bool)
    exact_s = _time(f, qj, kj, valid) / nq
    s_ex, i_ex = f(qj, kj, valid)
    recall = float((np.asarray(i_ex)[:, 0] == ids_h[:, 0]).mean())
    rows = [{
        "name": "design3/hnsw_vs_exact",
        "us_per_call": search_s * 1e6,
        "derived": (f"hnsw_search_us={search_s*1e6:.0f} "
                    f"exact_batched_us={exact_s*1e6:.1f} "
                    f"hnsw_build_s={build_s:.1f} agreement={recall:.2f}"),
    }]
    return rows, {}


def ivf_bench():
    from repro.core.index import ExactIndex, ExactState, IVFIndex
    d, n, nq = 384, 65536, 64
    rng = jax.random.PRNGKey(0)
    keys = jax.random.normal(rng, (n, d))
    keys = keys / jnp.linalg.norm(keys, axis=1, keepdims=True)
    valid = jnp.ones((n,), bool)
    queries = keys[:nq] + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                   (nq, d))
    ivf = IVFIndex(ncentroids=256, nprobe=16, bucket_cap=512, topk=1)
    st = ivf.fit(keys, valid, jax.random.PRNGKey(2))
    fs = jax.jit(lambda q: ivf.search(st, q, keys, valid))
    fe = jax.jit(lambda q: ExactIndex(topk=1, backend="jnp").search(
        ExactState(), q, keys, valid))
    t_ivf = _time(fs, queries)
    t_ex = _time(fe, queries)
    _, i_ivf = fs(queries)
    _, i_ex = fe(queries)
    recall = float(jnp.mean((i_ivf[:, 0] == i_ex[:, 0]).astype(jnp.float32)))
    rows = [{
        "name": "beyond/ivf_n65536",
        "us_per_call": t_ivf * 1e6,
        "derived": (f"ivf_us={t_ivf*1e6:.0f} exact_us={t_ex*1e6:.0f} "
                    f"speedup={t_ex/t_ivf:.2f}x recall@1={recall:.3f}"),
    }]
    return rows, {}


def fused_step_bench():
    """Fused ``SemanticCache.step`` (one compiled dispatch) vs the real
    separate path — two jitted dispatches, lookup then masked insert, with
    the hit mask crossing the dispatch boundary — on a hot mixed hit/miss
    batch (DESIGN.md §7)."""
    from repro.core import CacheConfig, SemanticCache
    d, n, b, vlen = 384, 32768, 64, 32
    cfg = CacheConfig(dim=d, capacity=n, value_len=vlen, ttl=None,
                      threshold=0.8)
    cache = SemanticCache(cfg)
    runtime = cache.init()
    rng = jax.random.PRNGKey(0)
    warm_q = jax.random.normal(rng, (n // 2, d))
    vals = jnp.zeros((n // 2, vlen), jnp.int32)
    runtime = cache.insert(runtime, warm_q, vals,
                           jnp.full((n // 2,), vlen), 0.0)
    # half the batch paraphrases cached entries (hits), half is novel
    queries = jnp.concatenate([
        warm_q[:b // 2] + 0.01 * jax.random.normal(rng, (b // 2, d)),
        jax.random.normal(jax.random.PRNGKey(1), (b // 2, d))])
    mv = jnp.zeros((b, vlen), jnp.int32)
    ml = jnp.full((b,), vlen)

    fused = jax.jit(lambda rt, q, t: cache.step(rt, q, mv, ml, t))
    lookup_j = jax.jit(lambda rt, q, t: cache.lookup(rt, q, t))
    insert_j = jax.jit(lambda rt, q, m, t: cache.insert(
        rt, q, mv, ml, t, mask=m))

    def sep(q):
        res, rt = lookup_j(runtime, q, jnp.float32(1.0))
        return insert_j(rt, q, ~res.hit, jnp.float32(1.0))

    t_fused = _time(lambda q: fused(runtime, q, jnp.float32(1.0)), queries)
    t_sep = _time(sep, queries)
    rows = [{
        "name": "beyond/fused_step_n32768_b64",
        "us_per_call": t_fused * 1e6,
        "derived": (f"fused_us={t_fused*1e6:.0f} separate_us={t_sep*1e6:.0f} "
                    f"speedup={t_sep/max(t_fused, 1e-9):.2f}x"),
    }]
    return rows, {}
