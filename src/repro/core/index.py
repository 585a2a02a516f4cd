"""ANN indexes over the cache slab (paper §2.4, TPU-adapted — DESIGN.md §8).

Two TPU-native index structures replace the paper's HNSW graph:

* ``ExactIndex`` — blocked brute-force cosine top-k on the MXU. Exact
  (recall = 1.0), one GEMM; dispatches to the Pallas fused kernel on TPU
  and to the jnp reference elsewhere. Stateless: its index state is an
  empty pytree.
* ``IVFIndex`` — inverted-file index: k-means centroids over the slab;
  search probes the top-``nprobe`` clusters only. This recovers HNSW's
  sub-linear scaling with *static shapes and dense matmuls*: both the
  centroid scoring and the in-cluster scoring are GEMMs. Cluster membership
  is a padded (ncentroids, bucket_cap) table rebuilt by ``refit`` —
  the analogue of the paper's periodic HNSW "rebalancing" (§2.4) — and kept
  fresh between rebuilds by ``absorb`` (incremental assignment of new rows,
  vectorized as a sort-by-centroid scatter). Search runs in two stages that
  both hit fused Pallas kernels on TPU (DESIGN.md §15): the centroid probe
  goes through ``ops.cosine_topk`` (§3's kernel, centroids as the slab) and
  the candidate stage through ``ops.ivf_topk``, which gathers the probed
  slab rows HBM -> VMEM *inside* the kernel — the (B, M, d) gathered-
  candidate tensor of the jnp formulation never materializes in HBM. All
  visibility (bucket validity, aliveness, tenancy intervals, per-row
  duplicate suppression) is folded into the candidate ids by
  ``IVFIndex.candidates`` so the jnp oracle and the kernel share one
  contract.

Both conform to the ``repro.core.runtime.Index`` protocol — uniform
``init(config) / search(istate, ...) / absorb(istate, ...) /
refit(istate, ...)`` signatures so callers never branch on the index type
(DESIGN.md §8.1). The paper-faithful HNSW itself lives in
``repro.core.hnsw`` (CPU reference).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.similarity import (NEG_INF, cosine_scores,
                                   interval_visibility, l2_normalize,
                                   masked_topk)
from repro.core.types import CacheConfig

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ExactState:
    """Empty index state: brute-force scoring reads the slab directly."""


@dataclasses.dataclass(frozen=True)
class ExactIndex:
    """Exact blocked scoring. ``backend='auto'|'jnp'|'pallas'``."""

    topk: int = 4
    backend: str = "auto"

    def init(self, config: CacheConfig) -> ExactState:
        del config
        return ExactState()

    def search(self, istate: ExactState, queries: Array, keys: Array,
               alive: Array, *, interval: tuple[Array, Array] | None = None
               ) -> tuple[Array, Array]:
        """(B,d) x (N,d) -> (scores (B,k), indices (B,k)).

        ``alive`` is (N,) — one visibility mask for the whole batch — or
        (B, N) for general per-row visibility. ``interval`` = per-row
        ``(starts, sizes)`` operands restricting each row to a contiguous
        slot range on top of a shared (N,) ``alive`` — the tenancy path
        (contiguous PartitionMap regions, DESIGN.md §14): on TPU it stays
        on the fused interval-masked Pallas kernel with O(B) operand
        traffic; a (B, N) ``alive`` routes to the dense blocked-mask
        kernel. Rows with no visible live slot return exactly (-inf, -1).
        """
        del istate
        backend = self.backend
        if backend == "auto":
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        queries = l2_normalize(queries)  # keys are normalized at insert time
        if interval is not None and alive.ndim == 2:
            # interval on top of an already-per-row mask: fold it in so the
            # restriction is never dropped (IVF composes the same way)
            alive = interval_visibility(alive, *interval)
            interval = None
        if backend == "pallas":
            from repro.kernels import ops  # deferred: kernels are optional deps

            if interval is not None:
                starts, sizes = interval
                return ops.cosine_topk_interval(queries, keys, alive,
                                                starts, sizes, k=self.topk)
            return ops.cosine_topk(queries, keys, alive, k=self.topk)
        if interval is not None:
            alive = interval_visibility(alive, *interval)
        scores = cosine_scores(queries, keys, alive)
        vals, idx = masked_topk(scores, self.topk)
        # all-masked rows: same (-inf, -1) contract as the Pallas kernels
        idx = jnp.where(vals > NEG_INF, idx, -1)
        return vals, idx.astype(jnp.int32)

    def absorb(self, istate: ExactState, slots: Array, keys: Array,
               mask: Array) -> ExactState:
        del slots, keys, mask
        return istate

    def refit(self, istate: ExactState, keys: Array, alive: Array,
              rng: Array) -> ExactState:
        del keys, alive, rng
        return istate


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IVFState:
    centroids: Array   # (C, d) normalized
    buckets: Array     # (C, cap) int32 slot ids, -1 padded
    bucket_valid: Array  # (C, cap) bool


def dedup_candidates(cand: Array, visible: Array) -> Array:
    """Suppress per-row duplicate candidate slot ids (DESIGN.md §15.3).

    A slot recycled across buckets (``absorb`` leaves stale pointers behind
    by design) can reach ``search`` twice in one row's candidate list with
    *identical* scores — and without suppression would occupy two of the k
    result rows. Returns ``visible`` with every duplicate of an
    already-visible slot id turned off, keeping the *first visible*
    occurrence per row (matching ``top_k``'s lowest-position tie-break).
    Invisible occurrences never suppress a visible one.

    cand: (B, M) int32 slot ids; visible: (B, M) bool. O(B·M log M) — a
    sort over int32 ids, noise next to the candidate gather it protects.
    """
    b, m = cand.shape
    pos = jnp.arange(m, dtype=jnp.int32)[None, :]
    # invisible entries get unique sentinels so they never collide with a
    # real id (slot ids < 2**30) or with each other
    key = jnp.where(visible, cand, jnp.int32(2 ** 30) + pos)
    order = jnp.argsort(key, axis=1)                 # stable: earliest first
    skey = jnp.take_along_axis(key, order, axis=1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros((b, 1), bool), skey[:, 1:] == skey[:, :-1]], axis=1)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    dup = jnp.zeros((b, m), bool).at[rows, order].set(dup_sorted)
    return visible & ~dup


def _absorb_serial(buckets: Array, bucket_valid: Array, assign: Array,
                   slots: Array, mask: Array, cap: int
                   ) -> tuple[Array, Array]:
    """Reference serial absorb: the original O(B) ``fori_loop`` scatter.

    Kept as the semantic oracle for the vectorized scatter in
    ``IVFIndex.absorb`` (parity-tested): rows append *in batch order* to
    their assigned bucket's fill point; once a bucket is full, later rows
    overwrite the tail slot (last writer wins).
    """
    def body(i, carry):
        buckets, bucket_valid = carry
        c = assign[i]
        fill = jnp.sum(bucket_valid[c]).astype(jnp.int32)
        pos = jnp.minimum(fill, cap - 1)
        do = mask[i]
        buckets = buckets.at[c, pos].set(
            jnp.where(do, slots[i].astype(jnp.int32), buckets[c, pos]))
        bucket_valid = bucket_valid.at[c, pos].set(
            jnp.where(do, True, bucket_valid[c, pos]))
        return buckets, bucket_valid

    return jax.lax.fori_loop(0, slots.shape[0], body, (buckets, bucket_valid))


@dataclasses.dataclass(frozen=True)
class IVFIndex:
    """Inverted-file ANN. ``refit`` = k-means rebuild; ``search`` = probe
    GEMM + fused candidate gather/score (``backend='auto'|'jnp'|'pallas'``
    pins both stages for parity tests and on-chip references — 'auto'
    follows the ops dispatch: Pallas on TPU, jnp elsewhere)."""

    ncentroids: int = 64
    nprobe: int = 8
    bucket_cap: int = 512
    topk: int = 4
    kmeans_iters: int = 10
    backend: str = "auto"

    def init(self, config: CacheConfig) -> IVFState:
        """Empty index: deterministic random unit centroids, all-invalid
        buckets. Shape-identical to a fitted state, so the whole runtime has
        one static treedef from birth (DESIGN.md §2.1). The centroids are
        random rather than zero so that pre-refit ``absorb`` spreads new
        entries across all buckets (zero centroids would argmax every row
        into bucket 0, losing entries past one bucket's capacity); ``refit``
        replaces them with real k-means centroids."""
        c, cap = self.ncentroids, self.bucket_cap
        centroids = l2_normalize(jax.random.normal(
            jax.random.PRNGKey(0), (c, config.dim), dtype=jnp.float32))
        return IVFState(
            centroids=centroids,
            buckets=jnp.full((c, cap), -1, dtype=jnp.int32),
            bucket_valid=jnp.zeros((c, cap), dtype=bool),
        )

    def refit(self, istate: IVFState, keys: Array, alive: Array, rng: Array
              ) -> IVFState:
        """K-means over live keys; bucket table with static capacity.

        Overflowing buckets drop the farthest members (recall loss is
        measured in tests against the exact index) — the static-shape price
        of TPU-friendliness, and the analogue of HNSW's bounded degree M.
        """
        del istate  # full rebuild from the slab; prior state irrelevant
        if keys.dtype == jnp.int8:
            keys = keys.astype(jnp.float32) / 127.0  # uniform slab dequant
        valid = alive
        n, d = keys.shape
        c = self.ncentroids
        # init: random valid rows (fall back to arbitrary rows if few valid)
        p = valid.astype(jnp.float32) + 1e-6
        init_idx = jax.random.choice(rng, n, shape=(c,), replace=True, p=p / p.sum())
        centroids = l2_normalize(keys[init_idx])

        def step(centroids, _):
            sims = jnp.einsum("nd,cd->nc", keys, centroids)
            assign = jnp.argmax(sims, axis=-1)
            onehot = jax.nn.one_hot(assign, c, dtype=jnp.float32)
            onehot = onehot * valid[:, None]
            sums = jnp.einsum("nc,nd->cd", onehot, keys)
            counts = jnp.sum(onehot, axis=0)[:, None]
            new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), centroids)
            return l2_normalize(new), None

        centroids, _ = jax.lax.scan(step, centroids, None, length=self.kmeans_iters)

        sims = jnp.einsum("nd,cd->nc", keys, centroids)
        sims = jnp.where(valid[:, None], sims, NEG_INF)
        assign = jnp.argmax(sims, axis=-1)           # (N,)
        member_sim = jnp.max(sims, axis=-1)          # (N,)

        # Build padded buckets: for each centroid take its top-cap members.
        # score matrix (C, N): member_sim where assigned, else -inf
        belong = jax.nn.one_hot(assign, c, dtype=bool).T  # (C, N)
        belong = belong & valid[None, :]
        member_scores = jnp.where(belong, member_sim[None, :], NEG_INF)
        top_scores, top_idx = jax.lax.top_k(member_scores, min(self.bucket_cap, n))
        cap = self.bucket_cap
        if top_idx.shape[1] < cap:  # pad if slab smaller than bucket cap
            pad = cap - top_idx.shape[1]
            top_idx = jnp.pad(top_idx, ((0, 0), (0, pad)), constant_values=0)
            top_scores = jnp.pad(top_scores, ((0, 0), (0, pad)), constant_values=-jnp.inf)
        bucket_valid = top_scores > NEG_INF
        buckets = jnp.where(bucket_valid, top_idx, -1).astype(jnp.int32)
        return IVFState(centroids=centroids, buckets=buckets, bucket_valid=bucket_valid)

    def fit(self, keys: Array, valid: Array, rng: Array) -> IVFState:
        """From-scratch build (refit with a throwaway empty state)."""
        return self.refit(None, keys, valid, rng)

    def absorb(self, istate: IVFState, slots: Array, keys: Array, mask: Array
               ) -> IVFState:
        """Incrementally index freshly inserted slab rows (DESIGN.md §8.2).

        Each new key is appended to its nearest centroid's bucket (overwriting
        the bucket tail when full — those entries are the farthest members,
        restored at the next ``refit``). Stale references to a recycled slot
        elsewhere in the table cost nothing at search time: ``candidates``
        scores against the *live* slab key and suppresses per-row duplicates
        (``dedup_candidates``), so a stale pointer can neither return a wrong
        score nor occupy two of the k result rows.

        Vectorized (DESIGN.md §15.4): instead of the serial O(B) scatter
        (``_absorb_serial``, kept as the parity oracle) the batch is
        stable-sorted by assigned centroid, each row's in-bucket position is
        ``fill + rank`` (rank = position within its centroid's run, so
        batch order is preserved within a bucket), positions clamp to the
        bucket tail, and of the rows clamped onto one tail slot only the
        last in batch order writes — one gather, one sort, two scatters,
        no sequential dependency.
        """
        q = l2_normalize(keys)
        assign = jnp.argmax(
            jnp.einsum("bd,cd->bc", q, istate.centroids), axis=-1)
        cap, c = self.bucket_cap, self.ncentroids
        b = slots.shape[0]
        idx = jnp.arange(b, dtype=jnp.int32)
        # masked-out rows sort to a sentinel group past every real centroid
        group = jnp.where(mask, assign.astype(jnp.int32), jnp.int32(c))
        order = jnp.argsort(group)                     # stable: batch order
        sorted_g = group[order]
        is_start = jnp.concatenate(
            [jnp.array([True]), sorted_g[1:] != sorted_g[:-1]])
        first = jax.lax.associative_scan(                # cummax: start of
            jnp.maximum, jnp.where(is_start, idx, 0))    # each group's run
        rank = idx - first                               # 0,1,2,... per group
        fill0 = jnp.sum(istate.bucket_valid, axis=1).astype(jnp.int32)  # (C,)
        pos = jnp.minimum(fill0[jnp.minimum(sorted_g, c - 1)] + rank, cap - 1)
        # clamped rows pile onto the tail slot; the serial loop's last writer
        # wins, which in sorted space is the last row of the centroid's run
        is_end = jnp.concatenate(
            [sorted_g[1:] != sorted_g[:-1], jnp.array([True])])
        write = (sorted_g < c) & ((pos < cap - 1) | is_end)
        tgt = jnp.where(write, sorted_g, jnp.int32(c))   # OOB -> dropped
        vals = slots[order].astype(jnp.int32)
        buckets = istate.buckets.at[tgt, pos].set(vals, mode="drop")
        bucket_valid = istate.bucket_valid.at[tgt, pos].set(True, mode="drop")
        return IVFState(centroids=istate.centroids, buckets=buckets,
                        bucket_valid=bucket_valid)

    def candidates(self, istate: IVFState, q: Array, valid: Array, *,
                   interval: tuple[Array, Array] | None = None) -> Array:
        """Probe + visibility: (B, d) normalized queries -> (B, M) int32
        candidate slot ids, M = nprobe * bucket_cap, with -1 marking every
        invisible candidate. This is the single source of truth both search
        backends consume (``ref.ivf_topk_ref`` and the fused kernel), so
        their parity is structural, not coincidental.

        The centroid probe runs through ``ops.cosine_topk`` — §3's fused
        kernel on TPU, the jnp oracle elsewhere — with an all-true mask
        (centroids are always scoreable; dead buckets are filtered per
        candidate below). Folded into the ids, in order: bucket-slot
        validity, slab aliveness (``valid``, (N,) shared or (B, N)
        per-row), the per-row tenancy ``interval`` (O(B·M) compares on the
        gathered ids — never a (B, N) mask), and per-row duplicate
        suppression (``dedup_candidates``)."""
        from repro.kernels import ops  # deferred: kernels are optional deps

        ivf = istate
        b = q.shape[0]
        p = min(self.nprobe, self.ncentroids)
        always = jnp.ones((ivf.centroids.shape[0],), dtype=bool)
        _, probe = ops.cosine_topk(q, ivf.centroids, always, k=p,
                                   backend=self.backend)  # (B, P)
        cand = ivf.buckets[probe].reshape(b, -1)          # (B, M)
        visible = ivf.bucket_valid[probe].reshape(b, -1)  # (B, M)
        safe = jnp.maximum(cand, 0)
        if valid.ndim == 2:
            visible = visible & jnp.take_along_axis(valid, safe, axis=1)
        else:
            visible = visible & valid[safe]
        if interval is not None:
            starts, sizes = interval
            visible = visible & (safe >= starts[:, None]) \
                & (safe < (starts + sizes)[:, None])
        visible = dedup_candidates(cand, visible)
        return jnp.where(visible, cand, -1).astype(jnp.int32)

    def search(self, istate: IVFState, queries: Array, keys: Array,
               valid: Array, *, interval: tuple[Array, Array] | None = None
               ) -> tuple[Array, Array]:
        """(B,d) -> (scores (B,k), slot indices (B,k)). Probes nprobe buckets.

        ``valid`` is (N,) shared or (B, N) per-row; ``interval`` = per-row
        ``(starts, sizes)`` restricting each row to its own contiguous slab
        region on top of a shared (N,) ``valid`` (tenancy: each query sees
        only its own region's slots, whichever buckets they landed in) —
        applied to the gathered candidate slot ids, O(B·M), never a (B, N)
        mask. Rows with no visible live candidate return (-inf, -1).

        Both stages stay fused on TPU (DESIGN.md §15): the probe on §3's
        ``cosine_topk`` kernel and the candidate stage on ``ops.ivf_topk``,
        which gathers probed slab rows HBM -> VMEM in-kernel — the
        (B, M, d) gathered tensor of the jnp path never touches HBM."""
        from repro.kernels import ops  # deferred: kernels are optional deps

        q = l2_normalize(queries)
        cand = self.candidates(istate, q, valid, interval=interval)
        k = min(self.topk, cand.shape[1])
        return ops.ivf_topk(q, keys, cand, k=k, backend=self.backend)


@functools.partial(jax.jit, static_argnums=(0,))
def exact_search_jit(index: ExactIndex, queries, keys, valid):
    return index.search(ExactState(), queries, keys, valid)
