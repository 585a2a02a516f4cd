"""CachedEngine — the paper's full query-handling workflow (§2.5, §2.8)
wired together: embed -> semantic-cache lookup -> hit? serve cached :
call LLM backend -> insert -> respond.

The engine is batched (requests are grouped by the ``Batcher``), functional
on the device side and keeps host-side bookkeeping (detokenization table,
metrics) minimal. All device state lives in one ``CacheRuntime`` pytree
(DESIGN.md §2) — the engine holds exactly one mutable reference,
``self.runtime``, and never branches on index or policy type.

Two serve paths (DESIGN.md §7):
  * fused (``use_fused_step=True``, default): a pure *peek* lookup learns
    the miss set, the backend answers the misses, then one compiled
    ``SemanticCache.step`` does lookup + masked full-batch insert — static
    shapes at every batch size, so no per-miss-count retraces;
  * separate: mutating lookup, then an insert of just the missed rows
    (retraces per distinct miss count; kept as the reference path).

A ground-truth judge callback replaces the paper's GPT-4o-mini validation
(DESIGN.md §9): judge(query, matched_source_id) -> bool.

Multi-tenancy (DESIGN.md §13): constructing the engine with a
``TenantRegistry`` partitions the slab into per-tenant regions and threads
each request's ``tenant`` through the same compiled step — same batch
shapes, same jit cache, but lookups/inserts are masked to each row's own
region and both ``ServingMetrics`` and the device-side ``TenancyState``
keep per-tenant accounting.

Multi-turn sessions (DESIGN.md §16): constructing the engine with a
``ContextFusion`` strategy attaches a ``SessionStore`` and threads each
request's ``session`` through the same compiled step — a (B, W, d) window
of the session's prior raw turn embeddings rides along as one more traced
operand, the fused key searches AND populates the slab, and sessionless
rows (empty ``session``) pass through bit-identically, so session and
stateless traffic share one compiled program.

Generative near-hits (DESIGN.md §17): constructing the engine with a
``Synthesizer`` (and a band policy — defaulted when ``policy=None``)
routes lookups scoring in [τ_lo, τ_hi) through host-side answer synthesis
from their top-k neighbours instead of a full backend call. Converted
rows are admitted back into the slab under their own key with the
dominant neighbour's provenance, judged like exact hits, and fed back
into the band's lower edge. Without a synthesizer the band masks are
all-False and every path is bit-identical to binary hit/miss serving.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache import SemanticCache
from repro.core.runtime import CacheRuntime
from repro.core.types import CacheConfig
from repro.data.tokenizer import HashTokenizer
from repro.embedding.hash_embedder import HashEmbedder
from repro.obs.compiles import compile_log
from repro.obs.explain import build_why, effective_edges
from repro.obs.trace import Tracer, child_span
from repro.serving.metrics import ServingMetrics


@dataclasses.dataclass
class Request:
    query: str
    category: str = "default"
    source_id: int = -1          # ground-truth provenance (evaluation only)
    semantic_key: str = ""
    tenant: str = "default"      # isolation domain (multi-tenant serving,
                                 # DESIGN.md §13); ignored without a registry
    session: str = ""            # conversation id (multi-turn context,
                                 # DESIGN.md §16); "" = stateless request;
                                 # ignored without a fusion strategy
    explain: bool = False        # attach a decision-attribution ``why``
                                 # record to the Response (DESIGN.md §18.3)
    deadline_ms: float | None = None
                                 # remaining latency budget (SLO) in ms; the
                                 # scheduler decrements queue wait before
                                 # dispatch, and the §20.3 retry loop never
                                 # sleeps past it. None = no deadline.


@dataclasses.dataclass
class Response:
    answer: str
    cached: bool
    score: float
    latency_s: float
    coalesced: bool = False   # served by attaching to an in-flight duplicate
                              # (async scheduler, DESIGN.md §12.3)
    context: bool = False     # looked up under a non-empty session turn
                              # window, i.e. the key was context-fused (§16)
    near_hit: bool = False    # synthesized from top-k neighbours in the
                              # [τ_lo, τ_hi) band (§17) — ``cached`` stays
                              # False: near-hits are provenance-distinct
                              # from exact reuse
    trace_id: str = ""        # RequestTrace id when tracing retained this
                              # request's journey ("" when tracing is off)
    why: dict | None = None   # decision attribution (§18.3); only set when
                              # the request opted in via Request.explain or
                              # the engine forces explain_responses=True
    degraded: bool = False    # served from the best cached neighbour under
                              # the relaxed degraded floor because the
                              # backend was unavailable / budget exhausted
                              # (§20.4) — never admitted to the slab
    error: str = ""           # non-empty when this row's backend call
                              # failed and no degraded answer was servable;
                              # the async scheduler converts it into a
                              # per-row BackendError (§20.2)


#: Row used to right-pad a partial batch up to the engine's fixed batch
#: size. Its empty query embeds to the zero vector (cosine 0 against every
#: slab key — always a miss), and the ``valid`` mask threaded through the
#: fused step guarantees pad rows never touch counters or the slab.
PAD_REQUEST = Request(query="", category="__pad__", source_id=-1)


class Batcher:
    """Fixed-size batching with padding (sync analogue of a request queue)."""

    def __init__(self, batch_size: int = 32):
        self.batch_size = batch_size

    def batches(self, requests: Sequence[Request]):
        for i in range(0, len(requests), self.batch_size):
            yield list(requests[i:i + self.batch_size])

    def pad(self, batch: list[Request]) -> tuple[list[Request], int]:
        """Right-pad ``batch`` to the fixed batch size (DESIGN.md §12.2).

        Returns ``(padded_batch, n_valid)``. Every admission batch — the
        final partial batch of a sync workload or a deadline flush from the
        async scheduler — then shares ONE compiled shape, instead of
        retracing the fused step per distinct ragged size. Callers must
        route only the first ``n_valid`` rows into metrics and responses.
        """
        n = len(batch)
        if n >= self.batch_size:
            return list(batch), n
        return list(batch) + [PAD_REQUEST] * (self.batch_size - n), n


class CachedEngine:
    def __init__(self, cache_config: CacheConfig, backend, *,
                 embedder: HashEmbedder | None = None,
                 tokenizer: HashTokenizer | None = None,
                 judge: Callable[[Request, int], bool] | None = None,
                 batch_size: int = 32,
                 policy=None,
                 index=None,
                 rebuild_every: int = 2048,
                 use_fused_step: bool = True,
                 registry=None,
                 fusion=None,
                 session_ttl_s: float | None = 1800.0,
                 max_sessions: int = 4096,
                 synthesizer=None,
                 tracer: Tracer | None = None,
                 events=None,
                 explain_responses: bool = False,
                 resilience=None,
                 mesh=None,
                 cache_axes: tuple = ("data",)):
        # ``policy``: optional threshold policy (e.g. AdaptiveThreshold —
        # paper §2.10 future work). With an adaptive policy the engine feeds
        # judged hit outcomes back after every batch, closing the paper's
        # proposed precision-tracking control loop.
        # ``index``: optional ANN index (e.g. IVFIndex). The index is refit
        # every ``rebuild_every`` inserts — the analogue of the paper's
        # periodic HNSW rebalancing (§2.4); a no-op for stateless indexes.
        # ``registry``: optional TenantRegistry — partitions the slab into
        # per-tenant regions and routes each Request.tenant through the
        # compiled step (DESIGN.md §13). None = single-tenant (unchanged).
        # ``fusion``: optional ContextFusion strategy (DESIGN.md §16) —
        # attaches a SessionStore (TTL ``session_ttl_s`` on the engine's
        # tick clock, LRU-capped at ``max_sessions``) and fuses each
        # session row's turn window into its lookup/insert key inside the
        # compiled step. None = single-turn (unchanged).
        # ``synthesizer``: optional near-hit Synthesizer (DESIGN.md §17) —
        # band rows ([τ_lo, τ_hi) scores) are served by composing from
        # their top-k neighbours instead of a full backend call, and the
        # synthesized answer is admitted into the slab under the query's
        # own key. Requires a band policy; passing a synthesizer with
        # policy=None defaults the policy to BandPolicy(tau_hi=threshold).
        # None = binary hit/miss (unchanged — the band masks are all-False
        # and the compiled step is identical to the band-less program).
        # ``tracer``: optional repro.obs.Tracer (DESIGN.md §18.1) — threads
        # per-request stage spans through serve_batch. None = a disabled
        # Tracer: every hook is the shared NULL_TRACE singleton, so the
        # hot path allocates nothing.
        # ``events``: optional repro.obs.EventLog — one structured event
        # per serve step (batch composition + CacheStats delta, §18.4).
        # ``explain_responses``: force a ``why`` record onto EVERY
        # response (demos/debugging); normally per-request opt-in via
        # Request.explain.
        # ``resilience``: optional ResilienceConfig (DESIGN.md §20) — the
        # miss path gains deadline-budgeted retries, a circuit breaker and
        # degraded-mode serving from cached neighbours. None = a single
        # backend attempt whose failure marks only its own rows (§20.2);
        # with no faults every path is bit-identical to the pre-§20 engine.
        # ``mesh``: optional jax.sharding.Mesh — wraps the cache in a
        # DistributedCache (DESIGN.md §19): the slab is sharded over
        # ``cache_axes`` and every jitted call below goes through the
        # shard_map'd step. None = single-device (unchanged).
        if synthesizer is not None and policy is None:
            from repro.generative.policy import BandPolicy
            policy = BandPolicy(tau_hi=cache_config.threshold)
        self.synthesizer = synthesizer
        self.registry = registry
        self.mesh = mesh
        num_shards = 1
        if mesh is not None:
            from repro.core.distributed import shard_axes
            num_shards = shard_axes(mesh, tuple(cache_axes))
        self._num_shards = num_shards
        partition = None
        if registry is not None:
            partition = registry.partition(cache_config.capacity)
            if min(partition.sizes) // num_shards < batch_size:
                # the per-tenant ring guarantees distinct slots only while a
                # batch's rows per tenant fit inside the tenant's region —
                # on a mesh, inside the *per-shard* slice of the region
                # (parked rows of a masked insert may wrap otherwise)
                raise ValueError(
                    f"smallest tenant region ({min(partition.sizes)} slots "
                    f"/ {num_shards} shard(s), tenant "
                    f"{partition.names[partition.sizes.index(min(partition.sizes))]!r}) "
                    f"is below the batch size ({batch_size}); grow the slab "
                    "or the tenant's share/quota")
            self._tenant_index = {n: i for i, n in enumerate(partition.names)}
        elif cache_config.capacity // num_shards < batch_size:
            raise ValueError(
                f"per-shard capacity ({cache_config.capacity} slots / "
                f"{num_shards} shard(s)) is below the batch size "
                f"({batch_size}); grow the slab or shrink the mesh")
        base_cache = SemanticCache(cache_config, policy=policy, index=index,
                                   partition=partition, fusion=fusion)
        if mesh is not None:
            from repro.core.distributed import DistributedCache
            self.cache = DistributedCache(base_cache, mesh,
                                          cache_axes=tuple(cache_axes))
        else:
            self.cache = base_cache
        self.fusion = fusion
        self.sessions = None
        if fusion is not None:
            from repro.context.session import SessionStore
            self.sessions = SessionStore(
                window=fusion.window, dim=cache_config.dim,
                ttl=session_ttl_s, max_sessions=max_sessions)
        self.runtime: CacheRuntime = self.cache.init()
        self.use_fused_step = use_fused_step
        self.rebuild_every = rebuild_every
        self._inserts_since_rebuild = 0
        self._needs_refit = True
        self._rebuild_rng = jax.random.PRNGKey(17)
        self.backend = backend
        self.embedder = embedder or HashEmbedder(dim=cache_config.dim)
        self.tokenizer = tokenizer or HashTokenizer()
        self.judge = judge
        self.batcher = Batcher(batch_size)
        self.metrics = ServingMetrics()
        self.tracer = tracer if tracer is not None else Tracer()
        self.events = events
        self.explain_all = explain_responses
        self.resilience = resilience
        self._now = 0.0
        # One uniform set of jitted pure functions — no index/policy
        # branches. The runtime is owned linearly (each call's output
        # replaces self.runtime), so its buffers are donated: slab updates
        # are in-place at the XLA level instead of copying the slab per
        # batch. The peek must NOT donate — the same runtime is fed to the
        # fused step right after.
        # ``tid`` is the per-row tenant-id vector (None on a single-tenant
        # engine — an empty pytree, so the compiled signature is unchanged).
        # ``w``/``wl`` are the per-row session turn windows (None on a
        # fusion-less engine — same empty-pytree trick, §16.3).
        # Each function's name names its device program (``jit_peek``,
        # ``jit_step``, ...) in profiles and compile logs.
        cache = self.cache
        compile_log()          # count compiles from the first program on

        def lookup(rt, q, t, tid, w, wl):
            return cache.lookup(rt, q, t, tenant_id=tid, window=w,
                                window_len=wl)

        def peek(rt, q, t, tid, w, wl):
            return cache.lookup(rt, q, t, update_counters=False,
                                tenant_id=tid, window=w, window_len=wl)[0]

        def insert(rt, q, v, vl, t, sid, m, tid):
            return cache.insert(rt, q, v, vl, t, source_id=sid, mask=m,
                                tenant_id=tid)

        def step(rt, q, mv, mvl, t, sid, peeked, valid, tid, w, wl):
            return cache.step(rt, q, mv, mvl, t, source_id=sid,
                              peeked=peeked, valid=valid, tenant_id=tid,
                              window=w, window_len=wl)

        # standalone fusion op for the separate (reference) path, which
        # must insert the same fused keys the fused step would
        def fuse(rt, q, w, wl):
            return cache._maybe_fuse(rt, q, w, wl)

        # top-k neighbour payload gather for the near-hit path (§17.3):
        # pure read of the slab, never donated — the runtime is reused by
        # the fused step right after, exactly like the peek
        def gather_topk(rt, res):
            return cache.gather_topk(rt, res)

        def refit(rt, t, k):
            return cache.refit(rt, t, k)

        self._lookup_jit = jax.jit(lookup, donate_argnums=(0,))
        self._peek_jit = jax.jit(peek)
        self._insert_jit = jax.jit(insert, donate_argnums=(0,))
        self._step_jit = jax.jit(step, donate_argnums=(0,))
        self._fuse_jit = jax.jit(fuse)
        self._gather_topk_jit = jax.jit(gather_topk)
        self._refit_jit = jax.jit(refit, donate_argnums=(0,))

    # -- runtime views (read-only conveniences) ------------------------- #
    @property
    def state(self):
        return self.runtime.state

    @property
    def stats(self):
        return self.runtime.stats

    @property
    def policy_state(self):
        return self.runtime.policy_state

    def tenant_stats(self) -> dict:
        """Device-side per-tenant accounting (TenancyState counters), keyed
        by tenant name. Empty dict on a single-tenant engine."""
        t = self.runtime.tenancy
        if t is None:
            return {}
        # on a mesh the counters are stacked per-shard (S, T); the reduce
        # is exact because each event is attributed on exactly one shard
        t = t.reduced()
        part = self.cache.partition
        return {
            name: {
                "lookups": int(t.lookups[i]),
                "hits": int(t.hits[i]),
                "misses": int(t.lookups[i]) - int(t.hits[i]),
                "inserts": int(t.inserts[i]),
                "evictions": int(t.evictions[i]),
                "region_slots": part.sizes[i],
            }
            for i, name in enumerate(part.names)
        }

    # ------------------------------------------------------------------ #
    def save_cache(self, path: str) -> None:
        """Persist the *entire* runtime (the Redis RDB-snapshot analogue):
        slab, counters, policy state and index state — a restarted engine
        resumes serving hits immediately, keeps its adapted threshold and
        pays no forced index rebuild."""
        from repro.training.checkpoint import save_checkpoint
        part = self.cache.partition
        save_checkpoint(path, {"runtime": self.runtime},
                        metadata={"now": self._now,
                                  "dim": self.cache.config.dim,
                                  "capacity": self.cache.config.capacity,
                                  "index": type(self.cache.index).__name__,
                                  "policy": type(self.cache.policy).__name__,
                                  # static partition map: restores must be
                                  # built with the same tenant layout or the
                                  # per-tenant ring pointers/regions disagree
                                  "partition": None if part is None
                                  else part.manifest(),
                                  "fusion": None if self.fusion is None
                                  else type(self.fusion).__name__,
                                  # mesh shape + shard layout: a restore
                                  # onto a different layout must go through
                                  # reshard_runtime, not a strict load
                                  "shard_layout": None if self.mesh is None
                                  else self.cache.shard_layout()})

    def load_cache(self, path: str, *, reshard: bool = True) -> None:
        import json
        import os
        from repro.training.checkpoint import (load_checkpoint,
                                               open_checkpoint,
                                               reshard_runtime)
        # Fusion-aware restore (§16.5). The fusion leaf group follows the
        # tenancy None-keeps-the-treedef contract, so the npz either has
        # "runtime/fusion/..." keys (session-era snapshot) or none at all.
        # open_checkpoint reads every member eagerly, so a truncated or
        # corrupt snapshot fails HERE with CheckpointCorruptError naming
        # the file (§20 crash-safety), not with an arbitrary zipfile
        # traceback halfway through the restore.
        flat = open_checkpoint(path)
        saved_keys = list(flat)
        has_fusion_keys = any(k.startswith("runtime/fusion/")
                              for k in saved_keys)
        if has_fusion_keys and self.fusion is None:
            # silently dropping learned fusion weights would change every
            # fused key this snapshot's slab entries were stored under
            raise ValueError(
                f"snapshot {path!r} carries context-fusion weights "
                "(runtime/fusion/*) but this engine has no fusion "
                "strategy; construct the engine with fusion=... to load it")
        # Shard-layout gate (§19.5): the manifest records the mesh shape
        # the snapshot was taken under. Same layout -> strict load; a
        # different shard count -> reshard-on-load (or refuse).
        meta = {}
        manifest = path + ".manifest.json"
        if os.path.exists(manifest):
            with open(manifest) as f:
                meta = json.load(f).get("metadata", {})
            # partition maps are static config: a snapshot taken under one
            # tenant layout silently mis-regions under another, so verify
            saved = meta.get("partition")
            part = self.cache.partition
            current = None if part is None else part.manifest()
            if saved != current:
                raise ValueError(
                    f"snapshot partition map {saved} does not match this "
                    f"engine's {current}; rebuild the engine with the "
                    "registry the snapshot was taken under")
        saved_layout = meta.get("shard_layout")
        saved_shards = 1 if saved_layout is None \
            else int(saved_layout["num_shards"])
        if saved_shards != self._num_shards:
            if not reshard:
                raise ValueError(
                    f"snapshot {path!r} was taken on {saved_shards} shard(s) "
                    f"but this engine runs {self._num_shards}; pass "
                    "reshard=True to re-place the entries on load")
            # Cross-layout restore: re-place live entries into this
            # layout's rings on the host, keep a fresh index and force a
            # refit (the saved buckets hold old-placement local slot ids).
            fresh = self.cache.init()
            restored_runtime = reshard_runtime(
                flat, fresh,
                old_shards=saved_shards, new_shards=self._num_shards,
                partition=self.cache.partition)
            needs_refit = True
        else:
            template_runtime = self.runtime
            if not has_fusion_keys and self.fusion is not None:
                # pre-session snapshot into a session-enabled engine is
                # fine: restore the shared leaves, keep this engine's fresh
                # fusion state (slab keys in that snapshot were never
                # fused, and raw single-turn lookups still match them
                # bit-identically)
                template_runtime = self.runtime.replace(fusion=None)
            restored = load_checkpoint(path, {"runtime": template_runtime})
            restored_runtime = restored["runtime"]
            # index state was checkpointed with the slab — no forced rebuild
            needs_refit = False
        if restored_runtime.fusion is None and self.runtime.fusion is not None:
            restored_runtime = restored_runtime.replace(
                fusion=self.runtime.fusion)
        if self.mesh is not None:
            restored_runtime = self.cache.place(restored_runtime)
        self.runtime = restored_runtime
        # restore the TTL clock: slab expiries are *absolute* deadlines, so
        # resuming at now=0 would extend every entry's remaining lifetime.
        # save_checkpoint names the manifest after the path it was *given*
        # (np.savez appends .npz to the data file only), so mirror that.
        self._now = float(meta.get("now", self._now))
        self._needs_refit = needs_refit
        self._inserts_since_rebuild = 0

    def _maybe_refit(self) -> None:
        if self._needs_refit or \
                self._inserts_since_rebuild >= self.rebuild_every:
            self._rebuild_rng, k = jax.random.split(self._rebuild_rng)
            self.runtime = self._refit_jit(
                self.runtime, jnp.float32(self._now), k)
            self._needs_refit = False
            self._inserts_since_rebuild = 0

    def tick(self, seconds: float) -> None:
        """Advance the TTL clock (tests drive expiry deterministically)."""
        self._now += seconds

    def _session_windows(self, batch):
        """Per-row session turn windows for a (possibly padded) batch.

        Returns ``(window (B, W, d), window_len (B,), has_ctx)`` — or
        ``(None, None, [False]*B)`` on a fusion-less engine (None is an
        empty pytree, so the compiled signature is unchanged). Sessionless
        and pad rows get a zero window with length 0, which the fusion op
        passes through bit-identically (§16.3) — session and stateless
        rows share one compiled program at every mix.
        """
        if self.sessions is None:
            return None, None, [False] * len(batch)
        win = np.zeros((len(batch), self.sessions.window_size,
                        self.sessions.dim), dtype=np.float32)
        wlen = np.zeros((len(batch),), dtype=np.int32)
        for i, r in enumerate(batch):
            if r is PAD_REQUEST or not r.session:
                continue
            w, c = self.sessions.window_for(r.tenant, r.session, self._now)
            win[i] = w
            wlen[i] = c
        return (jnp.asarray(win), jnp.asarray(wlen),
                [bool(c) for c in wlen])

    def _canonical_keys(self, result, emb, win, wlen) -> np.ndarray:
        """(B, d) canonical slab key per row (§16.1): the matched entry's
        stored key on a hit, the row's own fused key — exactly what the
        step inserted — on a miss. Appending these (not raw embeddings)
        makes two conversations in the same dialogue state converge to
        identical turn windows, so their fused keys match at every depth."""
        fused = self._fuse_jit(self.runtime, emb, win, wlen)
        matched = jnp.take(self.runtime.state.keys, result.index,
                           axis=0).astype(jnp.float32)
        if self.cache.config.key_dtype == jnp.int8:
            matched = matched / 127.0          # symmetric unit-row quant
        return np.asarray(jnp.where(result.hit[:, None], matched, fused),
                          dtype=np.float32)

    def _append_turns(self, batch, n_valid: int, keys_np: np.ndarray,
                      skip=()) -> None:
        """Push each served session row's canonical turn key (§16.1) —
        after the batch, so a turn's own key never fuses into its own
        lookup and co-batched turns of one session can't race. ``skip``
        holds failed/degraded row indexes (§20): those turns were never
        answered from the slab, so their keys must not advance the
        session window."""
        if self.sessions is None:
            return
        for i in range(n_valid):
            r = batch[i]
            if r.session and i not in skip:
                self.sessions.append(r.tenant, r.session, keys_np[i],
                                     self._now)

    def _tenant_ids(self, batch) -> "jax.Array | None":
        """(B,) int32 tenant ids for a (possibly padded) batch; None on a
        single-tenant engine. Pad rows route as tenant 0 — harmless, since
        the ``valid`` mask keeps them out of every counter and the slab."""
        if self.registry is None:
            return None
        ids = []
        for r in batch:
            if r is PAD_REQUEST:
                ids.append(0)
            else:
                try:
                    ids.append(self._tenant_index[r.tenant])
                except KeyError:
                    raise KeyError(
                        f"unknown tenant {r.tenant!r}; registered: "
                        f"{tuple(self._tenant_index)}") from None
        return jnp.asarray(ids, dtype=jnp.int32)

    def warm(self, pairs, tenant: str | None = None) -> None:
        """Cache population phase (paper §3.1): embed+insert the corpus.

        On a multi-tenant engine the corpus lands in ``tenant``'s region
        (default: the registry's first tenant) — warm each tenant
        separately with its own corpus."""
        cfg = self.cache.config
        # distinct-slot guarantee: one chunk must fit inside the (per-shard
        # slice of the) target ring, else parked/written rows can alias
        bs = min(256, cfg.capacity // self._num_shards)
        tid_value = None
        if self.registry is not None:
            name = tenant if tenant is not None else self.registry.names[0]
            tid_value = self.registry.index(name)
            bs = min(bs, self.cache.partition.sizes[tid_value]
                     // self._num_shards)
        elif tenant is not None:
            raise ValueError("warm(tenant=...) needs a tenant registry")
        for i in range(0, len(pairs), bs):
            chunk = pairs[i:i + bs]
            emb = jnp.asarray(self.embedder.embed_batch(
                [p.question for p in chunk]))
            toks, lens = self.tokenizer.encode_batch(
                [p.answer for p in chunk], cfg.value_len)
            sid = jnp.asarray([p.qa_id for p in chunk], dtype=jnp.int32)
            tid = None if tid_value is None else jnp.full(
                (len(chunk),), tid_value, dtype=jnp.int32)
            self.runtime = self._insert_jit(
                self.runtime, emb, jnp.asarray(toks),
                jnp.asarray(lens), jnp.float32(self._now), sid,
                jnp.ones((len(chunk),), dtype=bool), tid)
            self._inserts_since_rebuild += len(chunk)

    # ------------------------------------------------------------------ #
    def process(self, requests: Sequence[Request]) -> list[Response]:
        out: list[Response] = []
        for batch in self.batcher.batches(requests):
            out.extend(self.serve_batch(batch))
        return out

    def _generate_misses(self, batch, miss_idx):
        """Backend call + tokenizer round-trip for the missed rows.

        Returns (token rows, lens, decoded answers, llm_time, llm_cost).
        Responses are tokenizer-normalized so the hit and miss paths emit
        byte-identical text for the same cache entry.
        """
        cfg = self.cache.config
        res = self.backend.generate(
            [batch[i].query for i in miss_idx],
            [batch[i].semantic_key for i in miss_idx])
        toks, lens = self.tokenizer.encode_batch(
            [res.answers[j] for j in range(len(miss_idx))], cfg.value_len)
        answers = {i: self.tokenizer.decode(toks[j])
                   for j, i in enumerate(miss_idx)}
        return toks, lens, answers, res.latency_s, res.cost_usd

    def _split_expired(self, batch, miss_idx):
        """Split the miss set into rows whose deadline budget is already
        spent (they go straight to degraded serving, §20.3) and rows still
        worth a backend call. No-op without a resilience config."""
        if self.resilience is None:
            return {}, list(miss_idx)
        failed: dict[int, str] = {}
        gen_idx: list[int] = []
        for i in miss_idx:
            d = batch[i].deadline_ms
            if d is not None and d <= 0.0:
                self.metrics.resilience.deadline_exhausted += 1
                failed[i] = ("DeadlineExhausted: budget spent before the "
                             "backend call")
            else:
                gen_idx.append(i)
        return failed, gen_idx

    def _resolve_misses(self, batch, miss_idx):
        """One backend resolution for the miss rows: containment + retries.

        Returns ``(result_tuple, None)`` on success or ``(None, err_msg)``
        — the caller turns ``err_msg`` into per-row degraded/error
        responses (§20.2) instead of letting the exception fail the whole
        batch. With a resilience config the call is gated by the circuit
        breaker and retried under the §20.3 backoff/deadline-budget rules;
        without one it is a single attempt whose failure is still
        contained to its own rows.
        """
        r = self.resilience
        rm = self.metrics.resilience
        if r is None:
            try:
                return self._generate_misses(batch, miss_idx), None
            except Exception as exc:
                self.metrics.resilience_seen = True
                rm.backend_failures += 1
                return None, f"{type(exc).__name__}: {exc}"
        budget_s = None
        deadlines = [batch[i].deadline_ms for i in miss_idx
                     if batch[i].deadline_ms is not None]
        if deadlines:
            # one call serves every miss row, so the tightest row's budget
            # bounds the retry schedule for the whole set
            budget_s = min(deadlines) / 1000.0
        key = batch[miss_idx[0]].query
        start = r.clock()
        attempt = 0
        while True:
            if r.breaker is not None and not r.breaker.allow():
                rm.breaker_short_circuits += 1
                return None, ("BreakerOpen: circuit breaker is open; "
                              "backend call short-circuited")
            attempt += 1
            try:
                out = self._generate_misses(batch, miss_idx)
            except Exception as exc:
                if r.breaker is not None:
                    r.breaker.record_failure()
                rm.backend_failures += 1
                delay = r.retry.backoff_s(attempt, key=key)
                elapsed = r.clock() - start
                if not r.retry.allows(attempt, elapsed_s=elapsed,
                                      next_backoff_s=delay,
                                      budget_s=budget_s):
                    if (budget_s is not None
                            and attempt < r.retry.max_attempts):
                        rm.deadline_exhausted += 1
                    return None, f"{type(exc).__name__}: {exc}"
                rm.retries += 1
                r.sleep(delay)
                continue
            if r.breaker is not None:
                r.breaker.record_success()
            if attempt > 1:
                rm.retry_successes += 1
            return out, None

    def _degraded_floor(self) -> float:
        """Relaxed score floor for degraded serving: explicit config wins,
        else the band policy's ``degraded_lo`` edge, else 0.55 (§20.4)."""
        r = self.resilience
        if r is not None and r.degraded_band_lo is not None:
            return float(r.degraded_band_lo)
        dl = getattr(self.cache.policy, "degraded_lo", None)
        return 0.55 if dl is None else float(dl)

    def _serve_degraded(self, batch, failed, result):
        """Degraded-mode serving (§20.4): each failed miss row is offered
        the best cached neighbour at or above the relaxed degraded floor —
        synthesis first when a synthesizer is installed, else the dominant
        neighbour's stored answer verbatim. Returns row -> (answer, score,
        source_id). Served rows stay OUT of the slab (the caller clears
        their ``valid`` bits): a degraded answer is another entry's answer
        under the wrong key, and admitting it would keep poisoning exact
        lookups for this query long after the outage clears."""
        r = self.resilience
        if r is None or not r.degraded_serving or not failed:
            return {}
        floor = self._degraded_floor()
        rm = self.metrics.resilience
        payload = self._gather_topk_jit(self.runtime, result)
        nb_slot = np.asarray(result.topk_index)
        nb_score = np.asarray(payload["score"])
        nb_sid = np.asarray(payload["source_id"])
        nb_vals = np.asarray(payload["values"])
        out: dict[int, tuple[str, float, int]] = {}
        for i in sorted(failed):
            cand = [j for j in range(nb_slot.shape[1])
                    if nb_slot[i, j] >= 0 and nb_score[i, j] >= floor]
            if not cand:
                rm.degraded_failed += 1
                continue
            served = None
            if self.synthesizer is not None:
                from repro.generative.synthesize import Neighbour
                neighbours = [
                    Neighbour(slot=int(nb_slot[i, j]),
                              score=float(nb_score[i, j]),
                              source_id=int(nb_sid[i, j]),
                              answer=self.tokenizer.decode(nb_vals[i, j]))
                    for j in cand]
                syn = self.synthesizer.synthesize(batch[i].query, neighbours)
                if syn is not None:
                    served = (syn.answer, float(nb_score[i, cand[0]]),
                              int(syn.source_id))
            if served is None:
                j = cand[0]      # neighbours arrive score-descending
                served = (self.tokenizer.decode(nb_vals[i, j]),
                          float(nb_score[i, j]), int(nb_sid[i, j]))
            out[i] = served
            rm.degraded_served += 1
        return out

    def _synthesize_near(self, batch, n_valid: int, result):
        """Host-side near-hit synthesis (§17.3), shared by both serve paths.

        For every band row ([τ_lo, τ_hi) score) of ``result``, gather the
        row's visible top-k neighbours (one jitted slab read) and offer
        them to the synthesizer. Returns ``(syn_by_row, syn_time, syn_cost)``
        — ``syn_by_row`` maps row index -> ``Synthesis`` for the rows it
        converted; abstained rows are simply absent and fall back to the
        full backend call like any miss.
        """
        if self.synthesizer is None:
            return {}, 0.0, 0.0
        near = np.asarray(result.near)
        if not near[:n_valid].any():
            return {}, 0.0, 0.0
        from repro.generative.synthesize import Neighbour
        payload = self._gather_topk_jit(self.runtime, result)
        nb_slot = np.asarray(result.topk_index)
        nb_score = np.asarray(payload["score"])
        nb_sid = np.asarray(payload["source_id"])
        nb_vals = np.asarray(payload["values"])
        syn_by_row: dict[int, object] = {}
        syn_time = syn_cost = 0.0
        for i in range(n_valid):
            if not near[i]:
                continue
            neighbours = [
                Neighbour(slot=int(nb_slot[i, j]),
                          score=float(nb_score[i, j]),
                          source_id=int(nb_sid[i, j]),
                          answer=self.tokenizer.decode(nb_vals[i, j]))
                for j in range(nb_slot.shape[1]) if nb_slot[i, j] >= 0]
            syn = self.synthesizer.synthesize(batch[i].query, neighbours)
            if syn is not None:
                syn_by_row[i] = syn
                syn_time += syn.latency_s
                syn_cost += syn.cost_usd
        return syn_by_row, syn_time, syn_cost

    def _why_snapshot(self, result):
        """Decision-time attribution snapshot (§18.3): the policy state and
        the top-k neighbour payload, pulled to host BEFORE the fused step
        donates the runtime buffers and the judged feedback moves the
        edges — these are the values the decision was actually made under."""
        payload = self._gather_topk_jit(self.runtime, result)
        return (np.asarray(self.runtime.policy_state),
                {"slots": np.asarray(result.topk_index),
                 "scores": np.asarray(result.topk_score),
                 "source_ids": np.asarray(payload["source_id"])})

    def _build_whys(self, batch, n_valid, tid, hit, near_served, scores,
                    matched_idx, matched_sid, near_row, has_ctx,
                    syn_by_row, why_ps, why_topk):
        """Per-row ``why`` records for the rows that opted in (§18.3)."""
        tid_np = None if tid is None else np.asarray(tid)
        edges_by_tenant: dict = {}
        whys: list = [None] * n_valid
        for i in range(n_valid):
            if not (self.explain_all or batch[i].explain):
                continue
            tix = None if tid_np is None else int(tid_np[i])
            if tix not in edges_by_tenant:
                edges_by_tenant[tix] = effective_edges(
                    self.cache.policy, why_ps, self.cache.partition, tix)
            whys[i] = build_why(
                i, request=batch[i], hit=bool(hit[i]),
                near_served=bool(near_served[i]), score=float(scores[i]),
                matched_slot=int(matched_idx[i]),
                matched_source_id=int(matched_sid[i]),
                topk_slots=why_topk["slots"][i],
                topk_scores=why_topk["scores"][i],
                topk_source_ids=why_topk["source_ids"][i],
                edges=edges_by_tenant[tix],
                session_fused=bool(has_ctx[i]),
                synthesizer_present=self.synthesizer is not None,
                near_band=bool(near_row[i]),
                synthesis_source_id=(syn_by_row[i].source_id
                                     if i in syn_by_row else None))
        return whys

    def explain(self, query: str, *, tenant: str = "default",
                session: str = "") -> dict:
        """Offline decision attribution (§18.3): what WOULD the cache do
        with ``query`` right now, and why? Pure peek — no counters move,
        nothing is inserted, no synthesis or backend call is attempted
        (``in_band`` tells the near-hit story; ``dry_run`` marks the
        record as a what-if)."""
        req = Request(query=query, tenant=tenant, session=session,
                      explain=True)
        batch, _ = self.batcher.pad([req])
        tid = self._tenant_ids(batch)
        emb = jnp.asarray(self.embedder.embed_batch(
            [r.query for r in batch]))
        win, wlen, has_ctx = self._session_windows(batch)
        peek = self._peek_jit(self.runtime, emb, jnp.float32(self._now),
                              tid, win, wlen)
        why_ps, why_topk = self._why_snapshot(peek)
        tix = None if tid is None else int(np.asarray(tid)[0])
        edges = effective_edges(self.cache.policy, why_ps,
                                self.cache.partition, tix)
        why = build_why(
            0, request=req, hit=bool(np.asarray(peek.hit)[0]),
            near_served=False, score=float(np.asarray(peek.score)[0]),
            matched_slot=int(np.asarray(peek.index)[0]),
            matched_source_id=int(np.asarray(peek.source_id)[0]),
            topk_slots=why_topk["slots"][0],
            topk_scores=why_topk["scores"][0],
            topk_source_ids=why_topk["source_ids"][0],
            edges=edges, session_fused=bool(has_ctx[0]),
            synthesizer_present=False,
            near_band=bool(np.asarray(peek.near)[0]),
            synthesis_source_id=None)
        why["dry_run"] = True
        return why

    def _release_hits(self, batch, n_valid: int, peek, peek_hit, has_ctx,
                      traces, own_traces: bool, clock, cache_time: float,
                      on_hits_ready) -> dict[int, str]:
        """Early release (DESIGN.md §12.1): hand the peek's hit rows to
        ``on_hits_ready(rows, responses)`` before the batch's misses go to
        the backend. Only a batch with a plain miss (a row neither hit nor
        in the near band) releases anything; rows that ask for a ``why``
        are held, since theirs is built from the finished batch. Returns
        row -> decoded answer of the released rows, for the final
        responses to reuse."""
        hit = peek_hit[:n_valid]
        if hit.all() or (hit | np.asarray(peek.near)[:n_valid]).all():
            return {}       # no backend call: the batch ends one step on
        rows = [i for i in range(n_valid) if peek_hit[i]
                and not (self.explain_all or batch[i].explain)]
        if not rows:
            return {}
        vals = np.asarray(peek.values)
        scores = np.asarray(peek.score)
        answers = {i: self.tokenizer.decode(vals[i]) for i in rows}
        responses = [Response(
            answer=answers[i], cached=True, score=float(scores[i]),
            latency_s=cache_time / n_valid, context=has_ctx[i],
            trace_id="" if traces is None else traces[i].trace_id)
            for i in rows]
        if clock is not None:
            # the batch's stages so far: ``near_synthesis`` has just opened
            stages = clock.spans[:-1]
            ready = next(s.t1 for s in stages if s.name == "peek_sync")
            for i in rows:
                tr = traces[i]
                if not tr:
                    continue
                tr.spans.extend(stages)
                tr.annotate(row=i, batch_rows=n_valid, path="hit",
                            ready_t=ready, release="early")
                if own_traces:
                    self.tracer.finish(tr, e2e_s=tr.span_sum_s)
        self.metrics.hits_released_early += len(rows)
        on_hits_ready(rows, responses)
        return answers

    def serve_batch(self, batch: list[Request], *,
                    record_path_latency: bool = True,
                    traces: list | None = None,
                    on_hits_ready: Callable | None = None
                    ) -> list[Response]:
        """Serve ONE admission batch: peek -> backend -> fused step commit.

        This is the pure device-side serve path (DESIGN.md §12.1): it does
        no re-batching of its own, so both the sync ``process()`` loop and
        the async continuous-batching scheduler drive it directly. On the
        fused path partial batches are right-padded to the fixed batch
        size (``Batcher.pad``); the ``valid`` mask keeps pad rows out of
        every counter, the judge, the metrics and the slab.

        ``record_path_latency=False`` skips the per-request hit/miss
        latency samples — the async scheduler records true end-to-end
        (queue wait + service) latencies itself instead of these
        batch-amortized service times.

        ``traces`` is an optional per-row list of ``RequestTrace``s (the
        async scheduler passes the entries' traces, already carrying their
        queue-side spans); engine stage spans are appended to each and the
        CALLER finishes them. When ``traces`` is None and the engine's
        tracer is collecting, serve_batch owns the traces itself: it
        starts one per real row and finishes it with the batch wall time
        (the sync ``process()`` path). When tracing is off there is no
        stage clock and no per-request allocation (§18.2).

        With a stage clock each row's trace gets the batch's stages and
        their children (``peek_sync``, ``step_sync``, ``fetch``,
        ``policy``) and ``meta["ready_t"]``, the instant the row's answer
        was known.

        ``on_hits_ready(rows, responses)``, when given, is called on the
        fused path right after the peek's sync, before near-hit synthesis
        and the backend, with the batch's hit rows and their responses
        (built from the peek, which the step commits unchanged), so that a
        caller can answer them without waiting for the batch's backend
        call. It is called only when some row goes to the backend (a miss
        outside the near band); a batch of hits ends one step later and is
        returned whole. Rows that ask for a ``why`` are held to the end,
        as are near-hits. The rest of the batch runs as without the
        callback, and the returned list still holds every row. A released
        row's trace keeps the stages up to the peek and is annotated
        ``release="early"``; a caller that passed ``traces`` finishes it at
        the release. Every other row's is annotated ``release="batch"``.
        """
        n_valid = len(batch)
        if self.resilience is not None:
            # surface the resilience section in metrics summaries even
            # before the first fault (callers replace engine.metrics, so
            # this cannot live in __init__)
            self.metrics.resilience_seen = True
        clock = self.tracer.stage_clock("embed")
        own_traces = False
        if clock is not None and traces is None:
            traces = [self.tracer.start() for _ in range(n_valid)]
            own_traces = True
        ev_stats0 = None
        if self.events is not None:
            ev_stats0 = {k: int(getattr(self.stats, k)) for k in
                         ("lookups", "hits", "misses", "inserts",
                          "expired_evictions")}
        if self.registry is not None and len(batch) > self.batcher.batch_size:
            # the per-tenant ring guarantees distinct slots only while a
            # batch's rows per tenant fit in the tenant's region, which the
            # constructor proved for batches up to batch_size; an oversized
            # admission batch (a mis-aligned SchedulerConfig.max_batch)
            # could silently collide slots, so fail loudly instead
            raise ValueError(
                f"tenant-partitioned engine got a {len(batch)}-row batch "
                f"but batch_size={self.batcher.batch_size}; align the "
                "scheduler's max_batch with the engine batch size "
                "(AsyncCacheServer's default config does)")
        if self.use_fused_step:
            batch, n_valid = self.batcher.pad(batch)
        cfg = self.cache.config
        n = len(batch)
        tid = self._tenant_ids(batch)
        if self.sessions is not None:
            # flush-path TTL sweep (§16.4): abandoned sessions die on the
            # next admission, not only if someone happens to touch them
            self.sessions.expire(self._now)
        t0 = time.perf_counter()
        emb, lookups, memo_hits = self.embedder.embed_batch_counted(
            [r.query for r in batch])
        emb = jnp.asarray(emb)
        self.metrics.embed_features += sum(lookups[:n_valid])
        self.metrics.embed_feature_memo_hits += sum(memo_hits[:n_valid])
        if clock is not None:
            for tr, n_feat, n_memo in zip(traces[:n_valid], lookups,
                                          memo_hits):
                if tr:
                    tr.annotate(embed_features=n_feat,
                                embed_memo_hits=n_memo)
        win, wlen, has_ctx = self._session_windows(batch)
        if clock is not None:
            clock.enter("device_step")
        now = jnp.float32(self._now)
        self._maybe_refit()

        llm_time = 0.0
        llm_cost = 0.0
        answers: dict[int, str] = {}
        released: dict[int, str] = {}     # early-released row -> answer
        want_why = self.explain_all or any(
            batch[i].explain for i in range(n_valid))
        why_ps = why_topk = None

        if self.use_fused_step:
            # 1. pure peek: learn the miss set without committing any state
            #    (the only slab search this batch — step commits it, §7)
            peek = self._peek_jit(self.runtime, emb, now, tid, win, wlen)
            with child_span(clock, "peek_sync"):
                peek_hit = np.asarray(peek.hit)
            cache_time = time.perf_counter() - t0
            if clock is not None:
                clock.enter("near_synthesis")
            if on_hits_ready is not None:
                released = self._release_hits(
                    batch, n_valid, peek, peek_hit, has_ctx, traces,
                    own_traces, clock, cache_time, on_hits_ready)
            if want_why:
                # attribution snapshot (§18.3) — BEFORE the fused step
                # donates the runtime and the policy feedback moves the
                # edges: these are the values the decision was made under
                why_ps, why_topk = self._why_snapshot(peek)
            # 1b. near-hit synthesis (§17.3): band rows the synthesizer
            #     converts skip the backend; abstained rows stay misses
            syn_by_row, syn_time, syn_cost = \
                self._synthesize_near(batch, n_valid, peek)
            if clock is not None:
                clock.enter("backend_call")
            miss_idx = [i for i in range(n_valid)
                        if not peek_hit[i] and i not in syn_by_row]
            # 2. backend answers the misses (paper §2.5 step 2). Failure
            #    containment (§20.2): a failed call marks only its own
            #    rows — hit/near rows of the same flush serve normally and
            #    the failed rows fall to degraded serving or an error row.
            miss_values = np.zeros((n, cfg.value_len), dtype=np.int32)
            miss_lens = np.zeros((n,), dtype=np.int32)
            failed, gen_idx = self._split_expired(batch, miss_idx)
            if gen_idx:
                out, err = self._resolve_misses(batch, gen_idx)
                if err is None:
                    toks, lens, answers, llm_time, llm_cost = out
                    miss_values[gen_idx] = np.asarray(toks)
                    miss_lens[gen_idx] = np.asarray(lens)
                else:
                    for i in gen_idx:
                        failed[i] = err
            degraded = self._serve_degraded(batch, failed, peek)
            if clock is not None:
                clock.enter("insert")
            # synthesized rows ride the same masked insert (insert mask is
            # ~hit, which includes band rows): the near-hit answer is
            # admitted under the query's own key (§17.4), carrying the
            # dominant neighbour's source_id as provenance
            sid_np = np.asarray([r.source_id for r in batch], dtype=np.int32)
            if syn_by_row:
                rows = sorted(syn_by_row)
                stoks, slens = self.tokenizer.encode_batch(
                    [syn_by_row[i].answer for i in rows], cfg.value_len)
                miss_values[rows] = np.asarray(stoks)
                miss_lens[rows] = np.asarray(slens)
                for j, i in enumerate(rows):
                    answers[i] = self.tokenizer.decode(stoks[j])
                    sid_np[i] = syn_by_row[i].source_id
            sid = jnp.asarray(sid_np)
            valid = np.zeros((n,), dtype=bool)
            valid[:n_valid] = True
            # failed AND degraded rows are never admitted (§20.4): a
            # cleared valid bit drops them from the step's insert mask and
            # every device counter, exactly like pad rows
            for i in failed:
                valid[i] = False
            # 3. one fused compiled step: commit the peek + masked insert
            t1 = time.perf_counter()
            result, self.runtime = self._step_jit(
                self.runtime, emb, jnp.asarray(miss_values),
                jnp.asarray(miss_lens), now, sid, peek, jnp.asarray(valid),
                tid, win, wlen)
            with child_span(clock, "step_sync"):
                # count the commit in cache_time
                jax.block_until_ready(result.hit)
            cache_time += time.perf_counter() - t1
            if clock is not None:
                clock.enter("respond")
            self._inserts_since_rebuild += \
                len(miss_idx) - len(failed) + len(syn_by_row)
        else:
            # reference path: pre-fuse once so the miss insert stores the
            # SAME fused key the lookup searched (parity with the fused
            # step, which fuses in-step)
            femb = emb if win is None else \
                self._fuse_jit(self.runtime, emb, win, wlen)
            result, self.runtime = self._lookup_jit(self.runtime, femb, now,
                                                    tid, None, None)
            with child_span(clock, "peek_sync"):
                lookup_hit = np.asarray(result.hit)
            cache_time = time.perf_counter() - t0
            if clock is not None:
                clock.enter("near_synthesis")
            if want_why:
                why_ps, why_topk = self._why_snapshot(result)
            syn_by_row, syn_time, syn_cost = \
                self._synthesize_near(batch, n, result)
            if clock is not None:
                clock.enter("backend_call")
            miss_idx = [i for i in range(n)
                        if not lookup_hit[i] and i not in syn_by_row]
            # per-row insert payload: backend answers for misses, admitted
            # syntheses (§17.4) for converted band rows
            row_toks: dict[int, np.ndarray] = {}
            row_lens: dict[int, int] = {}
            row_sid: dict[int, int] = {}
            failed, gen_idx = self._split_expired(batch, miss_idx)
            if gen_idx:
                out, err = self._resolve_misses(batch, gen_idx)
                if err is None:
                    toks, lens, answers, llm_time, llm_cost = out
                    for j, i in enumerate(gen_idx):
                        row_toks[i] = np.asarray(toks[j])
                        row_lens[i] = int(lens[j])
                        row_sid[i] = batch[i].source_id
                else:
                    for i in gen_idx:
                        failed[i] = err
            # failed rows simply never enter row_toks, so the subset insert
            # below skips them (§20.4); unlike the fused path the mutating
            # lookup above already counted them — accepted on the
            # reference path
            degraded = self._serve_degraded(batch, failed, result)
            if clock is not None:
                clock.enter("insert")
            if syn_by_row:
                rows = sorted(syn_by_row)
                stoks, slens = self.tokenizer.encode_batch(
                    [syn_by_row[i].answer for i in rows], cfg.value_len)
                for j, i in enumerate(rows):
                    row_toks[i] = np.asarray(stoks[j])
                    row_lens[i] = int(slens[j])
                    row_sid[i] = syn_by_row[i].source_id
                    answers[i] = self.tokenizer.decode(np.asarray(stoks[j]))
            # one subset insert in row order — the same slot-assignment
            # order the fused step's masked full-batch insert produces
            ins = sorted(row_toks)
            if ins:
                memb = femb[jnp.asarray(ins)]
                sid = jnp.asarray([row_sid[i] for i in ins], dtype=jnp.int32)
                mtid = None if tid is None else tid[jnp.asarray(ins)]
                self.runtime = self._insert_jit(
                    self.runtime, memb,
                    jnp.asarray(np.stack([row_toks[i] for i in ins])),
                    jnp.asarray([row_lens[i] for i in ins], dtype=jnp.int32),
                    now, sid, jnp.ones((len(ins),), dtype=bool), mtid)
                self._inserts_since_rebuild += len(ins)
            # this path has no sync in its insert: the lookup's hit mask
            # was already on the host, and the subset insert is left to run
            if clock is not None:
                clock.enter("respond")

        if self.sessions is not None:
            self._append_turns(batch, n_valid,
                               self._canonical_keys(result, emb, win, wlen),
                               skip=failed)

        with child_span(clock, "fetch"):
            hit = np.asarray(result.hit)
            scores = np.asarray(result.score)
            matched_sid = np.asarray(result.source_id)
            near_row = np.asarray(result.near)
            vals = np.asarray(result.values)
        near_served = np.zeros((n,), dtype=bool)
        for i in syn_by_row:
            near_served[i] = True

        # hit path: detokenize cached responses (real rows only); a
        # released row's answer was decoded at its release
        for i in range(n_valid):
            if hit[i]:
                answers[i] = released[i] if i in released \
                    else self.tokenizer.decode(vals[i])

        # judge hits (ground-truth oracle replaces GPT-4o-mini); pad rows
        # are never hits (valid-masked), so they contribute no feedback.
        # Synthesized near-hits are judged against their *synthesis*
        # provenance — the dominant neighbour's source_id (§17.3)
        positives = np.zeros((n,), dtype=bool)
        with child_span(clock, "policy"):
            if self.judge is not None:
                for i in range(n_valid):
                    if hit[i]:
                        positives[i] = self.judge(batch[i],
                                                  int(matched_sid[i]))
                    elif near_served[i]:
                        positives[i] = self.judge(
                            batch[i], int(syn_by_row[i].source_id))
                # adaptive-threshold feedback (paper §2.10): judged precision
                # nudges the threshold toward the target
                self.runtime = self.cache.update_policy(
                    self.runtime,
                    was_positive=jnp.asarray(positives & hit),
                    was_hit=jnp.asarray(hit))
                if self.synthesizer is not None:
                    # judged near-hit outcomes nudge the band's lower edge
                    # (§17.2) — the near analogue of the adaptive threshold
                    self.runtime = self.cache.update_band(
                        self.runtime,
                        was_positive=jnp.asarray(positives),
                        was_near=jnp.asarray(near_served))
            if self.judge is not None and degraded:
                # degraded answers are judged for OBSERVATION only
                # (§20.4): their precision is a brownout quality signal,
                # but they never feed the threshold/band adaptation — an
                # outage must not move the edges the healthy path serves
                # under
                rm = self.metrics.resilience
                for i, d in degraded.items():
                    rm.degraded_judged += 1
                    if self.judge(batch[i], int(d[2])):
                        rm.degraded_positives += 1

        # metrics: baseline = every query pays the LLM call. Only the
        # n_valid real rows are recorded — pad rows must not move counters,
        # and neither do failed/degraded rows (§20.4): like the device-side
        # valid mask, the host accounting sees only the rows the cache
        # actually resolved; the fault path has its own counters.
        ok_rows = [i for i in range(n_valid) if i not in failed]
        per_call = getattr(self.backend, "latency_per_call_s", None)
        baseline_time = (per_call or (llm_time / max(len(miss_idx), 1))) \
            * len(ok_rows)
        per_cost = getattr(self.backend, "cost_per_call_usd", 0.0)
        self.metrics.record_batch(
            [batch[i].category for i in ok_rows],
            hit[ok_rows], positives[ok_rows],
            judged=[self.judge is not None
                    and (bool(hit[i]) or bool(near_served[i]))
                    for i in ok_rows],
            cache_time_s=cache_time, llm_time_s=llm_time + syn_time,
            llm_cost=llm_cost + syn_cost,
            baseline_cost=per_cost * len(ok_rows),
            baseline_time=baseline_time,
            tenants=None if self.registry is None else
            [batch[i].tenant for i in ok_rows],
            contexts=None if self.sessions is None else
            [has_ctx[i] for i in ok_rows],
            nears=None if self.synthesizer is None else near_row[ok_rows],
            near_served=None if self.synthesizer is None
            else near_served[ok_rows],
            syn_cost=syn_cost, syn_time=syn_time)

        whys = None
        if want_why:
            whys = self._build_whys(
                batch, n_valid, tid, hit, near_served, scores,
                np.asarray(result.index), matched_sid, near_row, has_ctx,
                syn_by_row, why_ps, why_topk)

        per_q_latency = (cache_time + llm_time + syn_time) / max(n_valid, 1)

        def _path_of(i: int) -> str:
            if i in degraded:
                return "degraded"
            if i in failed:
                return "error"
            if hit[i]:
                return "hit"
            return "near" if near_served[i] else "miss"

        if record_path_latency:
            for i in range(n_valid):
                self.metrics.record_latency(
                    _path_of(i), per_q_latency,
                    tenant=None if self.registry is None
                    else batch[i].tenant)

        def _mk_response(i: int) -> Response:
            tr_id = "" if traces is None or i >= len(traces) \
                else traces[i].trace_id
            w = None if whys is None else whys[i]
            if i in degraded:
                ans, sc, _sid = degraded[i]
                return Response(answer=ans, cached=False, score=sc,
                                latency_s=per_q_latency, context=has_ctx[i],
                                degraded=True, trace_id=tr_id, why=w)
            if i in failed:
                return Response(answer="", cached=False,
                                score=float(scores[i]),
                                latency_s=per_q_latency, context=has_ctx[i],
                                error=failed[i], trace_id=tr_id, why=w)
            return Response(answer=answers[i], cached=bool(hit[i]),
                            score=float(scores[i]), latency_s=per_q_latency,
                            context=has_ctx[i],
                            near_hit=bool(near_served[i]),
                            trace_id=tr_id, why=w)

        responses = [_mk_response(i) for i in range(n_valid)]
        if clock is not None:
            clock.close()
            if traces is not None:
                # engine stages tile serve_batch's wall time contiguously
                # (§18.1), so for the sync path span-sum == e2e by
                # construction; the scheduler adds its queue-side and
                # handoff spans and finishes with the true e2e
                ends = {sp.name: sp.t1 for sp in clock.spans}
                batch_wall = sum(sp.duration_s for sp in clock.spans
                                 if sp.parent is None)
                for i in range(min(n_valid, len(traces))):
                    tr = traces[i]
                    if not tr or i in released:
                        continue
                    tr.spans.extend(clock.spans)
                    path = _path_of(i)
                    # when the row's answer was known: a hit's at the peek's
                    # sync, a synthesized near-hit's after synthesis, every
                    # other row's after the backend call
                    ready = ends["peek_sync"] if path == "hit" else (
                        ends["near_synthesis"] if path == "near"
                        else ends["backend_call"])
                    tr.annotate(row=i, batch_rows=n_valid, path=path,
                                ready_t=ready, release="batch")
                    if whys is not None and whys[i] is not None:
                        tr.why = whys[i]
                    if own_traces:
                        self.tracer.finish(tr, e2e_s=batch_wall)
        if self.events is not None:
            fault_kw = {} if not failed else {
                "failed": len(failed), "degraded": len(degraded)}
            self.events.emit(
                "serve_batch", rows=n_valid,
                hits=int(hit[:n_valid].sum()),
                near_hits=len(syn_by_row),
                backend_calls=len(miss_idx),
                cache_time_s=round(cache_time, 6),
                llm_time_s=round(llm_time + syn_time, 6),
                stats_delta={k: int(getattr(self.stats, k)) - ev_stats0[k]
                             for k in ev_stats0},
                **fault_kw)
        return responses
