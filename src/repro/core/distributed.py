"""DistributedCache — the ONE fused step compiled for any mesh (§2.10,
DESIGN.md §19).

The paper scales its Redis store by clustering (§2.10 "Distributed
Caching"); the JAX analogue shards the slab's capacity axis across a device
mesh and runs the *same* ``SemanticCache`` step body per shard under
``shard_map``, with the cross-shard dataflow routed through the
``repro.core.cache`` communication seam (``_LocalComm``):

  merge_topk   — per-shard top-k candidates become (score, global_slot)
                 pairs, all-gathered along the cache axes and re-top-k'd
                 per row, so the merged list is replicated and its ids are
                 *global* slot ids (``gather_topk`` / near-hit payloads
                 work on the global view unchanged);
  fetch_best   — each shard contributes its owned rows' payload, combined
                 with one masked ``psum``;
  touch        — only the owning shard touches LRU/LFU counters;
  primary      — replicated per-batch lookup/hit counts are attributed on
                 shard 0 only, so a sum-reduce over the sharded
                 ``TenancyState`` counters is exact;
  insert_take  — round-robin routing by the cumulative rank of *masked-in*
                 rows (not the raw row index: a batch where only a few
                 rows miss must not systematically skew early shards),
                 offset by the replicated global insert clock;
  prepare/finalize_insert — each shard's local ring pointer is derived
                 from the replicated clock (shard ``s`` holds
                 ``ceil((n_inserts - s) / S)`` of the first ``n_inserts``
                 round-robin inserts), and after the write the clock
                 leaves are re-replicated: ``n_inserts`` advances by the
                 global masked count, ``ptr`` parks at 0.

Slot-id convention (shard-major): global slot ``g`` lives on shard
``g // local_capacity`` at local row ``g % local_capacity`` — which is
exactly the global row index of the sharded slab arrays, so every
global-view consumer (``gather_topk``, checkpointing, explain) indexes the
placed arrays directly.

Sharded state layout:
  * ``CacheState`` matrices/vectors split on the capacity axis; ``ptr`` /
    ``n_inserts`` replicated (the insert clock is global);
  * ``CacheStats`` / policy state / fusion weights replicated;
  * ``TenancyState`` leaves stacked per shard — global ``(S, T)``, local
    ``(T,)`` — each shard runs its own per-tenant rings over its local
    region slice; ``tenant_stats`` sum-reduces counters via
    ``TenancyState.reduced()``;
  * index state stacked on the leading axis — e.g. IVF centroids
    ``(S*C, d)`` and buckets ``(S*C, cap)`` of *local* slot ids — so each
    shard trains/probes its own IVF over its own rows. Any Index plugin
    whose state follows the leading-axis convention shards transparently;
    the old "leafless index only" restriction is gone.

All static shard math (shard counts, strides) is pure-Python int — no
device op is ever dispatched for a trace-time constant.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import store
from repro.core.cache import SemanticCache, _LocalComm
from repro.core.runtime import CacheRuntime
from repro.core.types import LookupResult

Array = jax.Array


def shard_axes(mesh: Mesh, cache_axes: tuple[str, ...]) -> int:
    """Number of slab shards = product of the mesh axes the capacity axis is
    split over. Mesh axis sizes are static host ints, so this is a plain
    Python product — never a device op."""
    return math.prod(int(mesh.shape[a]) for a in cache_axes)


@dataclasses.dataclass(frozen=True)
class _MeshComm(_LocalComm):
    """Mesh specialization of the cache's cross-shard seam: the same
    ``SemanticCache`` method bodies run per shard inside ``shard_map``;
    these overrides splice collectives into the combine points."""

    axes: tuple[str, ...] = ()
    axis_sizes: tuple[int, ...] = ()
    local_capacity: int = 0

    @property
    def num_shards(self) -> int:  # type: ignore[override]
        return math.prod(self.axis_sizes)

    def shard_id(self) -> Array:
        """Row-major linear shard index over the cache axes (matches the
        order ``PartitionSpec((*axes,))`` assigns capacity blocks). Only the
        per-axis ``axis_index`` is traced; strides are Python ints."""
        sid: Any = 0
        for name, size in zip(self.axes, self.axis_sizes):
            sid = sid * size + jax.lax.axis_index(name)
        return sid

    # -- lookup seams ------------------------------------------------------
    def merge_topk(self, top_s: Array, top_i: Array) -> tuple[Array, Array]:
        k = top_i.shape[1]
        gid = jnp.where(top_i >= 0,
                        self.shard_id() * self.local_capacity + top_i, -1)
        s_all, i_all = top_s, gid
        for name in self.axes:
            s_all = jax.lax.all_gather(s_all, name, axis=1, tiled=True)
            i_all = jax.lax.all_gather(i_all, name, axis=1, tiled=True)
        merged_s, sel = jax.lax.top_k(s_all, k)          # (B, k) of (B, S*k)
        merged_i = jnp.take_along_axis(i_all, sel, axis=1)
        merged_i = jnp.where(merged_s > -jnp.inf, merged_i, -1)
        return merged_s, merged_i.astype(jnp.int32)

    def fetch_best(self, state, top0: Array) -> tuple[Array, Array, Array]:
        mine = (top0 >= 0) & (top0 // self.local_capacity == self.shard_id())
        lidx = jnp.where(mine, top0 % self.local_capacity, 0)
        payload = jnp.concatenate(
            [state.values[lidx].astype(jnp.int32),
             state.value_lens[lidx].astype(jnp.int32)[:, None],
             state.source_id[lidx].astype(jnp.int32)[:, None]], axis=1)
        payload = jnp.where(mine[:, None], payload, 0)
        payload = jax.lax.psum(payload, self.axes)       # one combine
        return payload[:, :-2], payload[:, -2], payload[:, -1]

    def touch(self, state, slot: Array, now: Array, hit: Array):
        mine = slot // self.local_capacity == self.shard_id()
        lidx = jnp.where(mine, slot % self.local_capacity, 0)
        return store.touch(state, lidx, now, hit & mine)

    def primary(self, counts: Array) -> Array:
        return jnp.where(self.shard_id() == 0, counts,
                         jnp.zeros_like(counts))

    # -- insert seams ------------------------------------------------------
    def insert_take(self, mask: Array, n_inserts: Array) -> Array:
        mi = mask.astype(jnp.int32)
        rank = jnp.cumsum(mi) - mi                   # rank among masked-in
        owner = (n_inserts + rank) % self.num_shards
        return mask & (owner == self.shard_id())

    def prepare_insert(self, state):
        # after N global round-robin inserts shard s has ceil((N - s) / S)
        s = self.num_shards
        fill = (state.n_inserts + (s - 1) - self.shard_id()) // s
        state = jax.tree_util.tree_map(lambda x: x, state)
        state.ptr = (fill % self.local_capacity).astype(jnp.int32)
        return state

    def finalize_insert(self, state, prev_n_inserts: Array, mask: Array):
        state = jax.tree_util.tree_map(lambda x: x, state)
        state.ptr = jnp.zeros((), dtype=jnp.int32)   # re-derived next insert
        state.n_inserts = (prev_n_inserts
                           + jnp.sum(mask).astype(jnp.int32))
        return state


@dataclasses.dataclass(frozen=True)
class DistributedCache:
    """Capacity-sharded ``SemanticCache`` with the same method surface.

    ``cache`` is the *global* single-device description (full capacity,
    global partition); the sharded step runs a derived shard-local cache
    (capacity / regions divided by the shard count, same index / policy /
    fusion plugins) under ``shard_map`` with a ``_MeshComm`` seam. Methods
    that never cross shards — ``expire``, ``gather_topk``,
    ``update_policy``, ``update_band``, ``_maybe_fuse`` — delegate to the
    global view directly (global slot ids ARE global row indices).

    Engine compatibility: ``config`` / ``partition`` / ``policy`` /
    ``index`` / ``fusion`` mirror the inner cache, and ``lookup`` /
    ``step`` / ``insert`` / ``refit`` take the same signatures, so
    ``CachedEngine`` and the async scheduler drive a mesh with zero
    call-site changes (DESIGN.md §19.4).
    """

    cache: SemanticCache
    mesh: Mesh
    cache_axes: tuple[str, ...] = ("data",)

    def __post_init__(self):
        object.__setattr__(self, "cache_axes", tuple(self.cache_axes))
        for a in self.cache_axes:
            if a not in self.mesh.shape:
                raise ValueError(f"mesh has no axis {a!r}: "
                                 f"{dict(self.mesh.shape)}")
        s = self.num_shards
        cfg = self.cache.config
        if cfg.capacity % s != 0:
            raise ValueError(f"capacity {cfg.capacity} not divisible by "
                             f"{s} shards")
        part = self.cache.partition
        local_part = None
        if part is not None:
            if any(sz % s for sz in part.sizes):
                raise ValueError(
                    f"per-tenant region sizes {part.sizes} must be "
                    f"multiples of the shard count {s} (size regions in "
                    f"shard-count multiples)")
            local_part = dataclasses.replace(
                part, starts=tuple(x // s for x in part.starts),
                sizes=tuple(x // s for x in part.sizes),
                capacity=cfg.capacity // s)
        local = SemanticCache(
            config=dataclasses.replace(cfg, capacity=cfg.capacity // s),
            index=self.cache.index, policy=self.cache.policy,
            partition=local_part, fusion=self.cache.fusion)
        comm = _MeshComm(
            axes=self.cache_axes,
            axis_sizes=tuple(int(self.mesh.shape[a])
                             for a in self.cache_axes),
            local_capacity=cfg.capacity // s)
        object.__setattr__(self, "local", local)
        object.__setattr__(self, "comm", comm)

    # -- engine-facing mirrors --------------------------------------------
    @property
    def config(self):
        return self.cache.config

    @property
    def partition(self):
        return self.cache.partition

    @property
    def policy(self):
        return self.cache.policy

    @property
    def index(self):
        return self.cache.index

    @property
    def fusion(self):
        return self.cache.fusion

    @property
    def num_shards(self) -> int:
        return shard_axes(self.mesh, self.cache_axes)

    @property
    def local_capacity(self) -> int:
        return self.cache.config.capacity // self.num_shards

    def shard_layout(self) -> dict:
        """JSON-able record of the sharded placement — written into
        checkpoint manifests and compared (or resharded against) on load."""
        return {
            "num_shards": self.num_shards,
            "cache_axes": list(self.cache_axes),
            "mesh_axes": [str(a) for a in self.mesh.axis_names],
            "mesh_shape": [int(self.mesh.shape[a])
                           for a in self.mesh.axis_names],
            "local_capacity": self.local_capacity,
        }

    # -- spec / placement helpers -----------------------------------------
    @property
    def _ax0(self):
        """The dim-0 PartitionSpec entry for capacity-sharded leaves."""
        return (self.cache_axes[0] if len(self.cache_axes) == 1
                else self.cache_axes)

    def _spec_sharded(self, x) -> P:
        """Leading axis split over the cache axes; scalars replicated."""
        if getattr(x, "ndim", 0) == 0:
            return P()
        return P(self._ax0, *([None] * (x.ndim - 1)))

    def _rt_specs(self, runtime: CacheRuntime) -> CacheRuntime:
        """Runtime-shaped pytree of PartitionSpecs: slab + index + tenancy
        leaves sharded on dim 0, stats / policy / fusion replicated."""
        tmap = jax.tree_util.tree_map
        rep = lambda x: P()  # noqa: E731
        return CacheRuntime(
            state=tmap(self._spec_sharded, runtime.state),
            stats=tmap(rep, runtime.stats),
            policy_state=tmap(rep, runtime.policy_state),
            index_state=tmap(self._spec_sharded, runtime.index_state),
            tenancy=tmap(self._spec_sharded, runtime.tenancy),
            fusion=tmap(rep, runtime.fusion),
        )

    def runtime_shardings(self, runtime: CacheRuntime) -> CacheRuntime:
        """NamedShardings mirroring ``_rt_specs`` (for device_put / jit)."""
        shard = lambda x: NamedSharding(  # noqa: E731
            self.mesh, self._spec_sharded(x))
        rep = lambda x: NamedSharding(self.mesh, P())  # noqa: E731
        tmap = jax.tree_util.tree_map
        return CacheRuntime(
            state=tmap(shard, runtime.state),
            stats=tmap(rep, runtime.stats),
            policy_state=tmap(rep, runtime.policy_state),
            index_state=tmap(shard, runtime.index_state),
            tenancy=tmap(shard, runtime.tenancy),
            fusion=tmap(rep, runtime.fusion),
        )

    def place(self, runtime: CacheRuntime) -> CacheRuntime:
        """device_put every leaf onto its mesh sharding."""
        return jax.tree_util.tree_map(
            jax.device_put, runtime, self.runtime_shardings(runtime))

    def init(self) -> CacheRuntime:
        """Fresh sharded runtime. Slab/stats/policy/fusion leaves come from
        the global init; per-shard leaf groups tile the *local* init along
        a new leading axis (index init is deterministic, so S tiled copies
        == S independent shard inits)."""
        g = self.cache.init()
        loc = self.local.init()
        s = self.num_shards
        tile = lambda x: (x if getattr(x, "ndim", 0) == 0  # noqa: E731
                          else jnp.concatenate([x] * s, axis=0))
        index_state = jax.tree_util.tree_map(tile, loc.index_state)
        tenancy = None
        if loc.tenancy is not None:
            tenancy = jax.tree_util.tree_map(
                lambda x: jnp.tile(x[None], (s,) + (1,) * x.ndim),
                loc.tenancy)
        return self.place(CacheRuntime(
            state=g.state, stats=g.stats, policy_state=g.policy_state,
            index_state=index_state, tenancy=tenancy, fusion=g.fusion))

    # -- global <-> shard-local views -------------------------------------
    def _to_local(self, rt: CacheRuntime) -> CacheRuntime:
        """Inside the shard body tenancy leaves arrive as (1, T) slices of
        the stacked (S, T) global; the local core wants (T,)."""
        if rt.tenancy is None:
            return rt
        return rt.replace(tenancy=jax.tree_util.tree_map(
            lambda x: x[0], rt.tenancy))

    def _from_local(self, rt: CacheRuntime) -> CacheRuntime:
        if rt.tenancy is None:
            return rt
        return rt.replace(tenancy=jax.tree_util.tree_map(
            lambda x: x[None], rt.tenancy))

    def _shard_call(self, body, operands: dict, operand_specs: dict,
                    out_specs):
        """Run ``body(operands)`` under shard_map. Optional call arguments
        are simply absent from the dict, so one wrapper serves every
        combination without None-leaf spec gymnastics; replication checking
        is off (the seam maintains replication invariants manually)."""
        return jax.shard_map(body, mesh=self.mesh,
                             in_specs=(operand_specs,), out_specs=out_specs,
                             check_vma=False)(operands)

    # -- sharded methods (same signatures as SemanticCache) ----------------
    def lookup(self, runtime: CacheRuntime, queries: Array,
               now: Array | float, *, update_counters: bool = True,
               tenant_id: Array | None = None, window: Array | None = None,
               window_len: Array | None = None
               ) -> tuple[LookupResult, CacheRuntime]:
        rt_spec = self._rt_specs(runtime)
        ops = {"runtime": runtime, "queries": queries,
               "now": jnp.asarray(now, dtype=jnp.float32)}
        specs = {"runtime": rt_spec, "queries": P(), "now": P()}
        for name, v in (("tenant_id", tenant_id), ("window", window),
                        ("window_len", window_len)):
            if v is not None:
                ops[name], specs[name] = v, P()

        def body(o):
            rt = self._to_local(o["runtime"])
            res, rt = self.local.lookup(
                rt, o["queries"], o["now"],
                update_counters=update_counters,
                tenant_id=o.get("tenant_id"), window=o.get("window"),
                window_len=o.get("window_len"), comm=self.comm)
            return res, self._from_local(rt)

        return self._shard_call(body, ops, specs, (P(), rt_spec))

    def step(self, runtime: CacheRuntime, queries: Array,
             miss_values: Array, miss_value_lens: Array,
             now: Array | float, *, source_id: Array | None = None,
             peeked: LookupResult | None = None,
             valid: Array | None = None, tenant_id: Array | None = None,
             window: Array | None = None, window_len: Array | None = None
             ) -> tuple[LookupResult, CacheRuntime]:
        """The ONE fused step, compiled for this mesh: per-shard lookup →
        merged decide → per-tenant overrides → routed masked insert →
        stats/tenancy scatter, all inside one shard_map (DESIGN.md §19.3)."""
        rt_spec = self._rt_specs(runtime)
        ops = {"runtime": runtime, "queries": queries,
               "miss_values": miss_values,
               "miss_value_lens": miss_value_lens,
               "now": jnp.asarray(now, dtype=jnp.float32)}
        specs = {k: P() for k in ops}
        specs["runtime"] = rt_spec
        for name, v in (("source_id", source_id), ("peeked", peeked),
                        ("valid", valid), ("tenant_id", tenant_id),
                        ("window", window), ("window_len", window_len)):
            if v is not None:
                ops[name], specs[name] = v, P()

        def body(o):
            rt = self._to_local(o["runtime"])
            res, rt = self.local.step(
                rt, o["queries"], o["miss_values"], o["miss_value_lens"],
                o["now"], source_id=o.get("source_id"),
                peeked=o.get("peeked"), valid=o.get("valid"),
                tenant_id=o.get("tenant_id"), window=o.get("window"),
                window_len=o.get("window_len"), comm=self.comm)
            return res, self._from_local(rt)

        return self._shard_call(body, ops, specs, (P(), rt_spec))

    def insert(self, runtime: CacheRuntime, queries: Array, values: Array,
               value_lens: Array, now: Array | float, *,
               source_id: Array | None = None, mask: Array | None = None,
               tenant_id: Array | None = None) -> CacheRuntime:
        rt_spec = self._rt_specs(runtime)
        ops = {"runtime": runtime, "queries": queries, "values": values,
               "value_lens": value_lens,
               "now": jnp.asarray(now, dtype=jnp.float32)}
        specs = {k: P() for k in ops}
        specs["runtime"] = rt_spec
        for name, v in (("source_id", source_id), ("mask", mask),
                        ("tenant_id", tenant_id)):
            if v is not None:
                ops[name], specs[name] = v, P()

        def body(o):
            rt = self._to_local(o["runtime"])
            rt = self.local.insert(
                rt, o["queries"], o["values"], o["value_lens"], o["now"],
                source_id=o.get("source_id"), mask=o.get("mask"),
                tenant_id=o.get("tenant_id"), comm=self.comm)
            return self._from_local(rt)

        return self._shard_call(body, ops, specs, rt_spec)

    def refit(self, runtime: CacheRuntime, now: Array | float, rng: Array
              ) -> CacheRuntime:
        """Per-shard index rebuild over each shard's own rows; the rng is
        folded with the shard id so shards train independent structures."""
        rt_spec = self._rt_specs(runtime)
        ops = {"runtime": runtime,
               "now": jnp.asarray(now, dtype=jnp.float32), "rng": rng}
        specs = {"runtime": rt_spec, "now": P(), "rng": P()}

        def body(o):
            rt = self._to_local(o["runtime"])
            rng_s = jax.random.fold_in(o["rng"], self.comm.shard_id())
            return self._from_local(self.local.refit(rt, o["now"], rng_s))

        return self._shard_call(body, ops, specs, rt_spec)

    # -- shard-oblivious methods: delegate to the global view --------------
    def expire(self, runtime: CacheRuntime, now: Array | float):
        return self.cache.expire(runtime, now)

    def gather_topk(self, runtime: CacheRuntime, result: LookupResult):
        # merged topk_index entries are global slot ids == global row
        # indices (shard-major), so the global gather is already correct
        return self.cache.gather_topk(runtime, result)

    def update_policy(self, runtime: CacheRuntime, **kw):
        return self.cache.update_policy(runtime, **kw)

    def update_band(self, runtime: CacheRuntime, **kw):
        return self.cache.update_band(runtime, **kw)

    def _maybe_fuse(self, runtime: CacheRuntime, queries: Array,
                    window, window_len):
        return self.cache._maybe_fuse(runtime, queries, window, window_len)

    # -- PR-1 compat shim ---------------------------------------------------
    def make_lookup_insert(self):
        """Legacy fused lookup+insert entry point, now a thin shim over the
        unified ``step`` — it compiles for ANY index plugin (the old
        ExactIndex-only restriction is gone with the fork it guarded)."""
        @functools.partial(jax.jit, donate_argnums=(0,))
        def fused(runtime, queries, miss_values, miss_value_lens,
                  source_id, now):
            result, runtime = self.step(
                runtime, queries, miss_values, miss_value_lens, now,
                source_id=source_id)
            return runtime, (result.index, result.score, result.hit,
                             result.values, result.value_lens,
                             result.source_id)
        return fused
