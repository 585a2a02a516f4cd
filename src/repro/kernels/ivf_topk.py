"""Fused Pallas IVF candidate search — in-kernel gather + scoring (DESIGN.md §15).

The IVF index (``repro.core.index.IVFIndex``) probes ``nprobe`` buckets per
query and scores the probed members. The jnp formulation materializes the
gathered candidates as a ``(B, M, d)`` tensor in HBM (``keys[cand]``) before
a separate einsum. This kernel never writes that tensor: candidate slab
rows are DMA'd HBM -> VMEM *inside* the kernel, scored from VMEM, and
folded into a running top-k.

Tiling:
  grid = (B/BB, M/BM); the candidate axis M is minor (sequential), so the
  running top-k stays resident in VMEM across candidate tiles — the same
  running-merge structure as ``cosine_topk`` (§3), with the key *block*
  stream replaced by a gathered key *tile* stream.

The TPU DMA engine moves whole HBM tiles: a slab row cannot be copied on
its own, only the aligned group of T rows that holds it (T = 8 rows for
f32, 16 for bf16, 32 for int8 — 32 bits of sublane per row group). So each
candidate fetches its T-row group, and the kernel selects the wanted row
in VMEM. Per grid step:
  1. the (BB, BM) tile of candidate slot ids arrives twice: an SMEM copy
     (scalar reads drive the DMAs) and a VMEM copy (vector mask + result
     ids). Invisible candidates — dead bucket slots, other tenants' rows,
     TTL-expired slots, per-row duplicates — are pre-masked to -1 by the
     caller (``IVFIndex.candidates``), so visibility is one compare.
  2. gather, double-buffered by query row: row r+1's BM row-group DMAs are
     in flight while row r is scored. Candidates with id < 0 start no DMA.
  3. score on the VPU: each candidate's (T, d) group is multiplied by the
     query row and summed over d, and the wanted row (id mod T) is picked
     by a sublane mask — exact f32 arithmetic, no MXU rounding. int8 slabs
     dequant by the uniform ``key_scale = 1/127`` of §14.3.
  4. merge: masked scores (id < 0 -> NEG_INF) merge into the running
     top-k via the same k-step max-and-suppress as §3.

VMEM: the two row buffers hold 2 * BM * T * d * itemsize bytes — 3.0 MiB at
BM=128, d=384 for every slab dtype (T * itemsize = 32 bytes) — well inside
the 16 MiB scoped limit. Each candidate reads T * d * itemsize bytes of
HBM (12 KiB at d=384), T times its own row: the price of tiled HBM until
the slab is laid out bucket-major.

Contract (shared with ``ref.ivf_topk_ref``): candidates with id -1 are
invisible; rows whose candidates are all -1 return exactly ``(-inf, -1)``
(§14.4). Returned ids are *slot ids* (the candidate values), not candidate
positions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cosine_topk import LANES, NEG_INF, _iter_topk, _pad_to

Array = jax.Array


def _ivf_topk_kernel(q_ref, ids_smem, ids_vmem, k_ref, ts_ref, ti_ref,
                     buf, sem, *, k: int, block_b: int, block_m: int,
                     rows: int, key_scale: float | None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ts_ref[...] = jnp.full_like(ts_ref, NEG_INF)
        ti_ref[...] = jnp.full_like(ti_ref, -1)

    def copy(r, c):
        # the aligned row group holding slot ids[r, c] -> buffer r % 2
        base = pl.multiple_of((ids_smem[r, c] // rows) * rows, rows)
        return pltpu.make_async_copy(
            k_ref.at[pl.ds(base, rows), :],
            buf.at[r % 2, pl.ds(pl.multiple_of(c * rows, rows), rows), :],
            sem.at[r % 2])

    def for_each_visible(r, action):
        def body(c, carry):
            @pl.when(ids_smem[r, c] >= 0)       # masked candidate: no DMA
            def _():
                action(copy(r, c))
            return carry
        jax.lax.fori_loop(0, block_m, body, 0)

    sub = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (1, block_m), 1)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (block_b, block_m), 0)
    s = jnp.zeros((block_b, block_m), jnp.float32)
    for_each_visible(0, lambda cp: cp.start())
    for r in range(block_b):
        if r + 1 < block_b:
            for_each_visible(r + 1, lambda cp: cp.start())
        for_each_visible(r, lambda cp: cp.wait())
        q = q_ref[pl.ds(r, 1), :]                       # (1, d)

        def score(c, s_row, r=r, q=q):
            grp = buf[r % 2, pl.ds(pl.multiple_of(c * rows, rows), rows), :]
            per_row = jnp.sum(grp.astype(jnp.float32) * q, axis=1,
                              keepdims=True)            # (T, 1)
            want = ids_smem[r, c] & (rows - 1)          # row within group
            sc = jnp.sum(jnp.where(sub == want, per_row, 0.0), axis=0,
                         keepdims=True)                 # (1, 1)
            return jnp.where(lanes == c, sc, s_row)

        s_row = jax.lax.fori_loop(0, block_m, score,
                                  jnp.zeros((1, block_m), jnp.float32))
        s = jnp.where(row_of == r, s_row, s)
    if key_scale is not None:
        s = s * key_scale                               # uniform int8 dequant
    ids = ids_vmem[...]                                 # (BB, BM) int32
    s = jnp.where(ids >= 0, s, NEG_INF)                 # un-DMA'd buffer rows

    cand_s = jnp.concatenate([ts_ref[...], s], axis=1)  # (BB, LANES + BM)
    cand_i = jnp.concatenate([ti_ref[...], ids], axis=1)
    ts_ref[...], ti_ref[...] = _iter_topk(cand_s, cand_i, k)


_STATIC = ("k", "block_b", "block_m", "interpret", "key_scale")


@functools.partial(jax.jit, static_argnames=_STATIC)
def ivf_topk_pallas(queries: Array, keys: Array, cand: Array, *, k: int = 4,
                    block_b: int = 8, block_m: int = 128,
                    interpret: bool = False, key_scale: float | None = None
                    ) -> tuple[Array, Array]:
    """Fused IVF candidate gather + score + top-k. See module docstring.

    queries (B, d) f32 normalized; keys (N, d) f32|bf16|int8 — the *whole*
    slab, left in HBM (ANY memory space) and gathered in-kernel; cand
    (B, M) int32 candidate slot ids with -1 marking invisible candidates
    (dead bucket slots, foreign tenants, expired, duplicates).
    Returns (scores (B, k) f32, slot ids (B, k) int32, -1 where empty).
    """
    b, d = queries.shape
    n = keys.shape[0]
    m = cand.shape[1]
    if not 0 < k <= LANES:
        raise ValueError(f"k={k} must be within [1, {LANES}]")
    rows = 4 // keys.dtype.itemsize * 8                 # HBM tile rows
    bb = min(block_b, max(1, b))
    bm = min(block_m, m)
    b_pad = -(-b // bb) * bb
    m_pad = -(-m // bm) * bm
    if keys.dtype == jnp.int8 and key_scale is None:
        key_scale = 1.0 / 127.0  # uniform slab dequant (§14.3)

    q = _pad_to(queries.astype(jnp.float32), b_pad, 0, 0.0)
    ids = _pad_to(_pad_to(cand.astype(jnp.int32), b_pad, 0, -1), m_pad, 1, -1)
    # whole row groups only; a slab of deployment size is already aligned
    keys = _pad_to(keys, -(-n // rows) * rows, 0, 0)

    kernel = functools.partial(_ivf_topk_kernel, k=k, block_b=bb, block_m=bm,
                               rows=rows, key_scale=key_scale)
    ts, ti = pl.pallas_call(
        kernel,
        grid=(b_pad // bb, m_pad // bm),
        in_specs=[
            pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, bm), lambda i, j: (i, j),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bb, bm), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=[
            pl.BlockSpec((bb, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, LANES), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, bm * rows, d), keys.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(q, ids, ids, keys)
    ts = jnp.where(ts <= NEG_INF, -jnp.inf, ts)
    return ts[:b, :k], ti[:b, :k]
