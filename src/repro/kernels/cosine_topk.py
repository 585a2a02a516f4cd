"""Fused cosine-similarity + top-k Pallas TPU kernel — the scoring hot-spot.

This is the TPU-native replacement for the paper's HNSW search (DESIGN.md §3):
one pass over the cache slab, blocked through VMEM, with the similarity GEMM
on the MXU and a running top-k merge held in VMEM across grid steps.

Tiling:
  grid = (B/BB, N/BN); the N axis is the minor (sequential) axis, so the
  output blocks stay resident in VMEM and carry the running top-k while
  key blocks (BN, d) stream HBM -> VMEM.

  BB=128, BN=512 compile for a v5e within the default 16 MiB of scoped
  VMEM at d = 384, 768 and 1536 (``tests/test_tpu_compile.py`` compiles
  every variant at d=384). Per step: the double-buffered key block
  (2 x 512 x d x 4B: 1.5 MiB at d=384, 6 MiB at d=1536), the query block
  (2 x 128 x d x 4B) and the (128, 128 + 512) f32/int32 candidate rows of
  the selection. BN=1024 runs out of VMEM at d=1536. The contraction dim
  d and BN are multiples of 128, keeping the MXU fully tiled.

Top-k strategy: ``k`` is tiny (<=8). The running top-k lives in lanes
[0, k) of the resident (BB, 128) output rows. Each step concatenates them
ahead of the (BB, BN) score block and runs one k-step max-and-suppress
over the lot — pure VPU/XLU work, no sort network. Mosaic cannot gather
along lanes, so the winner's column and id come from masked reductions
and land in their output lane by a lane-mask select.

Masking (all fused, all optional — DESIGN.md §14):
  * ``valid`` — shared (N,) aliveness (validity ∧ TTL), shipped as a
    (1, N) f32 row riding with each key block: the kernel-level analogue
    of Redis lazy expiry.
  * per-row *intervals* — (B,) ``starts``/``sizes`` operands, one visible
    contiguous slot range per query row. The (BB, BN) visibility mask is
    built *inside* the kernel from block iota against the (BB, 1) interval
    operands, so per-row masking costs O(B) operand traffic instead of a
    (B, N) bool mask in HBM. This is the multi-tenant path: PartitionMap
    regions are contiguous by construction (§13.2).
  * dense per-row mask — a blocked (BB, BN) int8 mask operand for masks
    that are *not* contiguous ranges (e.g. future embedding-LSH bucket
    coalescing), widened to int32 in VMEM for the compare. Costs B*N bytes
    of HBM traffic; prefer intervals.

int8 slabs: keys stored as ``round(normalized * 127)`` (store.insert) score
through the same kernel with a uniform static ``key_scale = 1/127``
applied to the block's scores — entrypoints apply it automatically for
int8 keys so raw-int8 scores (inflated x127) cannot happen. Per-row-scale
quantization (``quantize_keys``) uses the (1, N) ``scales`` operand
instead.

All-masked rows (empty tenant region, padded row) return exactly
``(-inf, -1)`` — the same contract as ``ref.cosine_topk_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array
NEG_INF = -3.0e38  # finite -inf stand-in (python float: not a traced const)
LANES = 128        # lane width of the resident running top-k rows (k <= 128)


def _iter_topk(scores: Array, ids: Array, k: int) -> tuple[Array, Array]:
    """k-step max-and-suppress. scores (B, M) f32, ids (B, M) i32.

    Returns (B, LANES) score/id rows holding the top-k in lanes [0, k)
    (NEG_INF / -1 beyond). Ties go to the lowest column, as in
    ``lax.top_k``. Mosaic has no lane gather, so the winner's column and
    id are found by masked reductions and written by a lane-mask select.
    """
    b, m = scores.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, m), 1)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (b, LANES), 1)
    out_s = jnp.full((b, LANES), NEG_INF, jnp.float32)
    out_i = jnp.full((b, LANES), -1, jnp.int32)
    for t in range(k):
        best = jnp.max(scores, axis=1, keepdims=True)                # (B, 1)
        arg = jnp.min(jnp.where(scores == best, cols, m), axis=1,
                      keepdims=True)
        win = cols == arg
        sel = jnp.max(jnp.where(win, ids, -1), axis=1, keepdims=True)
        out_s = jnp.where(lanes == t, best, out_s)
        out_i = jnp.where(lanes == t, jnp.where(best > NEG_INF, sel, -1),
                          out_i)
        scores = jnp.where(win, NEG_INF, scores)
    return out_s, out_i


def _cosine_topk_kernel(q_ref, k_ref, ts_ref, ti_ref, *,
                        k: int, block_n: int, key_scale: float | None,
                        scale_ref=None, valid_ref=None,
                        start_ref=None, size_ref=None, mask_ref=None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        ts_ref[...] = jnp.full_like(ts_ref, NEG_INF)
        ti_ref[...] = jnp.full_like(ti_ref, -1)

    # MXU GEMM, contraction over d, at fp32 contract precision: Mosaic's
    # default rounds f32 operands, which moves scores by ~1e-3 — enough to
    # flip decisions at the threshold against the f32 oracle. Dequant
    # scales multiply the (BB, BN) scores rather than the (BN, d) key
    # block: the same product with d/BB fewer multiplies.
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)           # (BB, BN)
    if key_scale is not None:
        s = s * key_scale                             # uniform int8 dequant
    if scale_ref is not None:
        s = s * scale_ref[...]                        # (1, BN) per-row scale

    bb, bn = s.shape
    gids = j * block_n + jax.lax.broadcasted_iota(jnp.int32, (bb, bn), 1)

    if valid_ref is not None:
        # (1, BN) f32 0/1 aliveness row, shared by the batch
        s = jnp.where(valid_ref[...] > 0.5, s, NEG_INF)
    if start_ref is not None:
        # per-row interval visibility, built from iota in VMEM: row b sees
        # slots [start[b], start[b] + size[b]) — O(B) operands, no (B, N)
        # mask ever touches HBM
        start = start_ref[...]          # (BB, 1) int32
        size = size_ref[...]            # (BB, 1) int32
        s = jnp.where((gids >= start) & (gids < start + size), s, NEG_INF)
    if mask_ref is not None:
        # dense per-row mask block (BB, BN) int8 — the general
        # (non-contiguous) visibility path; the TPU compares 32-bit lanes
        s = jnp.where(mask_ref[...].astype(jnp.int32) > 0, s, NEG_INF)

    # the running top-k sits in lanes [0, k) of the resident output rows,
    # ahead of the block, so score ties keep the lower (earlier) slot
    cand_s = jnp.concatenate([ts_ref[...], s], axis=1)   # (BB, LANES + BN)
    cand_i = jnp.concatenate([ti_ref[...], gids], axis=1)
    ts_ref[...], ti_ref[...] = _iter_topk(cand_s, cand_i, k)


def _pad_to(x: Array, n: int, axis: int, fill) -> Array:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _launch(queries: Array, keys: Array, *, valid=None, scales=None,
            key_scale=None, starts=None, sizes=None, row_mask=None,
            k: int, block_b: int, block_n: int, interpret: bool
            ) -> tuple[Array, Array]:
    """Shared pallas_call assembly for every kernel variant: pads operands
    to tile multiples, wires the optional mask/scale operands, slices the
    batch padding back off. Padded key columns are masked invalid (shared
    column / dense mask) or fall outside every interval (intervals never
    extend past N); padded batch rows get size-0 intervals / zero masks and
    are discarded by the final slice."""
    b, d = queries.shape
    n = keys.shape[0]
    if not 0 < k <= LANES:
        raise ValueError(f"k={k} must be within [1, {LANES}]")
    bb = min(block_b, max(8, b))
    bn = min(block_n, n)
    b_pad = -(-b // bb) * bb
    n_pad = -(-n // bn) * bn
    if keys.dtype == jnp.int8 and scales is None and key_scale is None:
        # int8 slab = round(normalized * 127): uniform dequant, folded into
        # the in-VMEM cast. Raw-int8 scoring would inflate scores x127 and
        # make every threshold comparison spuriously hit.
        key_scale = 1.0 / 127.0

    operands = [_pad_to(queries.astype(jnp.float32), b_pad, 0, 0.0),
                _pad_to(keys, n_pad, 0, 0)]
    in_specs = [pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, d), lambda i, j: (j, 0))]
    ref_names = []

    def add(name, op, spec):
        operands.append(op)
        in_specs.append(spec)
        ref_names.append(name)

    if scales is not None:
        add("scale_ref",
            _pad_to(scales.astype(jnp.float32)[None, :], n_pad, 1, 0.0),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    if valid is not None:
        add("valid_ref",
            _pad_to(valid.astype(jnp.float32)[None, :], n_pad, 1, 0.0),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)))
    if starts is not None:
        add("start_ref",
            _pad_to(starts.astype(jnp.int32)[:, None], b_pad, 0, 0),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)))
        add("size_ref",
            _pad_to(sizes.astype(jnp.int32)[:, None], b_pad, 0, 0),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)))
    if row_mask is not None:
        # int8, not f32: the mask is pure HBM traffic on a memory-bound op,
        # so ship 1 byte/element (B*N bytes total)
        rm = _pad_to(_pad_to(row_mask.astype(jnp.int8), b_pad, 0, 0),
                     n_pad, 1, 0)
        add("mask_ref", rm, pl.BlockSpec((bb, bn), lambda i, j: (i, j)))

    def kernel(q_ref, k_ref, *rest):
        refs = dict(zip(ref_names, rest[:len(ref_names)]))
        ts_ref, ti_ref = rest[len(ref_names):]
        _cosine_topk_kernel(q_ref, k_ref, ts_ref, ti_ref, k=k, block_n=bn,
                            key_scale=key_scale, **refs)

    ts, ti = pl.pallas_call(
        kernel,
        grid=(b_pad // bb, n_pad // bn),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bb, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, LANES), jnp.float32),
            jax.ShapeDtypeStruct((b_pad, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    ts = jnp.where(ts <= NEG_INF, -jnp.inf, ts)
    return ts[:b, :k], ti[:b, :k]


_STATIC = ("k", "block_b", "block_n", "interpret", "key_scale")


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_topk_pallas(queries: Array, keys: Array, valid: Array, *,
                       k: int = 4, block_b: int = 128, block_n: int = 512,
                       interpret: bool = False, key_scale: float | None = None
                       ) -> tuple[Array, Array]:
    """Fused masked cosine top-k. See module docstring for the contract.

    queries (B, d) f32 normalized; keys (N, d) f32|bf16|int8; valid (N,)
    bool shared across the batch. int8 keys dequant in-kernel (uniform
    ``key_scale``, default 1/127 — the slab's symmetric scale).
    Returns (scores (B, k), indices (B, k) int32, -1 where masked/empty).
    """
    return _launch(queries, keys, valid=valid, key_scale=key_scale,
                   k=k, block_b=block_b, block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def quant_cosine_topk_pallas(queries: Array, keys_q: Array, scales: Array,
                             valid: Array, *, k: int = 4, block_b: int = 128,
                             block_n: int = 512, interpret: bool = False,
                             key_scale: float | None = None
                             ) -> tuple[Array, Array]:
    """int8-slab variant: keys int8 + per-row f32 scale, dequant in VMEM.

    Cuts slab HBM traffic 4x vs f32 keys; keys widen to f32 after the DMA,
    on the block in VMEM, and the per-row scales multiply the scores.
    """
    del key_scale  # per-row scales take precedence by construction
    return _launch(queries, keys_q, scales=scales, valid=valid,
                   k=k, block_b=block_b, block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_topk_interval_pallas(queries: Array, keys: Array, valid: Array,
                                starts: Array, sizes: Array, *, k: int = 4,
                                block_b: int = 128, block_n: int = 512,
                                interpret: bool = False,
                                key_scale: float | None = None
                                ) -> tuple[Array, Array]:
    """Per-row interval-masked variant — the tenancy fast path (§13.2).

    Row ``b`` sees slots ``[starts[b], starts[b] + sizes[b])`` ∩ ``valid``.
    The interval operands are O(B); the (B, N) visibility mask is built from
    block iota in VMEM and never materializes in HBM. ``sizes[b] == 0``
    (empty region / padded row) returns exactly ``(-inf, -1)`` for that row.
    """
    return _launch(queries, keys, valid=valid, starts=starts, sizes=sizes,
                   key_scale=key_scale, k=k, block_b=block_b, block_n=block_n,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def quant_cosine_topk_interval_pallas(queries: Array, keys_q: Array,
                                      scales: Array, valid: Array,
                                      starts: Array, sizes: Array, *,
                                      k: int = 4, block_b: int = 128,
                                      block_n: int = 512,
                                      interpret: bool = False,
                                      key_scale: float | None = None
                                      ) -> tuple[Array, Array]:
    """Interval-masked int8 variant with per-row dequant scales."""
    del key_scale
    return _launch(queries, keys_q, scales=scales, valid=valid, starts=starts,
                   sizes=sizes, k=k, block_b=block_b, block_n=block_n,
                   interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def cosine_topk_masked_pallas(queries: Array, keys: Array, mask: Array, *,
                              k: int = 4, block_b: int = 128,
                              block_n: int = 512, interpret: bool = False,
                              key_scale: float | None = None
                              ) -> tuple[Array, Array]:
    """General per-row-masked variant: ``mask`` is (B, N) bool — full
    visibility (aliveness ∧ per-row) folded in by the caller. Streams the
    mask in (BB, BN) blocks; for contiguous regions prefer the interval
    variant (O(B) operands vs O(B·N) mask traffic)."""
    return _launch(queries, keys, row_mask=mask, key_scale=key_scale,
                   k=k, block_b=block_b, block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=_STATIC)
def quant_cosine_topk_masked_pallas(queries: Array, keys_q: Array,
                                    scales: Array, mask: Array, *,
                                    k: int = 4, block_b: int = 128,
                                    block_n: int = 512,
                                    interpret: bool = False,
                                    key_scale: float | None = None
                                    ) -> tuple[Array, Array]:
    """Dense-masked int8 variant with per-row dequant scales."""
    del key_scale
    return _launch(queries, keys_q, scales=scales, row_mask=mask,
                   k=k, block_b=block_b, block_n=block_n, interpret=interpret)


def quantize_keys(keys: Array) -> tuple[Array, Array]:
    """Symmetric per-row int8 quantization: keys ≈ q * scale."""
    absmax = jnp.max(jnp.abs(keys), axis=1)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(keys / scale[:, None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)
