"""Pure-jnp oracles for every Pallas kernel in this package.

These define the numerical contract; kernel tests sweep shapes/dtypes and
``assert_allclose`` against these functions (interpret mode on CPU). The
scoring contractions run at ``Precision.HIGHEST``: on a TPU the default
rounds f32 operands, and the contract is f32 cosine scores.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# python float, not jnp.float32(...): this module may be first imported
# *inside* a jit trace (index.py defers its kernels import), and a
# module-level device constant created under a trace leaks as a tracer
NEG_INF = float("-inf")


def cosine_topk_ref(queries: Array, keys: Array, valid: Array, k: int
                    ) -> tuple[Array, Array]:
    """Exact masked cosine top-k.

    Args:
      queries: (B, d) float32, assumed L2-normalized.
      keys: (N, d) float or quantized-dequantized values, normalized. int8
        keys are the uniform slab quantization (round(normalized * 127))
        and dequant by 1/127 before scoring — raw int8 GEMMs would inflate
        every score x127.
      valid: (N,) bool aliveness mask shared by the batch, or (B, N) bool
        per-row visibility.
      k: neighbours to return.
    Returns:
      (scores (B, k) f32 desc-sorted, indices (B, k) int32; -1 where masked).
      All-masked rows return exactly (-inf, -1) — the contract every kernel
      variant and index path must match.
    """
    if keys.dtype == jnp.int8:
        keys = keys.astype(jnp.float32) / 127.0
    scores = jnp.einsum("bd,nd->bn", queries, keys.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    mask = valid if valid.ndim == 2 else valid[None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    vals, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(vals > NEG_INF, idx, -1)
    return vals, idx.astype(jnp.int32)


def quant_cosine_topk_ref(queries: Array, keys_q: Array, scales: Array,
                          valid: Array, k: int) -> tuple[Array, Array]:
    """int8-quantized scoring oracle: dequantize then exact top-k.

    keys_q: (N, d) int8; scales: (N,) f32 per-row dequant scale.
    valid: (N,) shared or (B, N) per-row.
    """
    keys = keys_q.astype(jnp.float32) * scales[:, None]
    return cosine_topk_ref(queries, keys, valid, k)


def interval_mask(starts: Array, sizes: Array, n: int) -> Array:
    """(B,) interval operands -> (B, N) bool visibility mask: row ``b`` sees
    slots ``[starts[b], starts[b] + sizes[b])``. The jnp oracle for the
    iota-built mask the interval kernel never materializes."""
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    return (cols >= starts[:, None]) & (cols < (starts + sizes)[:, None])


def cosine_topk_interval_ref(queries: Array, keys: Array, valid: Array,
                             starts: Array, sizes: Array, k: int
                             ) -> tuple[Array, Array]:
    """Oracle for the per-row interval-masked kernel (tenancy fast path):
    dense (B, N) mask = shared aliveness ∧ per-row interval, then exact
    top-k. ``sizes[b] == 0`` rows return (-inf, -1)."""
    mask = valid[None, :] & interval_mask(starts, sizes, keys.shape[0])
    return cosine_topk_ref(queries, keys, mask, k)


def quant_cosine_topk_interval_ref(queries: Array, keys_q: Array,
                                   scales: Array, valid: Array, starts: Array,
                                   sizes: Array, k: int
                                   ) -> tuple[Array, Array]:
    """Interval oracle over a per-row-scale int8 slab."""
    keys = keys_q.astype(jnp.float32) * scales[:, None]
    return cosine_topk_interval_ref(queries, keys, valid, starts, sizes, k)


def ivf_topk_ref(queries: Array, keys: Array, cand: Array, k: int
                 ) -> tuple[Array, Array]:
    """Oracle for the fused IVF candidate kernel (DESIGN.md §15): gather the
    candidate rows, score, top-k — the ``(B, M, d)`` HBM materialization the
    kernel exists to avoid, acceptable here because the oracle defines
    numerics, not traffic.

    Args:
      queries: (B, d) float32, assumed L2-normalized.
      keys: (N, d) slab; int8 is the uniform slab quantization and dequants
        by 1/127 exactly like ``cosine_topk_ref``.
      cand: (B, M) int32 candidate slot ids; -1 marks an invisible candidate
        (dead bucket slot, foreign tenant, expired, per-row duplicate —
        the caller folds all visibility into the ids, see
        ``IVFIndex.candidates``).
      k: neighbours to return.
    Returns:
      (scores (B, k) f32 desc-sorted, slot ids (B, k) int32). Rows whose
      candidates are all -1 return exactly (-inf, -1) — the §14.4 contract.
    """
    if keys.dtype == jnp.int8:
        keys = keys.astype(jnp.float32) / 127.0
    safe = jnp.maximum(cand, 0)
    gathered = keys[safe].astype(jnp.float32)            # (B, M, d) — in HBM
    scores = jnp.einsum("bd,bmd->bm", queries, gathered,
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)
    scores = jnp.where(cand >= 0, scores, NEG_INF)
    vals, pos = jax.lax.top_k(scores, k)
    ids = jnp.take_along_axis(cand, pos, axis=1)
    ids = jnp.where(vals > NEG_INF, ids, -1)
    return vals, ids.astype(jnp.int32)


def flash_attention_ref(q: Array, kk: Array, v: Array, *, causal: bool = True,
                        window: int | None = None, scale: float | None = None
                        ) -> Array:
    """Blockwise-attention oracle: plain softmax attention.

    Shapes: q (B, Lq, H, D), kk/v (B, Lk, H, D) — same head count (callers
    expand GQA groups before the kernel). Supports causal & sliding-window
    masks with the convention that query position i attends to key positions
    ``max(0, i - window + 1) .. i`` (absolute offset = Lk - Lq aligns ends).
    """
    b, lq, h, d = q.shape
    lk = kk.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(lq)[:, None] + (lk - lq)
    kpos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v).astype(q.dtype)
