"""Cosine similarity and masked top-k — the scoring primitive (paper §2.6).

``cosine_similarity(u, v) = u·v / (|u||v|)``. Stored keys are L2-normalized
at insert time, so scoring a normalized query against the slab is a single
``(B, d) @ (d, N)`` matmul — this is the MXU-friendly reformulation of the
paper's per-pair cosine (see DESIGN.md §3). The Pallas kernel in
``repro.kernels.cosine_topk`` implements the same contract with explicit
VMEM blocking; this module is the pure-jnp reference used on CPU and as the
kernel oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

NEG_INF = jnp.float32(-jnp.inf)


def l2_normalize(x: Array, axis: int = -1, eps: float = 1e-12) -> Array:
    """L2-normalize along ``axis`` (zero vectors map to zero)."""
    norm = jnp.linalg.norm(x, axis=axis, keepdims=True)
    return x / jnp.maximum(norm, eps)


def cosine_similarity(u: Array, v: Array, eps: float = 1e-12) -> Array:
    """Elementwise cosine similarity along the last axis (paper eq. in §2.6)."""
    un = jnp.linalg.norm(u, axis=-1)
    vn = jnp.linalg.norm(v, axis=-1)
    dot = jnp.sum(u * v, axis=-1)
    return dot / jnp.maximum(un * vn, eps)


def cosine_scores(queries: Array, keys: Array, valid: Array | None = None,
                  *, assume_normalized: bool = True) -> Array:
    """Batched scores: (B, d) x (N, d) -> (B, N); invalid slots get -inf.

    Args:
      queries: (B, d) query embeddings.
      keys: (N, d) slab keys.
      valid: (N,) bool slot-aliveness mask (validity ∧ not-expired), or
        (B, N) bool for per-row visibility — the multi-tenant path masks
        each query to its own slab region (DESIGN.md §13.2).
      assume_normalized: skip re-normalization (keys are normalized at insert).
    """
    if keys.dtype == jnp.int8:
        keys = keys.astype(jnp.float32) / 127.0
    if not assume_normalized:
        queries = l2_normalize(queries)
        keys = l2_normalize(keys)
    # f32 scores on every backend: a TPU's default precision rounds the
    # operands, which moves scores by ~1e-3 at the hit threshold
    scores = jnp.einsum(
        "bd,nd->bn", queries, keys, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32
    )
    if valid is not None:
        mask = valid if valid.ndim == 2 else valid[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    return scores


def interval_visibility(alive: Array, starts: Array, sizes: Array) -> Array:
    """Expand per-row interval operands into a dense (B, N) visibility mask:
    row ``b`` sees the alive slots in ``[starts[b], starts[b] + sizes[b])``.
    ``alive`` is (N,) shared or already-per-row (B, N).

    This is the jnp-path materialization of what the interval-masked Pallas
    kernel builds from iota in VMEM (DESIGN.md §14) — on CPU the (B, N)
    bool is cheap; on TPU the kernel avoids it entirely.
    """
    cols = jnp.arange(alive.shape[-1], dtype=jnp.int32)[None, :]
    inside = (cols >= starts[:, None]) & (cols < (starts + sizes)[:, None])
    return (alive if alive.ndim == 2 else alive[None, :]) & inside


def masked_topk(scores: Array, k: int) -> tuple[Array, Array]:
    """Top-k over the last axis. Returns (values (..., k), indices (..., k))."""
    k = min(k, scores.shape[-1])
    return jax.lax.top_k(scores, k)


def best_match(scores: Array) -> tuple[Array, Array]:
    """Argmax + max over the last axis: (B, N) -> ((B,), (B,))."""
    idx = jnp.argmax(scores, axis=-1).astype(jnp.int32)
    val = jnp.max(scores, axis=-1)
    return idx, val
