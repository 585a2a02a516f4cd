"""Jit'd dispatch wrappers for the Pallas kernels.

``cosine_topk`` / ``cosine_topk_interval`` pick the execution path:
  * TPU backend  -> compiled Pallas kernel,
  * anything else -> interpret-mode only when explicitly requested
    (``REPRO_PALLAS_INTERPRET=1``; it is Python-slow and meant for tests and
    the CPU CI job that exercises the kernel code paths),
    otherwise the jnp oracle, which XLA fuses perfectly well on CPU.
The numerical contract is ``repro.kernels.ref``.

Per-row visibility (DESIGN.md §14) dispatches by mask shape:
  * ``valid`` (N,)   -> shared-mask kernel (single-tenant fast path);
  * interval operands -> iota-masked kernel, O(B) operand traffic — the
    tenancy path (contiguous PartitionMap regions);
  * ``valid`` (B, N) -> dense blocked-mask kernel — the general path for
    non-contiguous visibility.

``ivf_topk`` dispatches the fused IVF candidate kernel (in-kernel HBM ->
VMEM gather of probed slab rows, DESIGN.md §15) the same way, with an
explicit ``backend=`` override for parity tests.

int8 slabs dequant *inside* the kernels (uniform 1/127 — the slab's
symmetric scale from ``store.insert``) and inside the oracles, so no
dispatch path ever scores raw int8 keys.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import ref
from repro.kernels.cosine_topk import (cosine_topk_interval_pallas,
                                       cosine_topk_masked_pallas,
                                       cosine_topk_pallas,
                                       quant_cosine_topk_masked_pallas,
                                       quant_cosine_topk_pallas,
                                       quantize_keys)
from repro.kernels.ivf_topk import ivf_topk_pallas

Array = jax.Array


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _interpret_requested() -> bool:
    return os.environ.get("REPRO_PALLAS_INTERPRET", "0") == "1"


def _pick_pallas(backend: str) -> bool:
    """``backend`` is ``'auto' | 'jnp' | 'pallas'``; explicit values pin a
    path for parity tests and on-chip references."""
    if backend not in ("auto", "jnp", "pallas"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend == "pallas" or (
        backend == "auto" and (_use_pallas() or _interpret_requested()))


def cosine_topk(queries: Array, keys: Array, valid: Array, *, k: int = 4,
                backend: str = "auto") -> tuple[Array, Array]:
    """Masked cosine top-k with automatic backend dispatch.

    ``valid`` is (N,) shared across the batch or (B, N) per-row; the (B, N)
    shape routes to the dense blocked-mask kernel on TPU (contiguous
    per-row regions should use ``cosine_topk_interval`` instead)."""
    if _pick_pallas(backend):
        interpret = not _use_pallas()
        if valid.ndim == 2:
            return cosine_topk_masked_pallas(queries, keys, valid, k=k,
                                             interpret=interpret)
        return cosine_topk_pallas(queries, keys, valid, k=k,
                                  interpret=interpret)
    return ref.cosine_topk_ref(queries, keys, valid, k)


def cosine_topk_interval(queries: Array, keys: Array, valid: Array,
                         starts: Array, sizes: Array, *, k: int = 4
                         ) -> tuple[Array, Array]:
    """Per-row interval-masked cosine top-k — the tenancy fast path.

    Row ``b`` sees ``valid`` ∩ ``[starts[b], starts[b] + sizes[b])``. The
    kernel builds the per-row mask from iota in VMEM, so the operand cost
    is O(B) regardless of slab size."""
    if _use_pallas() or _interpret_requested():
        return cosine_topk_interval_pallas(queries, keys, valid, starts,
                                           sizes, k=k,
                                           interpret=not _use_pallas())
    return ref.cosine_topk_interval_ref(queries, keys, valid, starts, sizes,
                                        k)


def quant_cosine_topk(queries: Array, keys_q: Array, scales: Array,
                      valid: Array, *, k: int = 4) -> tuple[Array, Array]:
    """int8-slab masked cosine top-k (per-row dequant scales).

    ``valid`` is (N,) shared or (B, N) per-row — same shape dispatch as
    ``cosine_topk``."""
    if _use_pallas() or _interpret_requested():
        interpret = not _use_pallas()
        if valid.ndim == 2:
            return quant_cosine_topk_masked_pallas(queries, keys_q, scales,
                                                   valid, k=k,
                                                   interpret=interpret)
        return quant_cosine_topk_pallas(queries, keys_q, scales, valid, k=k,
                                        interpret=interpret)
    return ref.quant_cosine_topk_ref(queries, keys_q, scales, valid, k)


def ivf_topk(queries: Array, keys: Array, cand: Array, *, k: int = 4,
             backend: str = "auto") -> tuple[Array, Array]:
    """Fused IVF candidate search with automatic backend dispatch (§15).

    ``cand`` is (B, M) int32 candidate slot ids with -1 marking invisible
    candidates (the caller — ``IVFIndex.candidates`` — folds bucket
    validity, aliveness, tenancy intervals and per-row dedup into the ids).
    On TPU (or under ``REPRO_PALLAS_INTERPRET=1``) the fused kernel gathers
    the candidate slab rows HBM -> VMEM in-kernel, so the (B, M, d) gathered
    tensor of the jnp oracle never materializes in HBM. ``backend`` is
    ``'auto' | 'jnp' | 'pallas'`` — explicit values pin a path for parity
    tests and benchmarks.
    """
    if _pick_pallas(backend):
        return ivf_topk_pallas(queries, keys, cand, k=k,
                               interpret=not _use_pallas())
    return ref.ivf_topk_ref(queries, keys, cand, k)


__all__ = ["cosine_topk", "cosine_topk_interval", "quant_cosine_topk",
           "ivf_topk", "quantize_keys"]
