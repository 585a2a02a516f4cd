"""Mixture-of-Experts block: GShard-style capacity dispatch, TPU-native.

Design (DESIGN.md §6): token dispatch is a static-shape scatter into
``(E, C, d)`` expert buffers (capacity factor 1.25, overflow tokens
dropped with their residual passthrough kept — standard Switch behaviour);
expert FFNs run as one batched einsum over E. Expert weights are
*tensor-parallel* — d_ff shards over the ``model`` mesh axis — so the
baseline path needs no all-to-all: each device holds every expert's d_ff
slice, computes its partial down-projection, and a single ``psum`` over
``model`` closes the contraction. Under ``shard_map`` the dispatch runs on
each device's local tokens (batch sharded over ``data``/``pod``).

grok-1: E=8, top-2, every layer.  llama4-maverick: E=128, top-1, every
second layer (interleave handled in the model assembly).
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp

Array = jax.Array


def router(x: Array, w_router: Array, topk: int
           ) -> tuple[Array, Array, Array]:
    """Softmax gating. x (T, d) -> (gates (T,k), experts (T,k) int32, aux ()).

    Aux is the Switch/GShard load-balance loss: E * Σ_e f_e · p_e where
    f_e = fraction of tokens whose top-1 choice is e and p_e = mean router
    probability for e.
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, topk)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    e = probs.shape[-1]
    f = jnp.mean(jax.nn.one_hot(experts[:, 0], e, dtype=jnp.float32), axis=0)
    p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * p)
    return gates.astype(x.dtype), experts.astype(jnp.int32), aux


def dispatch_indices(experts: Array, n_experts: int, capacity: int
                     ) -> tuple[Array, Array]:
    """Assign each (token, choice) a slot in its expert's capacity buffer.

    Returns (slots (T,k) int32 with -1 = dropped, counts (E,)).
    Ranks are assigned choice-major (all tokens' 1st choice first), the
    GShard convention that biases drops toward lower-gate choices.
    """
    t, k = experts.shape
    counts = jnp.zeros((n_experts,), dtype=jnp.int32)
    slots = []
    for j in range(k):
        oh = jax.nn.one_hot(experts[:, j], n_experts, dtype=jnp.int32)  # (T,E)
        ranks = jnp.cumsum(oh, axis=0) - 1 + counts[None, :]
        slot = jnp.sum(ranks * oh, axis=-1)
        ok = slot < capacity
        slots.append(jnp.where(ok, slot, -1))
        counts = counts + jnp.sum(oh, axis=0)
    return jnp.stack(slots, axis=1).astype(jnp.int32), counts


def moe_ffn(
    x: Array,                # (T, d) local tokens
    w_router: Array,         # (d, E)
    w_gate: Array,           # (E, d, F_local)
    w_up: Array,             # (E, d, F_local)
    w_down: Array,           # (E, F_local, d)
    *,
    topk: int,
    capacity_factor: float = 1.25,
    model_axes: Sequence[str] | None = None,   # inside shard_map: psum axes
) -> tuple[Array, Array]:
    """Returns (y (T, d), aux_loss ()). See module docstring."""
    t, d = x.shape
    e = w_gate.shape[0]
    capacity = int(math.ceil(t * topk / e * capacity_factor))
    capacity = max(capacity, 1)

    gates, experts, aux = router(x, w_router, topk)
    slots, _ = dispatch_indices(experts, e, capacity)

    # scatter tokens into (E, C, d) buffers
    buf = jnp.zeros((e, capacity, d), dtype=x.dtype)
    for j in range(topk):
        ok = slots[:, j] >= 0
        idx_e = jnp.where(ok, experts[:, j], 0)
        idx_c = jnp.where(ok, slots[:, j], 0)
        contrib = jnp.where(ok[:, None], x, 0)
        buf = buf.at[idx_e, idx_c].add(contrib)

    # batched expert FFN (SwiGLU) — MXU einsums over the expert axis
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g) * u
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
    if model_axes:
        for ax in model_axes:   # close the sharded d_ff contraction
            y_buf = jax.lax.psum(y_buf, ax)

    # gather + combine with gate weights
    y = jnp.zeros((t, d), dtype=jnp.float32)
    for j in range(topk):
        ok = slots[:, j] >= 0
        idx_e = jnp.where(ok, experts[:, j], 0)
        idx_c = jnp.where(ok, slots[:, j], 0)
        yj = y_buf[idx_e, idx_c].astype(jnp.float32)
        y = y + jnp.where(ok[:, None], gates[:, j:j + 1].astype(jnp.float32) * yj, 0)
    return y.astype(x.dtype), aux


def moe_ffn_sharded(mesh, data_axes: tuple[str, ...], model_axes: tuple[str, ...]):
    """Build the shard_map-wrapped MoE ffn for a mesh.

    Token batch shards over ``data_axes``; expert d_ff shards over
    ``model_axes``. Router weights replicate.
    """
    from jax.sharding import PartitionSpec as P

    def fn(x, w_router, w_gate, w_up, w_down, topk, capacity_factor):
        y, aux = moe_ffn(x, w_router, w_gate, w_up, w_down, topk=topk,
                         capacity_factor=capacity_factor,
                         model_axes=model_axes)
        # aux is per-shard; average over the data axes for a global scalar
        for ax in data_axes:
            aux = jax.lax.pmean(aux, ax)
        for ax in model_axes:   # replicated across model: any works; mean is safe
            aux = jax.lax.pmean(aux, ax)
        return y, aux

    def wrapped(x, w_router, w_gate, w_up, w_down, *, topk, capacity_factor):
        f = lambda a, b, c, dd, ee: fn(a, b, c, dd, ee, topk, capacity_factor)
        return jax.shard_map(
            f, mesh=mesh, check_vma=False,
            in_specs=(P(data_axes, None), P(), P(None, None, model_axes),
                      P(None, None, model_axes), P(None, model_axes, None)),
            out_specs=(P(data_axes, None), P()),
        )(x, w_router, w_gate, w_up, w_down)

    return wrapped
