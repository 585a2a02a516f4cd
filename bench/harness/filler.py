"""Filler keys: the entries that fill a slab to capacity before a run.

A cache in steady state is full, its FIFO ring wrapped. The benchmark
reaches that state in set-up by inserting random unit keys, made on the
device from the run's seed, through the cache's own insert. Chunk ``i``
of the filler is a pure function of (seed, i), so the reference can make
the same keys again once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: Provenance id of a filler entry: no corpus pair has it.
FILLER_SOURCE_ID = -2


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits (``jax.random.key`` keeps
    only the low 32 without x64)."""
    return jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)


def chunk_keys(key: jax.Array, i, chunk: int, dim: int) -> jax.Array:
    """Chunk ``i`` of the filler: (chunk, dim) float32 unit rows."""
    k = jax.random.normal(jax.random.fold_in(key, i), (chunk, dim),
                          jnp.float32)
    return k / jnp.linalg.norm(k, axis=1, keepdims=True)


def fill_program(cache, config: dict):
    """The jitted fill: ``(runtime, key) -> runtime`` with ``capacity``
    filler entries inserted through ``cache.insert``, looping over chunks
    of one shape. The key is an argument, not a constant of the program,
    so that every seed finds the same program in the compile cache."""
    capacity, dim = int(config["capacity"]), int(config["dim"])
    chunk = int(config["fill_chunk"])
    if capacity % chunk:
        raise ValueError(f"fill_chunk {chunk} does not divide {capacity}")
    value_len = int(config["value_len"])

    def fill_all(rt, key):
        def body(i, rt):
            return cache.insert(
                rt, chunk_keys(key, i, chunk, dim),
                jnp.zeros((chunk, value_len), jnp.int32),
                jnp.zeros((chunk,), jnp.int32), 0.0,
                source_id=jnp.full((chunk,), FILLER_SOURCE_ID, jnp.int32))

        return jax.lax.fori_loop(0, capacity // chunk, body, rt)

    return jax.jit(fill_all, donate_argnums=0)


def fill(engine, config: dict, seed: int) -> None:
    """Fill ``engine``'s slab to capacity with the seed's filler."""
    engine.runtime = fill_program(engine.cache, config)(engine.runtime,
                                                        seed_key(seed))


@functools.partial(jax.jit, static_argnames=("chunk", "dim", "k"))
def _chunk_top(qblocks, key, i, *, chunk, dim, k):
    keys = chunk_keys(key, i, chunk, dim)

    def one(qb):
        s = jnp.einsum("bd,nd->bn", qb, keys,
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        v, j = jax.lax.top_k(s, k)
        return v, j + i * chunk

    return jax.lax.map(one, qblocks)


def filler_top(queries: np.ndarray, config: dict, seed: int, *, k: int = 8,
               block: int = 512) -> tuple[np.ndarray, np.ndarray]:
    """Each query's ``k`` best filler entries over the whole filler, as
    (scores (M, k), ranks (M, k)); a rank is the entry's insert order."""
    capacity, dim = int(config["capacity"]), int(config["dim"])
    chunk = int(config["fill_chunk"])
    m = queries.shape[0]
    nb = -(-m // block)
    q = np.zeros((nb * block, dim), np.float32)
    q[:m] = queries
    qb = jnp.asarray(q.reshape(nb, block, dim))
    key = seed_key(seed)
    vals, ranks = [], []
    for i in range(capacity // chunk):
        v, r = _chunk_top(qb, key, i, chunk=chunk, dim=dim, k=k)
        vals.append(np.asarray(v).reshape(-1, k)[:m])
        ranks.append(np.asarray(r).reshape(-1, k)[:m])
    vals = np.concatenate(vals, axis=1)
    ranks = np.concatenate(ranks, axis=1)
    order = np.argsort(-vals, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(vals, order, axis=1).astype(np.float64),
            np.take_along_axis(ranks, order, axis=1))
