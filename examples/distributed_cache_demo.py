"""Distributed semantic cache demo (paper §2.10 future work, implemented).

    PYTHONPATH=src python examples/distributed_cache_demo.py

Runs the sharded cache on 8 forced host devices: the slab shards over the
``data`` mesh axis, lookups fan out with a pmax combine, inserts route
round-robin — a query cached on one shard is served to a query landing
anywhere on the mesh. State is one ``CacheRuntime`` pytree: slab sharded,
stats/policy replicated, threaded through the fused step.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import CacheConfig, DistributedCache, SemanticCache  # noqa: E402
from repro.embedding import HashEmbedder  # noqa: E402
from repro.data.tokenizer import HashTokenizer  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

mesh = make_mesh((4, 2), ("data", "model"))
print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

cache = SemanticCache(CacheConfig(dim=384, capacity=1024, value_len=24,
                                  ttl=3600.0, threshold=0.8))
dc = DistributedCache(cache, mesh, cache_axes=("data",))
runtime = dc.init()
step = dc.make_lookup_insert()
embedder = HashEmbedder()
tok = HashTokenizer()

faqs = [
    ("what are the interest rates for savings accounts",
     "Savings accounts earn 4.1% APY, paid monthly."),
    ("how do i reset my online banking password",
     "Use Settings -> Security -> Reset password."),
    ("where is the nearest branch",
     "Use the branch locator on the website homepage."),
    ("how do i order a new debit card",
     "Request a replacement card under Cards -> Replace."),
]
q_emb = jnp.asarray(embedder.embed_batch([q for q, _ in faqs]))
vals, lens = tok.encode_batch([a for _, a in faqs], 24)

# pass 1: cold — every query misses and the responses are inserted (sharded)
runtime, (slot, score, hit, v, vl, src) = step(
    runtime, q_emb, jnp.asarray(vals), jnp.asarray(lens),
    jnp.arange(len(faqs)), jnp.float32(0.0))
print(f"cold pass: hits={int(np.asarray(hit).sum())}/4")
per_shard = np.asarray(runtime.state.valid).reshape(4, -1).sum(axis=1)
print(f"entries per cache shard (round-robin): {per_shard.tolist()}")

# pass 2: paraphrased traffic — served from whichever shard owns the entry
paraphrases = [
    "what are the interest rates for savings accounts please",
    "hi how do i reset my online banking password",
    "where is the nearest branch located",
    "how do i order a new debit card today",
]
p_emb = jnp.asarray(embedder.embed_batch(paraphrases))
runtime, (slot, score, hit, v, vl, src) = step(
    runtime, p_emb, jnp.asarray(vals), jnp.asarray(lens),
    jnp.arange(len(faqs)), jnp.float32(1.0))
for i, p in enumerate(paraphrases):
    print(f"[hit={bool(np.asarray(hit)[i])} score={float(np.asarray(score)[i]):.2f} "
          f"shard={int(np.asarray(slot)[i]) // dc.local_capacity}] {p}")
print(f"global stats: lookups={int(runtime.stats.lookups)} "
      f"hits={int(runtime.stats.hits)} inserts={int(runtime.stats.inserts)}")
