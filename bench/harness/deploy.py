"""Build a configuration's deployment of the program, filled and warm.

The engine is built with its public constructor, as
``python -m repro.launch.serve --scheduler async`` builds it: the
simulated LLM API behind it, the ground-truth judge, one fixed batch shape.
Set-up then fills the slab to capacity with filler entries, inserts the
QA corpus (so the corpus entries are the newest in the ring and a miss in
the window evicts only filler), and serves one cached question through the
serve path, which compiles the peek and the fused step and inserts
nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import filler, qa


def corpus_of(config: dict) -> list:
    c = config["corpus"]
    pairs = qa.build_corpus(int(c["per_category"]), seed=int(c["seed"]))
    if len(pairs) != int(config["corpus_pairs"]):
        raise ValueError(f"corpus has {len(pairs)} pairs, the configuration "
                         f"states {config['corpus_pairs']}")
    return pairs


def build(config: dict, corpus, seed: int, *, tracer=None):
    """A warm ``CachedEngine`` for ``config``, its slab full."""
    from repro.core.types import CacheConfig
    from repro.data.tokenizer import HashTokenizer
    from repro.launch.serve import judge_for
    from repro.serving import CachedEngine, Request, SimulatedLLMBackend

    if config["index"] != "exact":
        raise ValueError(f"no deployment for index {config['index']!r}")
    cfg = CacheConfig(dim=int(config["dim"]), capacity=int(config["capacity"]),
                      value_len=int(config["value_len"]),
                      ttl=float(config["ttl_s"]),
                      threshold=float(config["threshold"]),
                      topk=int(config["topk"]), eviction=config["eviction"],
                      key_dtype=jnp.dtype(config["key_dtype"]))
    b = config["backend"]
    backend = SimulatedLLMBackend(
        corpus, latency_per_call_s=float(b["latency_per_call_s"]),
        cost_per_call_usd=float(b["cost_per_call_usd"]),
        block=bool(b["block"]))
    engine = CachedEngine(
        cfg, backend, judge=judge_for(corpus),
        tokenizer=HashTokenizer(vocab_size=int(config["value_vocab"])),
        batch_size=int(config["batch_size"]), tracer=tracer)
    filler.fill(engine, config, seed)
    engine.warm(corpus)
    p = corpus[0]
    engine.serve_batch([Request(query=p.question, category=p.category,
                                source_id=p.qa_id,
                                semantic_key=p.semantic_key)])
    jax.block_until_ready(engine.runtime)
    return engine


def scheduler_config(config: dict):
    from repro.serving import SchedulerConfig

    s = config["scheduler"]
    return SchedulerConfig(max_batch=int(s["max_batch"]),
                           max_wait_ms=float(s["max_wait_ms"]),
                           coalesce=bool(s["coalesce"]))
