"""The paper's deployment: the semantic cache in front of the LLM API.

Set-up builds the engine with its public constructor, as
``python -m repro.launch.serve --scheduler async`` builds it: the
simulated LLM API behind it, the ground-truth judge, one fixed batch shape.
It then fills the slab to capacity with filler entries, inserts the QA
corpus (so the corpus entries are the newest in the ring and a miss in
the window evicts only filler), and serves one cached question through the
serve path, which compiles the peek and the fused step and inserts
nothing.

The check replays the program's batches through the exact-cosine
reference (``bench/references/<reference>.py``): best score, decision,
answer text, the misses visible to later batches, the waiters' answers.
The filler's keys live only on the device, so each query's best filler
entries are scored there once the program's state is freed. With
``control``, the reference at the configuration's lower precision takes
the program's place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import check as checkmod
from harness import filler, qa


def corpus_of(config: dict) -> list:
    c = config["corpus"]
    pairs = qa.build_corpus(int(c["per_category"]), seed=int(c["seed"]))
    if len(pairs) != int(config["corpus_pairs"]):
        raise ValueError(f"corpus has {len(pairs)} pairs, the configuration "
                         f"states {config['corpus_pairs']}")
    return pairs


def build(config: dict, corpus, seed: int, *, tracer=None):
    """A warm ``CachedEngine`` for ``config``, its slab full; the check
    needs nothing the answers do not carry."""
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
    return engine, None


def scheduler_config(config: dict):
    from repro.serving import SchedulerConfig

    s = config["scheduler"]
    return SchedulerConfig(max_batch=int(s["max_batch"]),
                           max_wait_ms=float(s["max_wait_ms"]),
                           coalesce=bool(s["coalesce"]))


def filler_tops(texts: list[str], embed, config: dict, seed: int) -> dict:
    """Each text's best filler entries, as ``ExactCache`` takes them."""
    q = np.stack([embed(t) for t in texts]).astype(np.float32)
    scores, ranks = filler.filler_top(q, config, seed)
    return {t: (scores[j], ranks[j]) for j, t in enumerate(texts)}


def check(run, corpus, seed: int, *, control: bool = False
          ) -> tuple[bool, dict, dict]:
    """Replay the program's batches through the configuration's reference
    and compare. With ``control``, the configuration's control (its
    ``control.scorer``, a lower-precision reference) takes the program's
    place."""
    ref = run.spec.reference()
    config = run.config
    rec = run.records
    batches, unplaced = checkmod.replay_batches(rec)
    embedder = ref.Embedder(int(config["dim"]))
    texts = sorted({rec.items[i].query for b in batches for i in b})
    tops = filler_tops(texts, embedder.embed, config, seed)
    entries = [(p.question, p.answer, p.semantic_key) for p in corpus]

    def replay(**kw):
        cache = ref.ExactCache(config, entries, embedder.embed, tops, **kw)
        return checkmod.reference_rows(rec, batches, cache)

    owed, orphans = replay()
    if not control:
        served = checkmod.served_rows(rec)
    else:
        scorer = {"bf16x3": ref.bf16x3_scores}[config["control"]["scorer"]]
        rows, _ = replay(scorer=scorer)
        served = {i: checkmod.Served(r.hit, r.score, r.answer)
                  for i, r in rows.items()}
    values, seen = checkmod.compare(served, owed, float(config["threshold"]),
                                    float(config["limits"]["score_gap"]))
    values["unanswered"] = sum(r is None for r in rec.resp)
    values["unplaced"] = len(unplaced) + orphans
    ok, checks = checkmod.checks(values, config["limits"])
    seen.update(batches=len(batches), requests=len(rec))
    return ok, checks, seen
