"""A test-only deployment: the cache with a model on the device behind it.

It stands for what a configuration whose misses are answered by a model,
not by the simulated LLM API, brings as files of its own. The program's
``ModelBackend`` greedy-decodes the misses with a reduced architecture of
the repo (``config["arch"]``), its weights made from the run's seed. The
answers of a model with random weights carry no meaning, so the check
compares decisions with the exact-cosine reference, and holds the backend
to its one duty the answers cannot show: every miss leader, and nothing
else, reached it, once. ``build`` keeps a record of every backend call
for that, since the engine is freed before the check runs.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np

from harness import check as checkmod
from harness import qa


def corpus_of(config: dict) -> list:
    c = config["corpus"]
    return qa.build_corpus(int(c["per_category"]), seed=int(c["seed"]))


class _Recorded:
    """The backend, with the queries of each call kept in ``calls``."""

    def __init__(self, backend, calls: list):
        self.backend = backend
        self.calls = calls

    def generate(self, queries, semantic_keys=None):
        self.calls.append(list(queries))
        return self.backend.generate(queries, semantic_keys)


def build(config: dict, corpus, seed: int, *, tracer=None):
    """A warm engine over an empty slab with the corpus inserted, and the
    list its backend records each call's queries in."""
    from repro.configs import get_arch
    from repro.core.types import CacheConfig
    from repro.data.tokenizer import HashTokenizer
    from repro.models.model import Model
    from repro.serving import CachedEngine, ModelBackend, Request

    arch = get_arch(config["arch"]).reduced()
    model = Model(arch)
    params = model.init_params(jax.random.fold_in(
        jax.random.key(seed % 2 ** 32), seed // 2 ** 32))
    m = config["model"]
    calls: list[list[str]] = []
    backend = _Recorded(ModelBackend(
        model, params, HashTokenizer(vocab_size=arch.vocab),
        max_prompt_len=int(m["max_prompt_len"]),
        max_new_tokens=int(m["max_new_tokens"])), calls)
    cfg = CacheConfig(dim=int(config["dim"]), capacity=int(config["capacity"]),
                      value_len=int(config["value_len"]),
                      ttl=float(config["ttl_s"]),
                      threshold=float(config["threshold"]),
                      topk=int(config["topk"]), eviction=config["eviction"],
                      key_dtype=jnp.dtype(config["key_dtype"]))
    engine = CachedEngine(
        cfg, backend,
        tokenizer=HashTokenizer(vocab_size=int(config["value_vocab"])),
        batch_size=int(config["batch_size"]), tracer=tracer)
    engine.warm(corpus)
    p = corpus[0]
    engine.serve_batch([Request(query=p.question, category=p.category,
                                source_id=p.qa_id,
                                semantic_key=p.semantic_key)])
    jax.block_until_ready(engine.runtime)
    return engine, calls


def scheduler_config(config: dict):
    from repro.serving import SchedulerConfig

    s = config["scheduler"]
    return SchedulerConfig(max_batch=int(s["max_batch"]),
                           max_wait_ms=float(s["max_wait_ms"]),
                           coalesce=bool(s["coalesce"]))


def check(run, corpus, seed: int, *, control: bool = False
          ) -> tuple[bool, dict, dict]:
    """Decisions against the exact-cosine reference (no filler: the slab
    holds the corpus and the misses alone), and every miss leader's query
    sent to the backend once (``backend_rows_off``: rows in one and not
    the other, counted with their multiplicity)."""
    if control:
        raise ValueError("this deployment defines no control")
    ref = run.spec.reference()
    config = run.config
    rec = run.records
    batches, unplaced = checkmod.replay_batches(rec)
    embedder = ref.Embedder(int(config["dim"]))
    no_filler = (np.full(1, -1.0), np.zeros(1, int))
    texts = {rec.items[i].query for b in batches for i in b}
    cache = ref.ExactCache(config, [(p.question, p.answer, p.semantic_key)
                                    for p in corpus],
                           embedder.embed, {t: no_filler for t in texts})
    owed, orphans = checkmod.reference_rows(rec, batches, cache)
    values, seen = checkmod.compare(checkmod.served_rows(rec), owed,
                                    float(config["threshold"]),
                                    float(config["limits"]["score_gap"]))
    leaders = collections.Counter(
        rec.items[i].query for i, r in enumerate(rec.resp)
        if r is not None and not r.cached and not r.coalesced)
    sent = collections.Counter(q for call in run.kept for q in call)
    values["backend_rows_off"] = sum(((leaders - sent) + (sent - leaders))
                                     .values())
    values["unanswered"] = sum(r is None for r in rec.resp)
    values["unplaced"] = len(unplaced) + orphans
    ok, checks = checkmod.checks(values, config["limits"])
    seen.update(batches=len(batches), requests=len(rec),
                backend_calls=len(run.kept), backend_rows=sum(sent.values()))
    return ok, checks, seen
