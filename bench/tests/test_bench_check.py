"""``correct`` comes out false for the control and for each fault the
cells can have, and true for a sound run, at a tiny size on the CPU.

The faults break the timed path underneath a run that is otherwise whole:
the fused step returns its state unchanged (misses are never inserted);
half of each batch is left out of the lookup (those rows come back as
misses); an answer is altered where it is produced (the detokenizer). The
exchange between chips has no counterpart: every cell runs on one chip.
"""
from __future__ import annotations

import dataclasses
import types

import jax
import jax.numpy as jnp
import pytest

from bench_tiny import run_tiny, tiny_spec
from harness import check, qa, traffic

PAPER = "paper-8k.paper-mix"


@pytest.fixture
def repeats(monkeypatch):
    """Paper-mix traffic for the first half of the window, then a third of
    it again, sent after the first copies were answered: a repeat of a miss
    must hit the entry the miss inserted."""
    orig = traffic.generate

    def generate(params, corpus, seed, seconds, **kw):
        t = orig(params, corpus, seed, seconds / 2, **kw)
        n = len(t.items) // 3
        t.items = t.items + t.items[:n]
        t.due_s = t.due_s + [seconds * (0.6 + 0.35 * j / n)
                             for j in range(n)]
        return t

    monkeypatch.setattr(traffic, "generate", generate)


def test_sound_run_is_correct(repeats):
    ok, checks, seen = run_tiny(tiny_spec(PAPER), seconds=3.0)
    assert ok, checks
    assert seen["rows"] == seen["requests"] > 100
    assert 0 < seen["hits"] < seen["rows"]


def step_keeps_state(engine):
    orig = engine._step_jit

    def step(rt, *args):
        kept = jax.tree.map(jnp.copy, rt)
        result, _ = orig(rt, *args)
        return result, kept

    engine._step_jit = step


def half_batch_left_out(engine):
    orig = engine._peek_jit

    def peek(rt, q, *args):
        res = orig(rt, q, *args)
        out = jnp.arange(q.shape[0]) % 2 == 1
        return dataclasses.replace(
            res, hit=res.hit & ~out,
            score=jnp.where(out, -jnp.inf, res.score))

    engine._peek_jit = peek


def answer_altered(engine):
    orig = engine.tokenizer.decode
    engine.tokenizer.decode = types.MethodType(
        lambda self, ids: "altered " + orig(ids), engine.tokenizer)


@pytest.mark.parametrize("fault,number", [
    (step_keeps_state, "wrong_decisions"),
    (half_batch_left_out, "wrong_decisions"),
    (answer_altered, "wrong_answers"),
])
def test_fault_is_not_correct(repeats, fault, number):
    ok, checks, _ = run_tiny(tiny_spec(PAPER), seconds=3.0, hook=fault)
    assert not ok
    assert checks[number]["value"] > checks[number]["limit"], checks


@pytest.mark.parametrize("cell_name,number", [
    ("paper-8k.paper-mix", "score_gap"),
    ("paper-8k.faq-repeat", "score_gap"),
])
def test_control_is_not_correct(cell_name, number):
    import calibrate

    spec = tiny_spec(cell_name)
    ok, checks, _ = run_tiny(spec, seconds=2.0,
                             control=calibrate.control_of(spec))
    assert not ok
    assert checks[number]["value"] > checks[number]["limit"], checks


class _Rec(types.SimpleNamespace):
    def __len__(self):
        return len(self.resp)


def test_replay_batches_groups_by_end_time():
    # batch A ends at 10.000, batch B at 10.008; row 3's send was
    # preempted (wide bracket) but its reply came before B ended
    rows = [(9.0, 9.00001, 1.0, 10.0001),
            (9.5, 9.50002, 0.5, 10.0002),
            (9.9, 9.90001, 0.108, 10.0081),
            (9.0, 9.004, 1.0, 10.0003),
            (9.99, 9.99001, 0.018, 10.0082),
            (5.0, 5.00001, 1.0, 6.1)]
    rec = _Rec(resp=[], t0=[], t1=[], t_obs=[])
    for t0, t1, lat, obs in rows:
        rec.t0.append(t0)
        rec.t1.append(t1)
        rec.t_obs.append(obs)
        rec.resp.append(types.SimpleNamespace(latency_s=lat))
    batches, unplaced = check.replay_batches(rec)
    assert batches == [[5], [0, 1, 3], [2, 4]]
    assert unplaced == []


@pytest.mark.parametrize("vocab,wrong", [(32768, 8), (1 << 31, 0)])
def test_answers_compare_as_text(vocab, wrong):
    """The comparison reads the served text, so two words that share a
    token id are a wrong answer: at the program's default id space,
    "switch" and "to" share one, and the 8 answers of the tiny corpus
    about a network switch come back wrong; at the configured one, none."""
    from repro.data.tokenizer import HashTokenizer

    ref = tiny_spec(PAPER).reference()
    corpus = qa.build_corpus(100, seed=0)
    tok = HashTokenizer(vocab_size=vocab)
    ids = [tok.encode(p.answer, max_len=48) for p in corpus]
    served = {i: check.Served(True, 1.0, tok.decode(t).casefold())
              for i, t in enumerate(ids)}
    owed = {i: ref.Row(hit=True, score=1.0,
                       answer=ref.stored_text(p.answer, 48), margin=1.0,
                       filler_close=False)
            for i, p in enumerate(corpus)}
    values, seen = check.compare(served, owed, 0.8, 3e-6)
    assert seen["rows"] == len(corpus)
    assert values["wrong_answers"] == wrong


def test_reference_ring_evicts_the_oldest():
    """Past capacity, each insert evicts the oldest entry: a question
    whose entry was overwritten misses again."""
    import numpy as np

    ref = tiny_spec(PAPER).reference()
    config = {"threshold": 0.8, "value_len": 48, "capacity": 3,
              "key_dtype": "float32"}
    corpus = [("what is alpha", "alpha.", "a"), ("what is beta", "beta.", "b")]
    emb = ref.Embedder(384)
    none = (np.full(1, -1.0), np.zeros(1, int))
    filler_top = {t: none for t in ("what is alpha", "gamma gamma gamma",
                                    "delta delta delta")}
    cache = ref.ExactCache(config, corpus, emb.embed, filler_top)
    assert cache.serve([("what is alpha", "a")])[0].hit
    misses = cache.serve([("gamma gamma gamma", "g"),
                          ("delta delta delta", "d")])
    assert not any(r.hit for r in misses)
    assert not cache.serve([("what is alpha", "a")])[0].hit
