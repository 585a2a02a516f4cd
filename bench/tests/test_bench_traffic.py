"""The traffic generator: deterministic from its seed, and each mix what
its file says it is."""
from __future__ import annotations

import collections
import json

import pytest

from bench_tiny import BENCH
from harness import cell, qa, traffic


@pytest.fixture(scope="module")
def corpus():
    return qa.build_corpus(2000, seed=0)


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def test_corpus_is_the_stated_size(corpus):
    config = json.loads(
        (BENCH / "configs" / "paper-8k.json").read_text())
    assert len(corpus) == config["corpus_pairs"] == 5625
    deployment = cell.load_module(
        BENCH / "deployments" / f"{config['deployment']}.py")
    assert len(deployment.corpus_of(config)) == 5625
    assert len({p.question for p in corpus}) == len(corpus)


def test_paper_mix_is_deterministic_and_seed_only_reorders(corpus):
    params = mix("paper-mix")
    a = traffic.generate(params, corpus, 2 ** 31 + 11, 20.0)
    b = traffic.generate(params, corpus, 2 ** 31 + 11, 20.0)
    c = traffic.generate(params, corpus, 7, 20.0)
    assert a.items == b.items and a.due_s == b.due_s
    assert a.items != c.items and a.due_s != c.due_s
    key = (lambda t: (t.query, t.source_id))
    assert sorted(a.items, key=key) == sorted(c.items, key=key)
    assert len(a.items) == round(params["rate_qps"] * 20.0)
    assert all(0 < x < y for x, y in zip(a.due_s, a.due_s[1:]))
    assert a.due_s[-1] < 20.0


def test_paper_mix_matches_its_parameters(corpus):
    params = mix("paper-mix")
    t = traffic.generate(params, corpus, 3, 20.0)
    n = len(t.items)
    novel = sum(i.source_id == -1 for i in t.items) / n
    share = params["mix"]["novel"] / sum(params["mix"].values())
    # binomial: 4 standard deviations of the novel share
    assert abs(novel - share) < 4 * (share * (1 - share) / n) ** 0.5
    cats = collections.Counter(i.category for i in t.items)
    assert max(cats.values()) - min(cats.values()) <= 1
    questions = {p.qa_id: p.question for p in corpus}
    paraphrases = [i for i in t.items if i.source_id >= 0]
    assert any(i.query != questions[i.source_id] for i in paraphrases)
    # mean rate over the window is the file's rate
    gaps = [b - a for a, b in zip(t.due_s, t.due_s[1:])]
    assert sum(gaps) / len(gaps) == pytest.approx(1 / params["rate_qps"],
                                                  rel=0.05)


def test_faq_repeat_sends_cached_questions_verbatim(corpus):
    params = mix("faq-repeat")
    t = traffic.generate(params, corpus, 2 ** 31 + 3, 2.0)
    by_id = {p.qa_id: p for p in corpus}
    assert t.loop == "closed" and t.clients == params["clients"] == 512
    for k in range(0, 20000, 7):
        item = t.item(k)
        p = by_id[item.source_id]
        assert item.query == p.question and item.semantic_key == p.semantic_key
    again = traffic.generate(params, corpus, 2 ** 31 + 3, 2.0)
    assert [again.item(k) for k in range(0, 20000, 7)] == \
        [t.item(k) for k in range(0, 20000, 7)]
    drawn = collections.Counter(t.item(k).source_id for k in range(20000))
    assert len(drawn) > 0.95 * len(corpus)


def test_unknown_kind_is_refused(corpus):
    with pytest.raises(ValueError, match="unknown request kinds"):
        traffic.generate({"loop": "closed", "clients": 2,
                          "mix": {"gibberish": 1.0}}, corpus, 1, 1.0)
