"""Plain reference of the exact semantic cache (paper arXiv 2411.05276).

The semantics the exact-index configurations state, written out without
the program: a query is embedded by signed feature hashing of its word
uni/bigrams and character 3/4-grams (the MiniLM stand-in); a slab key is
that embedding, L2-normalized. A lookup is a hit when the cosine of its
best live entry is at least the threshold, and a hit serves that entry's
stored answer. A miss is answered by the simulated LLM API and inserted;
the slab is a FIFO ring of ``capacity`` slots, so each insert evicts the
oldest live entry. An answer is served as the slab stores it: its first
``value_len - 1`` words and punctuation marks (the begin token takes one
slot), case-folded, one space apart.

Scores are float64 on the host over the float32 embeddings. The filler
entries that fill the slab to capacity are scored on the device, since
their keys live only there; they never decide a lookup (a random unit key
scores about N(0, 1/d) against a query), and a row whose filler score
comes near its best real score is reported, not compared.

This module imports nothing of the program under test.
"""
from __future__ import annotations

import dataclasses
import hashlib
import re

import numpy as np

_WORD = re.compile(r"\w+")
_TOKEN = re.compile(r"\w+|[^\w\s]")

def _h(s: str, salt: int) -> int:
    return int.from_bytes(hashlib.blake2s(
        s.encode(), digest_size=8, salt=salt.to_bytes(8, "little")).digest(),
        "little")


class Embedder:
    """Signed feature hashing into R^dim, L2-normalized in float32."""

    def __init__(self, dim: int, char_ngrams=(3, 4), word_weight=1.0,
                 char_weight=0.7):
        self.dim = dim
        self.char_ngrams = char_ngrams
        self.word_weight = word_weight
        self.char_weight = char_weight

    def embed(self, text: str) -> np.ndarray:
        words = _WORD.findall(text.lower().strip())
        feats: dict[int, float] = {}

        def add(tok: str, w: float) -> None:
            idx = _h(tok, 1) % self.dim
            sign = 1.0 if _h(tok, 2) & 1 else -1.0
            feats[idx] = feats.get(idx, 0.0) + sign * w

        for w in words:
            add("w:" + w, self.word_weight)
        for a, b in zip(words, words[1:]):
            add("b:" + a + "_" + b, self.word_weight * 0.8)
        joined = " ".join(words)
        for n in self.char_ngrams:
            for i in range(len(joined) - n + 1):
                add(f"c{n}:" + joined[i:i + n],
                    self.char_weight / max(len(joined), 1) * 10)
        v = np.zeros((self.dim,), dtype=np.float32)
        for idx, val in feats.items():
            v[idx] += val
        return v / max(np.linalg.norm(v), 1e-12)


def stored_text(text: str, value_len: int) -> str:
    """An answer as the value slab stores it and serves it back."""
    return " ".join(w.casefold() for w in _TOKEN.findall(text)[:value_len - 1])


def llm_answer(query: str, semantic_key: str, by_question: dict,
               by_key: dict) -> str:
    """The simulated LLM API: the gold answer of a cached question, else of
    the query's intent, else a templated answer."""
    if query in by_question:
        return by_question[query]
    if semantic_key in by_key:
        return by_key[semantic_key]
    return f"Here is a detailed answer to: {query}"


@dataclasses.dataclass
class Row:
    """What the reference (or a stand-in put in the program's place)
    serves for one request."""

    hit: bool
    score: float        # best live cosine
    answer: str         # the answer served, as the slab stores it
    margin: float       # best score less the runner-up with another answer
    filler_close: bool  # a filler entry comes within 0.01 of the best


#: Entries ranked per lookup when looking for the runner-up with another
#: answer; beyond them the margin is bounded by the last one's score.
_RANKED = 8


class ExactCache:
    """The reference cache, replayed batch by batch.

    ``corpus`` holds (question, answer, semantic_key) tuples in the order
    they were inserted after the filler; ``filler_top`` maps a query text
    to its best filler entries as ``(scores, ranks)``, ranks counting
    filler inserts from 0. ``scorer`` computes (queries, keys) scores: the
    float64 reference by default, a lower precision for the control.
    """

    def __init__(self, config: dict, corpus, embed, filler_top, *,
                 scorer=None):
        if config["key_dtype"] != "float32":
            raise ValueError(f"no reference for key_dtype "
                             f"{config['key_dtype']!r}")
        self.threshold = float(config["threshold"])
        self.value_len = int(config["value_len"])
        self.capacity = int(config["capacity"])
        self._embed = embed
        self._emb: dict[str, np.ndarray] = {}
        self.filler_top = filler_top
        self.scorer = scorer or (lambda q, k: q @ k.T)
        self.by_question = {c[0]: c[1] for c in corpus}
        self.by_key = {c[2]: c[1] for c in corpus}
        self._keys = np.stack([self.embedding(c[0]) for c in corpus])
        self._answers = [stored_text(c[1], self.value_len) for c in corpus]
        # inserts after the filler: the corpus, then every miss
        self.n_after_fill = len(corpus)
        self._seen: dict[str, tuple[int, Row]] = {}

    def embedding(self, text: str) -> np.ndarray:
        """The text's embedding, renormalized in float64."""
        e = self._emb.get(text)
        if e is None:
            e = self._embed(text).astype(np.float64)
            e = self._emb[text] = e / max(np.linalg.norm(e), 1e-300)
        return e

    def _rows(self, texts: list[str]) -> list[Row]:
        q = np.stack([self.embedding(t) for t in texts])
        scores = self.scorer(q, self._keys)
        # entries the ring has overwritten: the oldest beyond capacity
        scores[:, :max(0, self.n_after_fill - self.capacity)] = -np.inf
        n = scores.shape[1]
        top = np.argpartition(-scores, min(_RANKED, n - 1), axis=1)[
            :, :_RANKED]
        rows = []
        for i, text in enumerate(texts):
            order = top[i][np.argsort(-scores[i, top[i]], kind="stable")]
            best = int(order[0])
            s = float(scores[i, best])
            margin = s - float(scores[i, order[-1]])
            for j in order[1:]:
                if self._answers[j] != self._answers[best]:
                    margin = s - float(scores[i, j])
                    break
            f_scores, f_ranks = self.filler_top[text]
            live = f_ranks >= self.n_after_fill
            # the best live filler entry; where the ring has overwritten
            # every one of the query's best few, the rest score no higher
            # than the least of those: a bound, not a score, so a row it
            # comes near is not compared (``filler_close``)
            f_best = float(f_scores[live].max() if live.any()
                           else f_scores.min())
            top_score = max(s, f_best)
            hit = top_score >= self.threshold
            rows.append(Row(
                hit=hit, score=top_score,
                answer=self._answers[best] if hit else "",
                margin=margin, filler_close=f_best > s - 0.01))
        return rows

    def lookup(self, texts: list[str]) -> list[Row]:
        """One batch of lookups against the current slab (no inserts)."""
        version = self.n_after_fill
        todo = sorted({t for t in texts
                       if self._seen.get(t, (-1,))[0] != version})
        if todo:
            for t, row in zip(todo, self._rows(todo)):
                self._seen[t] = (version, row)
        return [dataclasses.replace(self._seen[t][1]) for t in texts]

    def serve(self, batch) -> list[Row]:
        """Serve one batch of (query, semantic_key) leaders: look up, answer
        the misses from the LLM, then insert the misses in row order."""
        rows = self.lookup([b[0] for b in batch])
        new_keys, new_answers = [], []
        for (query, skey), row in zip(batch, rows):
            if row.hit:
                continue
            row.answer = stored_text(
                llm_answer(query, skey, self.by_question, self.by_key),
                self.value_len)
            new_keys.append(self.embedding(query))
            new_answers.append(row.answer)
        if new_keys:
            self._keys = np.concatenate([self._keys, np.stack(new_keys)])
            self._answers.extend(new_answers)
            self.n_after_fill += len(new_keys)
        return rows


def bf16x3_scores(q: np.ndarray, k: np.ndarray) -> np.ndarray:
    """float32 scores at three bfloat16 passes (``Precision.HIGH``): each
    operand split into a bfloat16 head and tail, the tail-by-tail product
    dropped, float32 accumulation."""
    import ml_dtypes

    def split(x):
        x = x.astype(np.float32)
        hi = x.astype(ml_dtypes.bfloat16).astype(np.float32)
        lo = (x - hi).astype(ml_dtypes.bfloat16).astype(np.float32)
        return hi, lo

    qh, ql = split(q)
    kh, kl = split(k)
    return (qh @ kh.T + (qh @ kl.T + ql @ kh.T)).astype(np.float64)
