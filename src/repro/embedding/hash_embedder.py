"""Deterministic hash n-gram embedder — the offline MiniLM stand-in.

Signed feature hashing of word unigrams/bigrams and character 3/4-grams
into R^dim, TF-weighted, L2-normalized. Paraphrases share most n-grams so
their cosine similarity is high; unrelated queries share few. For this
workload (short customer-service queries with lexical paraphrase
perturbations) it reproduces the similarity *structure* the paper obtains
from all-MiniLM-L6-v2 — the substitution is recorded in DESIGN.md §9.

Each feature token (``"w:…"``, ``"b:…"``, ``"c3:…"``, ``"c4:…"``) maps to
a bucket and a sign through two ``blake2s`` digests. An embedder memoises
that pair per token, for its own ``dim``, in a least-recently-used memo
of ``MEMO_ENTRIES`` tokens: the memo is exact (the vectors are
bit-identical to hashing every token afresh), bounded whatever the
traffic, and keyed by the n-gram token, never by a whole text, so what it
saves comes from vocabulary that queries share.

Everything is numpy (embedding happens host-side in the serving engine,
exactly as the paper calls an external embedding API), with a jnp batch
path for the fused device-side pipeline.
"""
from __future__ import annotations

import functools
import hashlib
import re

import numpy as np

_WORD = re.compile(r"\w+")

#: Feature tokens an embedder's memo holds, at roughly 250 bytes each.
MEMO_ENTRIES = 65_536


def _h(s: str, salt: int) -> int:
    return int.from_bytes(
        hashlib.blake2s(s.encode(), digest_size=8, salt=salt.to_bytes(8, "little")
                        ).digest(), "little")


class HashEmbedder:
    """text -> R^dim unit vector. Deterministic; its only state is the
    token memo, which never changes a result."""

    def __init__(self, dim: int = 384, char_ngrams: tuple[int, ...] = (3, 4),
                 word_weight: float = 1.0, char_weight: float = 0.7):
        self.dim = dim
        self.char_ngrams = char_ngrams
        self.word_weight = word_weight
        self.char_weight = char_weight
        self._slot = functools.lru_cache(maxsize=MEMO_ENTRIES)(self._hash)

    def _hash(self, tok: str) -> tuple[int, float]:
        return _h(tok, 1) % self.dim, 1.0 if _h(tok, 2) & 1 else -1.0

    def _features(self, text: str) -> dict[int, float]:
        text = text.lower().strip()
        words = _WORD.findall(text)
        joined = " ".join(words)
        groups = [(["w:" + w_ for w_ in words], self.word_weight),
                  (["b:" + a + "_" + b for a, b in zip(words, words[1:])],
                   self.word_weight * 0.8)]
        char_w = self.char_weight / max(len(joined), 1) * 10
        for n in self.char_ngrams:
            pre = f"c{n}:"
            groups.append(([pre + joined[i:i + n]
                            for i in range(len(joined) - n + 1)], char_w))
        slot = self._slot
        feats: dict[int, float] = {}
        for toks, w in groups:
            for tok in toks:
                idx, sign = slot(tok)
                feats[idx] = feats.get(idx, 0.0) + sign * w
        return feats

    def _fill(self, v: np.ndarray, text: str) -> None:
        """Write ``text``'s unit vector into ``v``, a zeroed float32 row."""
        feats = self._features(text)
        v[list(feats)] = list(feats.values())
        v /= max(np.linalg.norm(v), 1e-12)

    def embed(self, text: str) -> np.ndarray:
        v = np.zeros((self.dim,), dtype=np.float32)
        self._fill(v, text)
        return v

    def embed_batch(self, texts) -> np.ndarray:
        return self.embed_batch_counted(texts)[0]

    def embed_batch_counted(self, texts
                            ) -> tuple[np.ndarray, list[int], list[int]]:
        """``embed_batch`` and, per text, its feature lookups and the memo's
        hits among them. The counts are the memo's own, so an ``embed`` on
        another thread meanwhile would be counted into this batch."""
        texts = list(texts)
        out = np.zeros((len(texts), self.dim), dtype=np.float32)
        info = self._slot.cache_info
        lookups, hits = [], []
        h0, m0 = info()[:2]
        for v, t in zip(out, texts):
            self._fill(v, t)
            h1, m1 = info()[:2]
            lookups.append(h1 - h0 + m1 - m0)
            hits.append(h1 - h0)
            h0, m0 = h1, m1
        return out, lookups, hits
