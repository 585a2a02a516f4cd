"""Data substrate + embedders: tokenizer round-trips, corpus statistics,
paraphrase similarity structure, encoder contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.data.qa_dataset import (CATEGORIES, build_corpus,
                                   build_test_queries, paraphrase)
from repro.data.tokenizer import EOS_ID, HashTokenizer, PAD_ID
from repro.embedding import (MINILM_L6, HashEmbedder, encode,
                             init_encoder_params)
from repro.embedding.hash_embedder import MEMO_ENTRIES

import hashlib
import random
import re


class TestTokenizer:
    def test_roundtrip(self):
        tok = HashTokenizer()
        ids = tok.encode("how do I reverse a list in Python")
        assert tok.decode(ids) == "how do i reverse a list in python"

    def test_determinism(self):
        t1, t2 = HashTokenizer(), HashTokenizer()
        assert t1.encode("hello world cache") == t2.encode("hello world cache")

    def test_batch_padding(self):
        tok = HashTokenizer()
        out, lens = tok.encode_batch(["a b c", "a"], max_len=8)
        assert out.shape == (2, 8)
        assert out[1, int(lens[1]):].tolist() == [PAD_ID] * (8 - int(lens[1]))

    @settings(max_examples=30, deadline=None)
    @given(st.text(alphabet=st.characters(whitelist_categories=("Ll", "Nd"),
                                          max_codepoint=0x7f), max_size=40))
    def test_ids_in_range(self, text):
        tok = HashTokenizer(vocab_size=1024)
        for t in tok.encode(text):
            assert 0 <= t < 1024


class TestCorpus:
    def test_sizes_and_uniqueness(self):
        pairs = build_corpus(200, seed=0)
        assert len(pairs) == 800
        assert len({p.question for p in pairs}) == 800
        for c in CATEGORIES:
            assert sum(p.category == c for p in pairs) == 200

    def test_test_queries_mix(self):
        pairs = build_corpus(200, seed=0)
        qs = build_test_queries(pairs, n_per_category=50, seed=1)
        assert len(qs) == 200
        n_para = sum(q.source_id >= 0 for q in qs)
        assert 0.5 < n_para / len(qs) < 0.95     # paraphrase-dominated mix

    def test_paraphrase_changes_text(self):
        rng = random.Random(0)
        q = "how do i reverse a list in python"
        outs = {paraphrase(q, rng, 0.8) for _ in range(20)}
        assert any(o != q for o in outs)

    def test_determinism(self):
        a = build_corpus(50, seed=3)
        b = build_corpus(50, seed=3)
        assert [p.question for p in a] == [p.question for p in b]


class TestHashEmbedder:
    def test_unit_norm(self):
        e = HashEmbedder()
        v = e.embed("hello world")
        assert np.linalg.norm(v) == pytest.approx(1.0, abs=1e-5)

    def test_paraphrase_similarity_structure(self):
        """Paraphrases score well above unrelated queries — the property the
        cache depends on (DESIGN.md §9)."""
        e = HashEmbedder()
        rng = random.Random(0)
        base = "how do i track my package from last week"
        para = paraphrase(base, rng, 0.4)
        unrelated = "python code to flatten a numpy array"
        vb, vp, vu = e.embed(base), e.embed(para), e.embed(unrelated)
        assert float(vb @ vp) > 0.7
        assert float(vb @ vu) < 0.5
        assert float(vb @ vp) > float(vb @ vu) + 0.3

    def test_deterministic(self):
        assert np.allclose(HashEmbedder().embed("abc"),
                           HashEmbedder().embed("abc"))

    def test_dim(self):
        assert HashEmbedder(dim=512).embed("x").shape == (512,)

    @pytest.mark.parametrize("case", ["corpus", "test_queries", "edge_texts",
                                      "evicted", "width_256_after_384"])
    def test_memo_is_bit_identical(self, case):
        """The token memo changes no bit of any vector, whatever the memo
        holds, and stays within its bound (DESIGN.md §9)."""
        pairs = build_corpus(2000, seed=0)
        dims = (384,)
        if case in ("corpus", "width_256_after_384"):
            texts = [p.question for p in pairs]
            assert len(texts) == 5625
            if case == "width_256_after_384":
                dims = (384, 256)
        elif case == "test_queries":
            texts = [q.query for q in
                     build_test_queries(pairs, n_per_category=500, seed=1)]
            assert len(texts) == 2000
        elif case == "edge_texts":
            texts = ["", "   ", "?!... --- ;;", "¿Dónde está mi pedido? 配送",
                     "ß straße STRASSE", "x" * 2000,
                     " ".join(f"word{i} café" for i in range(200))[:2000]]
        else:
            # ~100 distinct tokens a text: more than the memo holds
            rng = random.Random(0)
            texts = [" ".join("".join(rng.choice("abcdefghijklmnopqrstuvwxyz")
                                      for _ in range(7)) for _ in range(6))
                     for _ in range(1400)]
        for dim in dims:
            e = HashEmbedder(dim)
            got = e.embed_batch(texts)
            want = np.stack([_unmemoised_embed(t, dim) for t in texts])
            assert got.dtype == want.dtype and np.array_equal(got, want)
            assert np.array_equal(e.embed(texts[-1]), want[-1])
            info = e._slot.cache_info()
            assert info.currsize <= MEMO_ENTRIES
            if case == "evicted":
                assert info.misses > MEMO_ENTRIES + 10_000
                assert info.currsize == MEMO_ENTRIES


def _unmemoised_blake(s: str, salt: int) -> int:
    return int.from_bytes(
        hashlib.blake2s(s.encode(), digest_size=8,
                        salt=salt.to_bytes(8, "little")).digest(), "little")


def _unmemoised_embed(text: str, dim: int) -> np.ndarray:
    """The hash embedder's formula as it stood before the token memo: two
    digests per feature token, every time."""
    text = text.lower().strip()
    words = re.findall(r"\w+", text)
    feats: dict[int, float] = {}

    def add(tok: str, w: float):
        idx = _unmemoised_blake(tok, 1) % dim
        sign = 1.0 if _unmemoised_blake(tok, 2) & 1 else -1.0
        feats[idx] = feats.get(idx, 0.0) + sign * w

    for w_ in words:
        add("w:" + w_, 1.0)
    for a, b in zip(words, words[1:]):
        add("b:" + a + "_" + b, 1.0 * 0.8)
    joined = " ".join(words)
    for n in (3, 4):
        for i in range(len(joined) - n + 1):
            add(f"c{n}:" + joined[i:i + n], 0.7 / max(len(joined), 1) * 10)
    v = np.zeros((dim,), dtype=np.float32)
    for idx, val in feats.items():
        v[idx] += val
    return v / max(np.linalg.norm(v), 1e-12)


class TestEncoder:
    def test_output_contract(self):
        params = init_encoder_params(jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 16), 0,
                                    MINILM_L6.vocab)
        lengths = jnp.asarray([16, 8, 1])
        emb = encode(params, tokens, lengths)
        assert emb.shape == (3, MINILM_L6.d_model)
        np.testing.assert_allclose(np.asarray(jnp.linalg.norm(emb, axis=-1)),
                                   1.0, rtol=1e-5)

    def test_padding_invariance(self):
        """Embedding must ignore positions beyond `length`."""
        params = init_encoder_params(jax.random.PRNGKey(0))
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 3, 1000)
        t2 = t1.at[:, 8:].set(999)       # garbage in the padded region
        l = jnp.asarray([8])
        e1 = encode(params, t1, l)
        e2 = encode(params, t2, l)
        np.testing.assert_allclose(np.asarray(e1), np.asarray(e2), atol=1e-5)

    def test_jit_compatible(self):
        params = init_encoder_params(jax.random.PRNGKey(0))
        f = jax.jit(lambda p, t, l: encode(p, t, l))
        out = f(params, jnp.ones((2, 8), jnp.int32), jnp.asarray([8, 4]))
        assert bool(jnp.all(jnp.isfinite(out)))
