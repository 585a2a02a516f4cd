"""The benchmark's ``correct`` check on a batch whose hits were answered
at its peek, before its misses' backend call (DESIGN.md §12.1).

``bench/harness/check.py`` recovers the program's batches from the end
times its answers carry. Such a batch ends twice: its hits (and their
coalesced waiters) first, then its misses and theirs. The replay has to
place every answer, and serving the two groups through the plain
reference one after the other has to give the decisions and answers of
the one batch: the hit group inserts nothing, so the miss group looks up
the slab the program's peek saw.
"""
from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path
from typing import NamedTuple

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1] / "bench"


def load(rel: str):
    path = BENCH / rel
    name = "replay_release_" + path.stem
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


check = load("harness/check.py")
exact = load("references/exact_cosine.py")

CONFIG = {"key_dtype": "float32", "threshold": 0.8, "value_len": 48,
          "capacity": 64, "dim": 384}
CORPUS = [
    ("how do i reset my password", "Use the reset link on the sign-in page.",
     "pw"),
    ("where can i download my invoice", "Invoices are under Billing.",
     "invoice"),
    ("how do i cancel my subscription", "Cancel it under Plan settings.",
     "cancel"),
]


class Reply(NamedTuple):
    answer: str
    cached: bool
    score: float
    latency_s: float
    coalesced: bool


class Records:
    """What ``replay_batches`` and ``reference_rows`` read of a window."""

    def __init__(self):
        self.items, self.t0, self.t1, self.t_obs = [], [], [], []
        self.resp = []

    def __len__(self):
        return len(self.items)

    def add(self, query: str, key: str, arrival: float, end: float, *,
            cached: bool, coalesced: bool = False) -> None:
        self.items.append(types.SimpleNamespace(query=query,
                                                semantic_key=key))
        # the send bracket holds the arrival stamp; the answer carries the
        # time from that stamp to when its group's futures were set
        self.t0.append(arrival - 2e-6)
        self.t1.append(arrival + 2e-6)
        self.t_obs.append(end + 1e-4)
        self.resp.append(Reply("", cached, 0.0, end - arrival, coalesced))


def window() -> tuple[Records, list[int], list[int], list[int]]:
    """Two batches. The first releases its hits 40 ms after it starts and
    ends 800 ms later, after its misses' backend call; the second, served
    after it, holds hits only (one of them on the first batch's miss)."""
    rec = Records()
    t = 10.0
    first_hits = [0, 1, 2]
    rec.add("how do i reset my password", "pw", t, t + 0.04, cached=True)
    rec.add("where can i download my invoice ?", "invoice", t + 1e-3,
            t + 0.04, cached=True)
    rec.add("how do i reset my password", "pw", t + 2e-3, t + 0.04,
            cached=True, coalesced=True)
    first_rest = [3, 4, 5]
    rec.add("why is the sky blue at noon", "sky", t + 3e-3, t + 0.84,
            cached=False)
    rec.add("best sourdough starter feeding schedule", "bread", t + 4e-3,
            t + 0.84, cached=False)
    rec.add("why is the sky blue at noon", "sky", t + 5e-3, t + 0.84,
            cached=False, coalesced=True)
    second = [6, 7]
    rec.add("why is the sky blue at noon", "sky", t + 0.5, t + 0.9,
            cached=True)
    rec.add("how do i cancel my subscription", "cancel", t + 0.6, t + 0.9,
            cached=True)
    return rec, first_hits, first_rest, second


def reference():
    emb = exact.Embedder(CONFIG["dim"])
    texts = {q for q, *_ in CORPUS} | {it.query for it in window()[0].items}
    # no filler entry comes near any query
    tops = {q: (np.array([-1.0]), np.array([0])) for q in texts}
    return exact.ExactCache(CONFIG, CORPUS, emb.embed, tops)


def test_replay_places_a_released_batch_as_two_groups():
    rec, first_hits, first_rest, second = window()
    batches, unplaced = check.replay_batches(rec)
    assert unplaced == []
    assert batches == [first_hits, first_rest, second]


def test_two_groups_replay_as_the_one_batch():
    rec, first_hits, first_rest, second = window()
    split, orphans_split = check.reference_rows(
        rec, check.replay_batches(rec)[0], reference())
    whole, orphans_whole = check.reference_rows(
        rec, [first_hits + first_rest, second], reference())
    assert orphans_split == orphans_whole == 0
    assert sorted(split) == sorted(whole) == list(range(len(rec)))
    for i in range(len(rec)):
        assert split[i].hit == whole[i].hit == rec.resp[i].cached, i
        assert split[i].answer == whole[i].answer
        # float64 products over other batch shapes: equal to rounding
        assert split[i].score == pytest.approx(whole[i].score, abs=1e-12)
    # the first batch's miss is read back by the second batch
    assert whole[6].hit and whole[6].answer == whole[3].answer
