"""The pieces a deployment's check is built from: replay the program's
batches through a plain reference and compare every answered request.

The scheduler serves one batch at a time; a batch's leaders are looked up
against the slab as the batches before it left it, and its misses are
inserted after it. ``replay_batches`` recovers the batches from the
answers themselves: every answer of a batch is stamped with the same end
time, which the send bracket and ``latency_s`` pin to a few microseconds,
while one batch ends at least a slab scan after the last. The reference
then serves the same leaders in the same batches; a coalesced waiter is
owed its leader's answer.

Numbers compared, each with its limit from the configuration (the
deployment counts the last two):

* ``score_gap``: the widest gap between the best score the program
  reported and the reference's;
* ``wrong_decisions``: hit or miss unlike the reference, on rows whose
  reference score lies farther than ``score_gap``'s limit from the
  threshold;
* ``wrong_answers``: served answer texts, case-folded, unlike the
  reference's answer as the slab stores it (``stored_text``), on rows
  where the decision agrees and, for a hit, no entry with another answer
  scores within that limit of the best;
* ``unanswered``: requests that got no answer, or an error;
* ``unplaced``: answers that fit no batch, or waiters with no leader.
"""
from __future__ import annotations

import dataclasses
import math

#: Send brackets wider than this do not define a batch's end time.
_NARROW_S = 1e-4
#: Two narrow end-time intervals further apart than this are two batches.
_SPLIT_S = 5e-4


def normalize_query(text: str) -> str:
    """The coalescing key's text part: case and spacing folded."""
    return " ".join(text.split()).casefold()


def replay_batches(rec) -> tuple[list[list[int]], list[int]]:
    """The program's batches in the order they ended, as lists of request
    indexes, and the answered requests that fit no batch."""
    answered = [i for i in range(len(rec)) if rec.resp[i] is not None]
    lo = {i: rec.t0[i] + rec.resp[i].latency_s for i in answered}
    hi = {i: rec.t1[i] + rec.resp[i].latency_s for i in answered}
    def cluster(ids, ends):
        for i in sorted(ids, key=lambda i: lo[i]):
            if ends and lo[i] <= ends[-1][1] + _SPLIT_S \
                    and max(ends[-1][0], lo[i]) <= min(ends[-1][1], hi[i]):
                ends[-1][0] = max(ends[-1][0], lo[i])
                ends[-1][1] = min(ends[-1][1], hi[i])
            else:
                ends.append([lo[i], hi[i]])
        return ends

    def assign(ends):
        batches: list[list[int]] = [[] for _ in ends]
        unplaced = []
        eps = 1e-6
        for i in answered:
            fits = [k for k, (a, b) in enumerate(ends)
                    if a <= hi[i] + eps and lo[i] <= b + eps
                    and a <= rec.t_obs[i] + eps]
            if fits:
                batches[fits[-1]].append(i)
            else:
                unplaced.append(i)
        return batches, unplaced

    # batch ends from the narrow brackets; a batch whose every bracket is
    # wide (the send was preempted) gets its end from those
    ends = cluster([i for i in answered if hi[i] - lo[i] < _NARROW_S], [])
    batches, unplaced = assign(ends)
    if unplaced:
        ends = sorted(ends + cluster(unplaced, []))
        batches, unplaced = assign(ends)
    return [b for b in batches if b], unplaced


@dataclasses.dataclass
class Served:
    """One row as the program (or the control) served it."""

    hit: bool
    score: float
    answer: str


def served_rows(rec) -> dict[int, Served]:
    return {i: Served(bool(r.cached), float(r.score), r.answer.casefold())
            for i, r in enumerate(rec.resp) if r is not None}


def reference_rows(rec, batches, cache) -> tuple[dict, int]:
    """Serve the program's batches through ``cache``; returns the row it
    owes each placed request, and how many waiters had no leader."""
    owed: dict[int, object] = {}
    orphans = 0
    for batch in batches:
        leaders = [i for i in batch if not rec.resp[i].coalesced]
        rows = cache.serve([(rec.items[i].query, rec.items[i].semantic_key)
                            for i in leaders])
        by_text = {}
        for i, row in zip(leaders, rows):
            owed[i] = row
            by_text.setdefault(normalize_query(rec.items[i].query), row)
        for i in batch:
            if rec.resp[i].coalesced:
                row = by_text.get(normalize_query(rec.items[i].query))
                if row is None:
                    orphans += 1
                else:
                    owed[i] = row
    return owed, orphans


def compare(served: dict, owed: dict, threshold: float, limit: float
            ) -> tuple[dict, dict]:
    """The compared numbers (before ``unanswered``/``unplaced``) and a
    count of the rows behind them."""
    gap = 0.0
    wrong_dec = wrong_ans = 0
    seen = {"rows": 0, "near_threshold": 0, "near_tie": 0,
            "filler_close": 0, "hits": 0}
    for i, r in owed.items():
        p = served.get(i)
        if p is None:
            continue
        seen["rows"] += 1
        seen["hits"] += r.hit
        if r.filler_close:
            seen["filler_close"] += 1
        else:
            gap = max(gap, abs(p.score - r.score))
        if abs(r.score - threshold) <= limit:
            seen["near_threshold"] += 1
            continue
        if p.hit != r.hit:
            wrong_dec += 1
            continue
        if r.hit and (r.filler_close or r.margin <= limit):
            seen["near_tie"] += 1
            continue
        if p.answer != r.answer:
            wrong_ans += 1
    return ({"score_gap": gap, "wrong_decisions": wrong_dec,
             "wrong_answers": wrong_ans}, seen)


def checks(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each number beside its limit, and whether every one holds."""
    out = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out

