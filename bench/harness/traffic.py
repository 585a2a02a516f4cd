"""The one traffic generator; each mix is a data file of its parameters.

A mix file (``bench/traffic/<name>.json``) gives:

* ``loop``: ``"open"`` (requests due on a schedule, whether or not earlier
  ones were answered) or ``"closed"`` (``clients`` callers, each sending
  its next request when its reply lands);
* ``mix``: weights of the request kinds: ``verbatim`` (the stored text of
  a cached question), ``paraphrase`` (a minor rewrite of one, paper §3.2)
  and ``novel`` (a heavy rewrite, usually a miss);
* for an open loop, ``rate_qps`` and ``pool_seed``. The window's requests
  and their Poisson gaps are drawn once from ``pool_seed``; the run's seed
  permutes both. So every seed sends the same work in another order and
  with other arrival times, and the seed does not change how much work a
  run holds.

Every request is drawn with Python's ``random`` from the seed alone.
"""
from __future__ import annotations

import dataclasses
import random

from harness import qa

KINDS = ("verbatim", "paraphrase", "novel")


@dataclasses.dataclass(frozen=True)
class Item:
    """One request's text and ground truth."""

    query: str
    category: str
    source_id: int
    semantic_key: str


@dataclasses.dataclass
class Traffic:
    loop: str
    items: list            # open: in send order; closed: the stream
    due_s: list | None     # open: due time of each item from window start
    clients: int = 0       # closed: number of callers
    more: object = None    # closed: draws the stream's next items

    def item(self, k: int) -> Item:
        """The stream's ``k``-th request, drawn on demand past the end."""
        while k >= len(self.items):
            self.items.extend(self.more(len(self.items)))
        return self.items[k]


def _draw(rng: random.Random, kind: str, category: str, by_cat: dict,
          known: set) -> Item:
    pool = by_cat[category]
    if kind == "verbatim":
        p = rng.choice(pool)
        return Item(p.question, category, p.qa_id, p.semantic_key)
    if kind == "paraphrase":
        p = rng.choice(pool)
        q = qa.paraphrase(p.question, rng, qa._CATEGORY_STRENGTH[category])
        return Item(q, category, p.qa_id, p.semantic_key)
    if kind == "novel":
        key = ""
        for _ in range(64):
            q, _a, key = qa._GENS[category](rng)
            q = qa.paraphrase(q, rng, 0.9)
            if q not in known:
                break
        return Item(q, category, -1, key)
    raise ValueError(f"unknown request kind {kind!r}")


def draw_items(corpus, mix: dict, n: int, rng: random.Random) -> list[Item]:
    """``n`` requests, categories in equal shares, kinds by ``mix``."""
    unknown = set(mix) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown request kinds {sorted(unknown)}")
    by_cat = {c: [] for c in qa.CATEGORIES}
    for p in corpus:
        by_cat[p.category].append(p)
    known = {p.question for p in corpus}
    kinds = sorted(mix)
    weights = [float(mix[k]) for k in kinds]
    out = []
    for j in range(n):
        (kind,) = rng.choices(kinds, weights=weights)
        out.append(_draw(rng, kind, qa.CATEGORIES[j % len(qa.CATEGORIES)],
                         by_cat, known))
    rng.shuffle(out)
    return out


def generate(params: dict, corpus, seed: int, seconds: float,
             expected_qps: float = 5000.0) -> Traffic:
    """The requests of one run of ``seconds`` from ``seed``."""
    rng = random.Random(seed)
    if params["loop"] == "open":
        rate = float(params["rate_qps"])
        n = max(1, round(rate * seconds))
        base = random.Random(int(params["pool_seed"]))
        items = draw_items(corpus, params["mix"], n, base)
        gaps = [base.expovariate(rate) for _ in range(n + 1)]
        rng.shuffle(items)
        rng.shuffle(gaps)
        scale = seconds / sum(gaps)
        due, t = [], 0.0
        for g in gaps[:n]:
            t += g
            due.append(t * scale)
        return Traffic(loop="open", items=items, due_s=due)
    if params["loop"] == "closed":
        clients = int(params["clients"])

        def more(n):
            return draw_items(corpus, params["mix"], n, rng)

        # drawn ahead for the rate expected, so the window draws no more
        return Traffic(loop="closed", clients=clients, due_s=None, more=more,
                       items=more(clients + int(seconds * expected_qps)))
    raise ValueError(f"unknown loop {params['loop']!r}")
