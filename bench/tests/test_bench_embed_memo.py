"""``embed_memo_pct``: on hand-made traces, and in a tiny traced run of
each cell on the CPU, where every FAQ question was memoised by the
corpus fill and the paper mix's paraphrases share most of its tokens."""
from __future__ import annotations

import time
import types

import pytest

from bench_tiny import BENCH, SEED, tiny_spec
from harness import cell, runner
from repro.obs import RequestTrace

READER = cell.load_module(BENCH / "metrics" / "embed_memo_pct.py")


def row(embed_t: float, done: float, lookups: int | None,
        hits: int = 0) -> RequestTrace:
    tr = RequestTrace(f"rt-{embed_t}")
    tr.add("embed", embed_t, embed_t + 0.01)
    tr.add("resolve", done - 0.001, done)
    if lookups is not None:
        tr.annotate(embed_features=lookups, embed_memo_hits=hits)
    return tr


def run_of(traces, start=0.0, seconds=100.0):
    run = runner.Run(types.SimpleNamespace(config={}), seconds)
    run.traces, run.start = traces, start
    return run


def test_share_of_window_lookups_the_memo_answered():
    traces = [row(1.0, 1.5, 100, 100), row(2.0, 2.8, 50, 20),
              row(3.0, 3.1, 0, 0),
              row(-0.5, 0.5, 400, 0),       # embedded before the open
              row(99.0, 101.0, 400, 0)]     # answered after the close
    assert READER.read(run_of(traces)) == pytest.approx(100.0 * 120 / 150)
    assert READER.read(run_of(traces[1:2])) == pytest.approx(40.0)
    # rows with no lookups inside the window, or no annotation at all
    # (a program without the memo), read nothing
    assert READER.read(run_of(traces[2:])) is None
    assert READER.read(run_of([row(1.0, 1.5, None)])) is None
    assert READER.read(run_of(None)) is None


@pytest.mark.parametrize("cell_name,least", [
    ("paper-8k.paper-mix", 50.0), ("paper-8k.faq-repeat", 100.0)])
def test_tiny_traced_run_reports_memo_share(cell_name, least):
    spec = tiny_spec(cell_name)
    line = runner.run_cell(spec, SEED, 1.5, True,
                           t_start=time.perf_counter(), log=lambda *a: None)
    assert line["correct"] is True, line["checks"]
    (name,) = [k for k in line["metrics"] if k.startswith("embed_memo_pct")]
    value = line["metrics"][name]["value"]
    assert least <= value <= 100.0, value
