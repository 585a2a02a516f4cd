"""``early_hit_pct``: on hand-made traces, and in a tiny traced run of
each cell on the CPU, where the paper mix's batches of hits and misses
release their hits at the peek and the all-hit FAQ batches release
none."""
from __future__ import annotations

import time
import types

import pytest

from bench_tiny import BENCH, SEED, tiny_spec
from harness import cell, runner
from repro.obs import RequestTrace

READER = cell.load_module(BENCH / "metrics" / "early_hit_pct.py")


def hit(ready: float, done: float, release: str | None) -> RequestTrace:
    tr = RequestTrace(f"rt-{ready}")
    tr.add("device_step", ready - 0.01, ready)
    tr.add("resolve", ready, done)
    tr.annotate(path="hit", ready_t=ready)
    if release is not None:
        tr.annotate(release=release)
    return tr


def run_of(traces, start=0.0, seconds=100.0):
    run = runner.Run(types.SimpleNamespace(config={}), seconds)
    run.traces, run.start = traces, start
    return run


def test_share_of_window_hits_released_early():
    miss = hit(5.0, 5.8, "batch")
    miss.annotate(path="miss")
    traces = [hit(1.0, 1.001, "early"), hit(2.0, 2.001, "early"),
              hit(3.0, 3.8, "batch"), miss,
              hit(99.0, 101.0, "early")]      # ends after the close
    assert READER.read(run_of(traces)) == pytest.approx(200.0 / 3)
    assert READER.read(run_of(traces[2:4])) == 0.0
    # a program that annotates no release
    assert READER.read(run_of([hit(1.0, 1.8, None)])) is None
    assert READER.read(run_of(None)) is None


@pytest.mark.parametrize("cell_name,engaged", [
    ("paper-8k.paper-mix", True), ("paper-8k.faq-repeat", False)])
def test_tiny_traced_run_reports_early_hits(cell_name, engaged):
    spec = tiny_spec(cell_name)
    line = runner.run_cell(spec, SEED, 1.5, True,
                           t_start=time.perf_counter(), log=lambda *a: None)
    assert line["correct"] is True, line["checks"]
    (name,) = [k for k in line["metrics"] if k.startswith("early_hit_pct")]
    value = line["metrics"][name]["value"]
    assert (0.0 < value <= 100.0) if engaged else value == 0.0, value
