"""The readers of the program's own serve-path spans and counters: each on
a hand-made run, and a tiny traced run of each cell on the CPU that
reports the host-side ones listed for it."""
from __future__ import annotations

import time
import types

import jax
import numpy as np
import pytest

from bench_tiny import BENCH, SEED, tiny_spec
from harness import cell, runner
from repro.obs import RequestTrace, compile_log


def reader(name: str):
    return cell.load_module(BENCH / "metrics" / f"{name}.py")


def leader(path: str, t: float, *, busy: float, ready: float,
           served: float, done: float) -> RequestTrace:
    """A leader's trace through one batch that starts at ``t``."""
    tr = RequestTrace(f"rt-{path}-{t}")
    tr.add("queue_wait", t - 1.0, t)
    tr.add("engine_busy", t - 1.0, t - 1.0 + busy, parent="queue_wait")
    tr.add("batching", t - 1.0 + busy, t, parent="queue_wait")
    tr.add("batch_form", t, t + 0.001)
    tr.add("dispatch", t + 0.001, t + 0.003)
    tr.add("device_step", t + 0.003, t + 0.010)
    tr.add("peek_sync", t + 0.006, t + 0.009, parent="device_step")
    tr.add("insert", t + 0.010, t + 0.020)
    tr.add("step_sync", t + 0.015, t + 0.017, parent="insert")
    tr.add("respond", t + 0.020, served)
    tr.add("fetch", t + 0.021, t + 0.022, parent="respond")
    tr.add("resolve", served, done)
    tr.annotate(path=path, ready_t=ready)
    return tr


def hand_run(traces, *, start=0.0, seconds=100.0, trace=None):
    run = runner.Run(types.SimpleNamespace(config={}), seconds)
    run.traces, run.start, run.trace = traces, start, trace
    return run


def two_batches():
    """Batch 1 holds a hit and a miss, batch 2 one hit."""
    b1 = dict(served=10.030, done=10.034)
    return [leader("hit", 10.0, busy=0.4, ready=10.009, **b1),
            leader("miss", 10.0, busy=0.2, ready=10.015, **b1),
            leader("hit", 20.0, busy=0.0, ready=20.009, served=20.040,
                   done=20.046)]


def test_span_readers_on_hand_made_traces():
    run = hand_run(two_batches())
    assert reader("engine_busy_wait_ms").read(run) == pytest.approx(200.0)
    # hits only: 10.034 - 10.009 and 20.046 - 20.009
    assert reader("hit_hold_ms").read(run) == pytest.approx(
        (25.0 + 37.0) / 2)
    # per batch: peek_sync 3 + step_sync 2 + fetch 1 ms, shared spans once
    assert reader("host_sync_ms").read(run) == pytest.approx(6.0)
    # dispatch 2 ms; resolve 4 and 6 ms
    assert reader("handoff_ms").read(run) == pytest.approx(2.0 + 5.0)


def test_span_readers_count_only_the_window():
    # batch 2's request arrives after the close: only batch 1 counts
    run = hand_run(two_batches(), start=8.5, seconds=10.0)
    assert reader("engine_busy_wait_ms").read(run) == pytest.approx(300.0)
    assert reader("hit_hold_ms").read(run) == pytest.approx(25.0)
    assert reader("host_sync_ms").read(run) == pytest.approx(6.0)
    assert reader("handoff_ms").read(run) == pytest.approx(2.0 + 4.0)


def test_span_readers_read_nothing_without_the_spans():
    old = RequestTrace("rt-old")
    old.add("queue_wait", 0.0, 1.0)
    old.add("embed", 1.0, 2.0)
    old.annotate(path="hit")
    for run in (hand_run(None), hand_run([old])):
        for name in ("engine_busy_wait_ms", "hit_hold_ms", "host_sync_ms",
                     "handoff_ms"):
            assert reader(name).read(run) is None, name
    assert reader("step_device_ms").read(hand_run([old])) is None


def test_step_device_ms_reads_the_named_program():
    raw = {"start_ns": 0, "stop_ns": 10_000_000_000, "devices": {
        "/device:TPU:0": {
            "ops": [("%a = f32[] x()", 1.0, 1.5),
                    ("%b = f32[] y()", 1.2, 2.0),
                    ("%c = f32[] z()", 5.0, 5.5),
                    ("%d = f32[] w()", 6.0, 7.0)],
            "modules": [("jit_step(7)", 0.9, 2.1), ("jit_step(7)", 4.9, 5.6),
                        ("jit_peek(3)", 5.9, 7.1)],
        }}}
    run = hand_run([], trace={"raw": raw})
    # runs of jit_step: union 1.0..2.0 and 5.0..5.5; jit_peek's op ignored
    assert reader("step_device_ms").read(run) == pytest.approx(750.0)


def test_window_compiles_counts_lowerings_inside_the_window():
    log = compile_log()
    f = jax.jit(lambda x: x - 2.0)
    f(np.ones(5, np.float32))                  # before the window
    start = time.perf_counter()
    f(np.ones(6, np.float32))
    f(np.ones(6, np.float32))
    f(np.ones(11, np.float32))
    run = hand_run([], start=start, seconds=time.perf_counter() - start)
    mod = reader("window_compiles")
    assert mod.read(run) == 2
    assert sum(s.kind == "lowering" for s in log.spans(start)) == 2
    spec = cell.load(BENCH.parent, "paper-8k.faq-repeat")
    split = {m["name"] for m in spec.per_layer}
    assert "window_compiles.cache" in split
    assert spec.reader({"name": "window_compiles.cache"},
                       per_layer=True) is mod


HOST_METRICS = {
    "paper-8k.paper-mix": {"engine_busy_wait_ms", "hit_hold_ms",
                           "window_compiles.backend"},
    "paper-8k.faq-repeat": {"host_sync_ms", "handoff_ms",
                            "window_compiles.cache"},
}


@pytest.mark.parametrize("cell_name", sorted(HOST_METRICS))
def test_tiny_traced_run_reports_the_new_host_metrics(cell_name):
    spec = tiny_spec(cell_name)
    line = runner.run_cell(spec, SEED, 1.5, True,
                           t_start=time.perf_counter(), log=lambda *a: None)
    assert line["correct"] is True, line["checks"]
    for name in HOST_METRICS[cell_name]:
        value = line["metrics"][name]["value"]
        assert value >= 0, (name, value)
    compiles = [k for k in line["metrics"] if k.startswith("window_compiles")]
    assert [line["metrics"][k]["value"] for k in compiles] == [0]
