"""The trace reduction: busy time as the union of op intervals, idle gaps,
and the scan kernel's time by event name, on hand-made intervals and on a
short profiler trace recorded on a v5e (``data/small.xplane.pb``)."""
from __future__ import annotations

import types

import pytest

from bench_tiny import BENCH
from harness import cell, roofline, trace

DATA = BENCH / "tests" / "data" / "small.xplane.pb"


def test_union_and_gaps_by_hand():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.7), (6.0, 7.5)]
    assert trace.union_s(spans) == pytest.approx(2.0 + 1.0 + 1.5)
    assert trace.gaps(spans, 0.0, 7.0) == [(2.0, 3.0), (4.0, 6.0)]
    assert trace.gaps(spans, -1.0, 8.0) == [(-1.0, 0.0), (2.0, 3.0),
                                            (4.0, 6.0), (7.5, 8.0)]
    assert trace.union_s([]) == 0.0


def test_op_name():
    assert trace.op_name("%fusion.10 = f32[2]{0} fusion(f32[2]{0} %a)") \
        == "fusion.10"
    assert trace.op_name("%cosine_topk_pallas.1 = (f32[128,128]) "
                         "custom-call(...)") == "cosine_topk_pallas.1"


def _run(raw):
    return types.SimpleNamespace(trace={"raw": raw,
                                        "reduced": trace.reduce(raw)})


def test_reduce_by_hand_on_a_made_up_device():
    raw = {"start_ns": 0, "stop_ns": 10_000_000_000, "devices": {
        "/device:TPU:0": {
            "ops": [("%a = f32[] x()", 1.0, 2.0),
                    ("%cosine_topk_pallas.1 = (f32[]) custom-call()",
                     2.0, 5.0),
                    ("%b = f32[] y()", 4.0, 6.0),
                    ("%a = f32[] x()", 9.0, 11.0)],
            "modules": [("jit_peek(1)", 1.5, 5.5), ("jit_step(2)", 5.9, 6.1)],
        }}}
    red = trace.reduce(raw)
    # [1, 6] and [9, 10] inside the 10 s window
    assert red["busy_s"] == pytest.approx(6.0)
    assert red["window_s"] == pytest.approx(10.0)
    assert trace.idle_pct(_run(raw)) == pytest.approx(40.0)
    assert red["op_s"]["a"] == pytest.approx(3.0)
    scan = cell.load_module(BENCH / "metrics" / "slab_scan_roofline.py")
    events = scan.scan_events(_run(raw))
    assert [e[1:] for e in events] == [(2.0, 5.0)]
    peek = cell.load_module(BENCH / "metrics" / "peek_device_ms.py")
    # the peek program holds the kernel; of its ops only the kernel lies
    # wholly inside its run (1.5..5.5), so its busy time is 3 s
    assert peek.read(_run(raw)) == pytest.approx(3000.0)


def test_reduce_on_the_chip_trace():
    """The recorded trace: the first 0.45 s of a profile of the exact
    f32 2^21 deployment serving 512 closed-loop callers on one v5e,
    device plane only. Its 510 ops cover 60.807 ms of the window; laid on
    a 100 ns grid independently they cover 60.808 ms. Five batches ran:
    five peek programs, each with one 11.844 ms scan kernel."""
    raw = trace.read(str(DATA))
    red = trace.reduce(raw)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.45)
    assert red["busy_s"] == pytest.approx(0.060807, abs=2e-6)
    run = types.SimpleNamespace(
        trace={"raw": raw, "reduced": red}, peaks=roofline.peaks(
            "TPU v5 lite", BENCH / "peaks.json"),
        config={"batch_size": 128, "capacity": 1 << 21, "dim": 384,
                "key_dtype": "float32"})
    assert trace.idle_pct(run) == pytest.approx(86.487, abs=0.01)
    scan = cell.load_module(BENCH / "metrics" / "slab_scan_roofline.py")
    events = scan.scan_events(run)
    assert len(events) == 5
    assert all(b - a == pytest.approx(0.011844, abs=1e-6)
               for _, a, b in events)
    # memory bound: 3.2233e9 bytes at 819 GB/s = 3.9357 ms of 11.844 ms
    assert scan.read(run) == pytest.approx(33.23, abs=0.05)
    peek = cell.load_module(BENCH / "metrics" / "peek_device_ms.py")
    assert peek.read(run) == pytest.approx(11.88, abs=0.01)


def test_tile_counts_no_instant_twice():
    """A waiter's long span over a batch's stages: each instant is named
    once, by the span that began last, and the pieces add up to the
    union, so idle time split by stage never exceeds the idle time."""
    spans = [(0.0, 10.0, "respond"), (1.0, 3.0, "embed"),
             (3.0, 4.0, "device_step"), (12.0, 13.0, "embed")]
    pieces = trace.tile(spans)
    assert pieces == [(0.0, 1.0, "respond"), (1.0, 3.0, "embed"),
                      (3.0, 4.0, "device_step"), (4.0, 10.0, "respond"),
                      (12.0, 13.0, "embed")]
    assert sum(b - a for a, b, _ in pieces) == pytest.approx(
        trace.union_s([(a, b) for a, b, _ in spans]))
    assert trace.tile([]) == []
