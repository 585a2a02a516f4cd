"""The trace reduction: busy time as the union of op intervals, idle gaps,
the scan kernel's time by event name and the named programs' time, on
hand-made intervals and on short profiler traces recorded on a v5e
(``data/small.xplane.pb``, ``data/named.xplane.pb``)."""
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
    # the program traced here predates its named device programs: both
    # of its programs are ``jit__lambda``, so the peek reader, which
    # reads the program named ``jit_peek``, finds nothing to read
    assert {m[0].split("(")[0] for m in raw["devices"]["/device:TPU:0"][
        "modules"]} == {"jit__lambda", "jit_convert_element_type"}
    peek = cell.load_module(BENCH / "metrics" / "peek_device_ms.py")
    assert peek.read(run) is None


def test_named_programs_on_the_chip_trace():
    """A second recorded trace, of the program that names its device
    programs: the first 0.45 s of a traced ``paper-8k.faq-repeat`` run
    on one v5e (8,192 float32 slots), device plane only. Five batches
    began in it: five runs of ``jit_peek`` (each with one 47.76 us scan
    kernel) and four of ``jit_step`` (the fifth ends past 0.45 s). Its
    544 ops cover 0.58754 ms laid on a 10 ns grid independently."""
    raw = trace.read(str(BENCH / "tests" / "data" / "named.xplane.pb"))
    red = trace.reduce(raw)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.45)
    assert red["busy_s"] == pytest.approx(0.00058754, abs=2e-7)
    run = types.SimpleNamespace(trace={"raw": raw, "reduced": red})
    scan = cell.load_module(BENCH / "metrics" / "slab_scan_roofline.py")
    assert [round((b - a) * 1e6, 2) for _, a, b in scan.scan_events(run)] \
        == [47.76, 47.76, 47.76, 47.76, 47.99]
    # every scan kernel runs inside a run of the program named jit_peek
    dev = raw["devices"]["/device:TPU:0"]
    holding = {name.split("(")[0] for (name, _, _), evs
               in trace.within(dev["ops"], dev["modules"]).items()
               if any(scan.SCAN_KERNEL.match(e[0].split(" = ", 1)[0])
                      for e in evs)}
    assert holding == {"jit_peek"}
    peek = cell.load_module(BENCH / "metrics" / "peek_device_ms.py")
    step = cell.load_module(BENCH / "metrics" / "step_device_ms.py")
    assert peek.read(run) == pytest.approx(0.058594, abs=1e-6)
    assert step.read(run) == pytest.approx(0.073664, abs=1e-6)


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


def test_breakdown_names_idle_time_by_host_stage():
    """Idle device time is filed under what the host was doing: the
    scheduler's handoff (``dispatch``), the engine's stages, the loop
    setting the answers (``resolve``), and only the rest under no batch
    in service. A coalesced waiter's spans are left out."""
    from harness import runner
    from repro.obs import RequestTrace

    raw = {"start_ns": 0, "stop_ns": 10_000_000_000, "devices": {
        "/device:TPU:0": {"ops": [("%peek = f32[] x()", 2.0, 2.5),
                                  ("%step = f32[] y()", 6.0, 6.2)],
                          "modules": []}}}
    leader = RequestTrace("leader")
    for name, a, b in [("dispatch", 1.0, 1.2), ("embed", 1.2, 2.0),
                       ("device_step", 2.0, 2.6), ("backend_call", 2.6, 5.0),
                       ("insert", 5.0, 5.5), ("respond", 5.5, 6.0),
                       ("resolve", 6.0, 6.5)]:
        leader.add(name, a, b)
    waiter = RequestTrace("waiter")
    waiter.add("coalesce_attach", 0.5, 0.5)
    waiter.add("respond", 0.5, 9.0)
    run = types.SimpleNamespace(
        traces=[leader, waiter],
        trace={"raw": raw, "reduced": trace.reduce(raw),
               "profile": types.SimpleNamespace(
                   to_perf_counter=lambda raw, t: t)})
    idle = dict(runner._breakdown(run)["idle_gaps"])
    assert idle == pytest.approx({
        "no batch in service": 1.0 + 3.5, "dispatch": 0.2, "embed": 0.8,
        "device_step": 0.1, "backend_call": 2.4, "insert": 0.5,
        "respond": 0.5, "resolve": 0.3})
