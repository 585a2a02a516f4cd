"""BENCHMARK.json against the benchmark's contract, and the yardstick's
arithmetic: every cell resolves its files by name, names and units keep to
their characters, each per-layer metric's cells report what it moves, and
the slab scan's work and the peak table are right."""
from __future__ import annotations

import json
import re

import pytest

from bench_tiny import BENCH, ROOT
from harness import cell, roofline

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_top_level_keys_and_command(bench):
    assert set(bench) == TOP_KEYS
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_names_units_and_entry_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell_name", [
    "paper-8k.paper-mix", "paper-8k.faq-repeat"])
def test_cell_resolves_its_files(bench, cell_name):
    spec = cell.load(ROOT, cell_name)
    assert spec.config["name"] == spec.config_name
    assert spec.traffic["loop"] in ("open", "closed")
    e2e = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and spec.per_layer
    for m in spec.end_to_end:
        assert callable(spec.reader(m, per_layer=False).value)
    for m in spec.per_layer:
        assert callable(spec.reader(m, per_layer=True).read)
        assert m["moves"] in e2e
    assert hasattr(spec.reference(), "ExactCache")


def test_per_layer_cells_report_what_they_move(bench):
    for m in bench["per_layer"]:
        for w in m["workloads"]:
            e2e, _ = cell.metrics_of(bench, w)
            assert m["moves"] in {x["name"] for x in e2e}, (m["name"], w)


def test_every_config_is_used_and_its_file_is_its_own(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]


def test_slab_scan_work_of_known_shapes():
    f32 = {"batch_size": 128, "capacity": 8192, "dim": 384,
           "key_dtype": "float32"}
    ops, nbytes = roofline.slab_scan_work(f32)
    assert ops == 2 * 128 * 8192 * 384 == 805_306_368
    assert nbytes == 8192 * 384 * 4 + 8192 + 128 * 384 * 4 == 12_787_712
    i8 = dict(f32, capacity=1 << 23, key_dtype="int8")
    ops8, nbytes8 = roofline.slab_scan_work(i8)
    assert ops8 == 1024 * ops
    assert nbytes8 == (1 << 23) * 385 + 128 * 384 * 4
    peak = roofline.peaks("TPU v5 lite", BENCH / "peaks.json")
    t, bound = roofline.least_time_s(ops, nbytes, peak)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    t8, bound8 = roofline.least_time_s(ops8, nbytes8, peak)
    assert bound8 == "compute" and t8 == pytest.approx(ops8 / 197e12)


def test_split_metric_finds_its_stem_reader(bench):
    spec = cell.load(ROOT, "paper-8k.faq-repeat")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    backend = spec.reader(by_name["device_idle_pct.backend"], per_layer=True)
    cache = spec.reader(by_name["device_idle_pct.cache"], per_layer=True)
    assert backend is cache
    assert backend.__file__.endswith("device_idle_pct.py")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary", BENCH / "peaks.json")
