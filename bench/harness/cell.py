"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs[].file``: the configuration's sizes, guarantees and limits;
* ``bench/traffic/<traffic>.json``: the mix's parameters;
* ``bench/endtoend/<metric>.py`` and ``bench/metrics/<metric>.py``: one
  reader per end-to-end and per-layer metric, with ``value(run)`` or
  ``read(run)`` returning a number or None. A metric split by what it
  moves, ``<stem>.<part>``, is read by ``<stem>.py`` where it has no file
  of its own;
* ``bench/references/<reference>.py``: the configuration's plain reference;
* ``bench/deployments/<deployment>.py``: how the configuration's program is
  built and checked. It exports ``corpus_of(config)``;
  ``build(config, corpus, seed, *, tracer=None) -> (engine, kept)``, a warm
  engine and whatever its check needs that the answers do not carry
  (the runner frees the engine before the check, so that the reference
  never sets the memory peak); ``scheduler_config(config)``; and
  ``check(run, corpus, seed, *, control=False) -> (ok, checks, seen)``,
  which reads ``run.kept``.

So a cell, a configuration or a metric is added with files and entries,
without editing a file that is there.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]


def load_module(path: Path):
    """Import a file of the benchmark by its path, once."""
    name = "bench_" + "_".join(path.relative_to(BENCH).with_suffix("").parts
                               ).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Spec:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list      # metric entries of BENCHMARK.json
    per_layer: list
    root: Path

    def reader(self, metric: dict, per_layer: bool):
        d = BENCH / ("metrics" if per_layer else "endtoend")
        path = d / f"{metric['name']}.py"
        if not path.is_file():
            path = d / f"{metric['name'].split('.', 1)[0]}.py"
        return load_module(path)

    def reference(self):
        return load_module(BENCH / "references"
                           / f"{self.config['reference']}.py")

    def deployment(self):
        return load_module(BENCH / "deployments"
                           / f"{self.config['deployment']}.py")


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}")


def metrics_of(bench: dict, cell: str) -> tuple[list, list]:
    """The end-to-end metrics a cell reports, and its per-layer metrics:
    those that list it, or that list no cells and move a metric it
    reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (cell in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def load(root: Path, cell: str) -> Spec:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    w = _by_name(bench["workloads"], cell, "workload")
    c = _by_name(bench["configs"], w["config"], "configuration")
    config = json.loads((root / c["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e, per = metrics_of(bench, cell)
    return Spec(name=cell, chips=int(w["chips"]), config_name=c["name"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per, root=root)
