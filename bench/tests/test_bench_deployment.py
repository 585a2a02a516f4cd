"""A configuration names its own deployment, and the harness runs it
without an edit: a test-only configuration, whose deployment module lives
beside it under this directory, puts the program's ``ModelBackend`` (a
reduced Mamba-2 on the device) behind the cache in place of the LLM API,
and a tiny window through the harness's own runner comes out correct."""
from __future__ import annotations

import json
import time

from bench_tiny import BENCH, ROOT, SEED
from harness import cell, runner

HERE = BENCH / "tests" / "model_backed"


def test_model_backed_deployment_runs_without_harness_edits(monkeypatch):
    config = json.loads((HERE / "config.json").read_text())
    mix = json.loads((BENCH / "traffic" / "paper-mix.json").read_text())
    spec = cell.Spec(name=f"{config['name']}.paper-mix", chips=1,
                     config_name=config["name"], config=config,
                     traffic_name="paper-mix", traffic=dict(mix, rate_qps=12.0),
                     end_to_end=[], per_layer=[], root=ROOT)
    deployment = cell.load_module(HERE / "deployment.py")
    monkeypatch.setattr(spec, "deployment", lambda: deployment)
    assert not any(config["deployment"] in p.read_text()
                   for p in (BENCH / "harness").glob("*.py"))

    run, corpus = runner.serve_window(spec, SEED, 2.0, traced=False,
                                      t_start=time.perf_counter())
    ok, checks, seen = deployment.check(run, corpus, SEED)
    assert ok, (checks, seen)
    assert seen["rows"] == seen["requests"] > 0
    assert 0 < seen["hits"] < seen["rows"]
    assert seen["backend_rows"] > 0

    # the record the engine's release would have lost is what the check
    # holds the backend to: a call left out of it is seen
    run.kept = run.kept[1:]
    ok, checks, _ = deployment.check(run, corpus, SEED)
    assert not ok
    assert checks["backend_rows_off"]["value"] > 0, checks
