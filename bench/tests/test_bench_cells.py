"""Every cell's code path at a tiny size on the CPU: the entry point stops
at the look for a TPU, and the rest of a run, traced and untraced, ends in
a result line of the contract's shape."""
from __future__ import annotations

import json
import time

import pytest

from bench_tiny import BENCH, SEED, tiny_spec
from harness import cell, runner

CELLS = ["paper-8k.paper-mix", "paper-8k.faq-repeat"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_entry_point_refuses_without_tpu(cell_name, capsys):
    run = cell.load_module(BENCH / "run.py")
    rc = run.main(["--workload", cell_name, "--seed", "3", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "TPU" in out.err


@pytest.mark.parametrize("cell_name", CELLS)
def test_paper_deployment_builds_as_before(cell_name):
    """The configuration's deployment module builds the engine that the
    harness built before the deployment was a file of its own."""
    import jax.numpy as jnp
    from repro.serving import SchedulerConfig, SimulatedLLMBackend

    spec = tiny_spec(cell_name)
    deployment = spec.deployment()
    assert deployment is cell.load_module(BENCH / "deployments" / "llm_api.py")
    corpus = deployment.corpus_of(spec.config)
    assert len(corpus) == 400
    engine, kept = deployment.build(spec.config, corpus, SEED)
    assert kept is None
    c = engine.cache.config
    assert (c.dim, c.capacity, c.value_len, c.ttl, c.threshold, c.topk,
            c.eviction, c.key_dtype) == (384, 8192, 48, 3600.0, 0.8, 4,
                                         "ring", jnp.float32)
    assert type(engine.backend) is SimulatedLLMBackend
    assert (engine.backend.latency_per_call_s, engine.backend.block,
            engine.backend.cost_per_call_usd) == (0.02, True, 0.002)
    assert engine.tokenizer.vocab_size == 2 ** 31
    assert engine.batcher.batch_size == 128
    assert deployment.scheduler_config(spec.config) == SchedulerConfig(
        max_batch=128, max_wait_ms=5.0, coalesce=True)


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_end_to_end(cell_name, traced):
    spec = tiny_spec(cell_name)
    line = runner.run_cell(spec, SEED, 1.5, traced,
                           t_start=time.perf_counter(), log=lambda *a: None)
    json.dumps(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    names = {m["name"] for m in (spec.per_layer if traced
                                 else spec.end_to_end)}
    assert set(line["metrics"]) <= names
    if not traced:
        assert set(line["metrics"]) == names
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        # the host spans are read on any platform; device ones need a TPU
        host = {"queue_wait_ms", "backend_call_ms", "batch_rows", "embed_ms"}
        assert host & names <= set(line["metrics"])
