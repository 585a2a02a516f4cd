"""Tiny versions of the benchmark's cells for CPU tests.

The cells' configurations, shrunk: an 8,192-slot slab, a 400-pair
corpus, a 20 ms LLM API, 60 req/s open loop or 32 closed-loop clients.
Everything else, the harness included, runs as on the chip, except the
look for a TPU and the compile cache.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import cell, runner  # noqa: E402

SEED = 20240607


def tiny_spec(name: str) -> cell.Spec:
    spec = cell.load(ROOT, name)
    c = spec.config
    c.update(capacity=8192, fill_chunk=2048, corpus_pairs=400,
             corpus={"per_category": 100, "seed": 0})
    c["backend"]["latency_per_call_s"] = 0.02
    if spec.traffic["loop"] == "open":
        spec.traffic["rate_qps"] = 60.0
    else:
        spec.traffic["clients"] = 32
    return spec


def run_tiny(spec: cell.Spec, *, seconds: float = 2.0, hook=None,
             seed: int = SEED):
    """One tiny run; returns (correct, checks, rows seen)."""
    run, corpus = runner.serve_window(spec, seed, seconds, traced=False,
                                      t_start=time.perf_counter(),
                                      engine_hook=hook)
    return spec.deployment().check(run, corpus, seed)
