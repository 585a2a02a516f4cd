"""Smoke test of the semantic cache's serve path on a TPU.

Serves the paper's deployment — 384-wide keys, a 2^21-slot slab, 48-token
values, hit threshold 0.8, the simulated LLM backend — through
``CachedEngine`` and ``AsyncCacheServer``, built as
``python -m repro.launch.serve --scheduler async`` builds them:

    python chip_smoke.py               # one chip: three phases
    python chip_smoke.py --four-chips  # slab sharded over four chips

One chip runs three phases in turn, each on a fresh engine: the exact
index over an f32 slab, the exact index over an int8 slab, and
``IVFIndex(ncentroids=128, nprobe=16, bucket_cap=1024)``. Each phase warms
the cache with the 8,000-pair corpus and serves the same 512 requests
twice: once with the index on its default backend (the compiled Pallas
kernels) and once with the index pinned to its jnp backend, the
reference. ``--four-chips`` runs only the sharded slab over a ("data",)
mesh of four chips against the same deployment on one chip.

Every request is submitted before the first flush, so the scheduler forms
the same micro-batches in both runs and the two runs see the same slab at
every step. The runs must then agree on hit/miss for every row whose
reference score lies more than ``TOLERANCE`` from the threshold, and on
the matched entry's source id for every such hit whose reference top-1
leads its top-2 by more than ``TOLERANCE``. Top-1 scores must agree within
``TOLERANCE`` on every row. Any failed check exits non-zero. Without a TPU
the script exits non-zero before it builds anything.

The last line of stdout is ``{"ok": true, "device": {...}}``. Times and
memory printed before it are set-up diagnostics, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CAPACITY = 1 << 21
DIM = 384
VALUE_LEN = 48
THRESHOLD = 0.8
BATCH = 128
CORPUS_PER_CATEGORY = 2000     # 8,000 QA pairs
QUERIES_PER_CATEGORY = 128     # 512 requests
TOLERANCE = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass
class Run:
    """One engine's pass over the requests."""

    label: str
    responses: list
    hits: int
    positive_rate: float
    warm_s: float
    compile_s: float
    serve_s: float
    kernels: bool           # the peek program holds a Pallas TPU kernel
    peak_bytes: int | None


def serve_run(label, pairs, requests, *, capacity, key_dtype, index,
              mesh=None) -> Run:
    """Build an engine, warm it with ``pairs``, serve ``requests`` through
    the async server, free the engine, and return what it answered."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import CacheConfig
    from repro.launch.serve import compile_serve_path, judge_for, serve_async
    from repro.serving import CachedEngine, Response, SimulatedLLMBackend

    cfg = CacheConfig(dim=DIM, capacity=capacity, value_len=VALUE_LEN,
                      threshold=THRESHOLD, key_dtype=key_dtype)
    engine = CachedEngine(cfg, SimulatedLLMBackend(pairs),
                          judge=judge_for(pairs), batch_size=BATCH,
                          index=index, mesh=mesh)
    t0 = time.perf_counter()
    engine.warm(pairs)
    jax.block_until_ready(engine.runtime)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compile_serve_path(engine)
    compile_s = time.perf_counter() - t0
    peek_hlo = engine._peek_jit.lower(
        engine.runtime, jnp.zeros((BATCH, DIM), jnp.float32),
        jnp.float32(0.0), None, None, None).as_text()

    res = asyncio.run(serve_async(
        engine, [dataclasses.replace(r, explain=True) for r in requests],
        max_batch=BATCH, concurrency=len(requests)))
    check(all(isinstance(r, Response) and not r.error
              for r in res.responses),
          f"{label}: a request was not answered")
    failures = engine.metrics.resilience.backend_failures
    check(failures == 0, f"{label}: {failures} backend failures")
    cats = engine.metrics.per_category.values()
    judged = sum(m.judged_hits for m in cats)
    positive_rate = sum(m.positive_hits for m in cats) / max(judged, 1)
    stats = jax.devices()[0].memory_stats() or {}
    run = Run(label=label, responses=res.responses,
              hits=sum(r.cached for r in res.responses),
              positive_rate=positive_rate, warm_s=warm_s,
              compile_s=compile_s, serve_s=res.wall_s,
              kernels="tpu_custom_call" in peek_hlo,
              peak_bytes=stats.get("peak_bytes_in_use"))
    del engine, res
    gc.collect()
    n = len(run.responses)
    print(f"[{label}] warm {run.warm_s:.3f}s  compile {run.compile_s:.3f}s  "
          f"serve {run.serve_s:.3f}s  hit_rate {run.hits / n:.4f}  "
          f"positive_hit_rate {run.positive_rate:.4f}  "
          f"pallas_kernels {run.kernels}  "
          f"peak_bytes_in_use {run.peak_bytes}", flush=True)
    check(0 < run.hits < n, f"{label}: {run.hits}/{n} hits; the phase must "
          "exercise both the hit and the miss path")
    return run


def compare(label: str, got: Run, ref: Run) -> None:
    """Per-request decision parity of ``got`` against ``ref``."""
    near_thr = tied = mismatched = 0
    max_diff = 0.0
    for i, (g, r) in enumerate(zip(got.responses, ref.responses)):
        max_diff = max(max_diff, abs(g.score - r.score))
        if abs(r.score - THRESHOLD) <= TOLERANCE:
            near_thr += 1
            continue
        if g.cached != r.cached:
            mismatched += 1
            print(f"  row {i}: hit {g.cached} vs reference {r.cached} "
                  f"(scores {g.score:.6f} / {r.score:.6f})")
            continue
        if not r.cached:
            continue
        top = r.why["topk"]
        if len(top) > 1 and top[0]["score"] - top[1]["score"] <= TOLERANCE:
            tied += 1
            continue
        g_sid = g.why["matched_source_id"]
        r_sid = r.why["matched_source_id"]
        if g_sid != r_sid:
            mismatched += 1
            print(f"  row {i}: source id {g_sid} vs reference {r_sid}")
    print(f"[{label}] parity vs {ref.label}: {len(ref.responses)} rows, "
          f"{mismatched} mismatched; tolerance {TOLERANCE}: {near_thr} rows "
          f"within it of the threshold, {tied} hits with a top-2 within it "
          f"of the top-1; max |score diff| {max_diff:.3g}", flush=True)
    check(mismatched == 0, f"{label}: {mismatched} rows disagree with "
          f"{ref.label}")
    check(max_diff <= TOLERANCE, f"{label}: top-1 scores differ by "
          f"{max_diff:.3g} from {ref.label}")


def one_chip(pairs, requests, capacity: int) -> None:
    import jax.numpy as jnp

    from repro.core.index import ExactIndex, IVFIndex

    phases = (
        ("exact/f32", jnp.float32, lambda b: ExactIndex(backend=b)),
        ("exact/int8", jnp.int8, lambda b: ExactIndex(backend=b)),
        ("ivf/f32", jnp.float32,
         lambda b: IVFIndex(ncentroids=128, nprobe=16, bucket_cap=1024,
                            backend=b)),
    )
    for name, key_dtype, make_index in phases:
        got = serve_run(f"{name} pallas", pairs, requests, capacity=capacity,
                        key_dtype=key_dtype, index=make_index("auto"))
        ref = serve_run(f"{name} jnp", pairs, requests, capacity=capacity,
                        key_dtype=key_dtype, index=make_index("jnp"))
        check(got.kernels, f"{name}: the served program has no Pallas kernel")
        check(not ref.kernels, f"{name}: the reference ran a Pallas kernel")
        compare(name, got, ref)


def four_chips(pairs, requests, capacity: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.mesh import make_mesh

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found "
          f"{len(devices)}")
    mesh = make_mesh((4,), ("data",), devices=devices[:4])
    sharded = serve_run("sharded x4", pairs, requests, capacity=capacity,
                        key_dtype=jnp.float32, index=None, mesh=mesh)
    single = serve_run("one chip", pairs, requests, capacity=capacity,
                       key_dtype=jnp.float32, index=None)
    compare("sharded x4", sharded, single)
    per_shard = [0] * 4
    for r in sharded.responses:
        if r.cached:
            per_shard[r.why["matched_slot"] // (capacity // 4)] += 1
    print(f"[sharded x4] hits by owning shard {per_shard}", flush=True)
    check(sum(per_shard[1:]) > 0,
          "no hit was served from an entry on another shard than shard 0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the slab sharded over four chips against "
                         "the same deployment on one chip, and nothing else")
    args = ap.parse_args(argv)

    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {device.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2

    from repro.data.qa_dataset import build_corpus, build_test_queries
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import Request

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    pairs = build_corpus(CORPUS_PER_CATEGORY, seed=0)
    requests = [Request(query=q.query, category=q.category,
                        source_id=q.source_id, semantic_key=q.semantic_key)
                for q in build_test_queries(
                    pairs, n_per_category=QUERIES_PER_CATEGORY, seed=1)]
    (four_chips if args.four_chips else one_chip)(pairs, requests, CAPACITY)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
