"""Find the highest rate an open-loop cell sustains; not a benchmark run.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 100,120,140

Sets the cell up afresh for each rate, as a run of the cell does (a
cache that kept the last rate's misses would hit more at the next), then
drives its traffic at that rate for ``--seconds`` and prints one JSON
line per rate: the rate answered inside
the window, the latency percentiles from each request's due time, and
the median latency of the window's first and last fifths. Past the knee
the backlog grows through the window, so the last fifth waits longer than
the first. The cell's own rate is fixed in its traffic file, at about
four fifths of the knee this finds.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import cell, deploy, runner, traffic, window

    spec = cell.load(ROOT, args.workload)
    import jax

    if jax.devices()[0].platform != "tpu" or spec.traffic["loop"] != "open":
        print("sweep: needs a TPU and an open-loop cell", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving import AsyncCacheServer

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    corpus = deploy.corpus_of(spec.config)
    for rate in (float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        engine = deploy.build(spec.config, corpus, args.seed)
        print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
        flow = traffic.generate(dict(spec.traffic, rate_qps=rate), corpus,
                                args.seed, args.seconds)
        server = AsyncCacheServer(engine,
                                  deploy.scheduler_config(spec.config))

        async def go():
            await server.start()
            rec, start = await window.drive(server, flow,
                                            runner.make_request,
                                            args.seconds)
            await server.stop()
            return rec, start

        rec, start = asyncio.run(go())
        lat = [(rec.t_obs[i] - rec.due[i]) * 1e3 if rec.resp[i] else
               float("inf") for i in range(len(rec))]
        fifth = max(1, len(lat) // 5)
        done = sum(1 for i in range(len(rec)) if rec.resp[i] is not None
                   and rec.t_obs[i] <= start + args.seconds)
        print(json.dumps({
            "rate_qps": rate, "sent": len(rec),
            "answered_in_window_qps": done / args.seconds,
            "p50_ms": runner.percentile(lat, 50),
            "p95_ms": runner.percentile(lat, 95),
            "first_fifth_p50_ms": statistics.median(lat[:fifth]),
            "last_fifth_p50_ms": statistics.median(lat[-fifth:]),
            "hits": sum(bool(r and r.cached) for r in rec.resp)}),
            flush=True)
        del server, engine
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
