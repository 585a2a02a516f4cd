"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout and defined by files under
``bench/``. The run sets up the cell's deployment of the program in
``src/`` (set-up is ``setup_s``), drives its traffic for ``--seconds``,
checks every answer against the configuration's plain reference, and
prints one JSON object as the last line of standard output: the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics, device busy
time and a breakdown with ``--trace 1``. Each number compared for
``correct`` is printed beside its limit at the end of standard error and
under ``checks`` in that line.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import cell, runner

    spec = cell.load(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.chips:
        print(f"bench: cell {spec.name} needs {spec.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    # every program, however quick to compile, so that a run after the
    # first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    line = runner.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
