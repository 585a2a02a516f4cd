"""Readings that the limits of ``correct`` are set from; not a benchmark run.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3 [--set key=json]

For each seed, in one process: set up the cell afresh, drive a window of
``--seconds`` at the cell's own load, and compare what the program served
with the plain reference; then put the configuration's control in the
program's place (the reference one precision step below the one the
configuration states: bfloat16 x3 for float32 at HIGHEST) and compare
that too. Prints one JSON line per seed with every compared number, for
the program and for the control. The program's readings set each limit's
lower end, the control's its upper end. ``--set`` replaces a top-level
key of the configuration, to read the program at another setting.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def control_of(spec):
    """The scorer of the configuration's control."""
    scorer = spec.config["control"]["scorer"]
    return {"bf16x3": spec.reference().bf16x3_scores}[scorer]


def readings(spec, seed: int, seconds: float, t_start: float) -> dict:
    from harness import runner

    run, corpus = runner.serve_window(spec, seed, seconds, traced=False,
                                      t_start=t_start)
    ok, checks, seen = runner.reference_check(run, corpus, seed)
    c_ok, c_checks, _ = runner.reference_check(run, corpus, seed,
                                               control=control_of(spec))
    return {"seed": seed, "program_correct": ok, "control_correct": c_ok,
            "program": {k: v["value"] for k, v in checks.items()},
            "control": {k: v["value"] for k, v in c_checks.items()},
            "rows": seen}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    from harness import cell

    spec = cell.load(ROOT, args.workload)
    for kv in args.set:
        key, value = kv.split("=", 1)
        spec.config[key] = json.loads(value)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(spec, seed, args.seconds, t_start)),
              flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
