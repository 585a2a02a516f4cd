"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  table1/*        — paper Table 1 / Fig 4 (hits + positive hits per category)
  fig2/*          — API-call frequency, traditional vs cached
  fig3/*          — latency with vs without cache
  sec5.3/*        — threshold sweep 0.60..0.90
  sec2.7/*        — TTL behaviour
  context/*       — multi-turn record/replay: fused vs stateless follow-up
                    hit conversion + context-hit precision (DESIGN.md §16)
  shard/*         — fused step on a 4-shard forced-CPU mesh vs local: step
                    us/call + hit-mask parity (DESIGN.md §19)
  fault/*         — resilient serving under deterministic chaos: availability
                    with vs without the §20 layer, retry/breaker counters,
                    degraded-mode serving (DESIGN.md §20)
  kernel/*        — scoring-kernel scaling (slab 4k..512k); fused-IVF
                    operand bytes + exact-vs-IVF crossover (DESIGN.md §15)
  design3/*       — HNSW (paper algorithm) vs exact MXU scoring
  beyond/*        — IVF index (beyond-paper ANN); fused runtime step()
  roofline/*      — per (arch x shape) dominant roofline terms (from dry-run)
  dryrun/*        — dry-run coverage counters

Run ``python -m benchmarks.run --quick`` for a reduced-size pass.
``--json PATH`` additionally writes the machine-readable artifact
``{"meta": {...}, "rows": [...], "errors": [...]}`` — the BENCH trajectory
format CI smokes and perf PRs diff against (every row keeps ``name``,
``us_per_call`` and the parsed ``key=value`` pairs of ``derived``).
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time


def _emit(rows):
    for r in rows:
        print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        sys.stdout.flush()


def _derived_fields(derived: str) -> dict:
    """Parse the human-oriented ``key=value`` pairs (non-pairs are kept
    verbatim under ``notes``) so JSON consumers never re-parse strings."""
    fields, notes = {}, []
    for tok in str(derived).split():
        if "=" in tok:
            k, v = tok.split("=", 1)
            try:
                fields[k] = json.loads(v)
            except (json.JSONDecodeError, ValueError):
                fields[k] = v
        else:
            notes.append(tok)
    if notes:
        fields["notes"] = " ".join(notes)
    return fields


def _write_json(path: str, rows: list, errors: list, argv: list) -> None:
    import jax

    doc = {
        "meta": {
            "argv": argv,
            "jax_version": jax.__version__,
            "backend": jax.default_backend(),
            "python": platform.python_version(),
            "unix_time": time.time(),
        },
        "rows": [{
            "name": r["name"],
            "us_per_call": float(r["us_per_call"]),
            "derived": _derived_fields(r["derived"]),
            "derived_raw": str(r["derived"]),
        } for r in rows],
        "errors": errors,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"# wrote {len(doc['rows'])} rows -> {path}", file=sys.stderr)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced dataset sizes (CI-friendly)")
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filters on benchmark "
                         "group names (a group runs if any filter matches)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write a machine-readable results artifact")
    args = ap.parse_args()
    full = not args.quick

    from benchmarks import kernel_bench, paper_tables, roofline_report

    groups = []
    groups.append(("table1", lambda: paper_tables.table1(full=full)))
    # fig2/fig3 reuse table1's system run only when sizes match; rerun cheap
    summary_holder = {}

    def _table1_then_figs():
        rows, s = paper_tables.table1(full=full)
        summary_holder["s"] = s
        return rows, s

    groups = [
        ("table1", _table1_then_figs),
        ("fig2", lambda: paper_tables.fig2(summary_holder.get("s"))),
        ("fig3", lambda: paper_tables.fig3(summary_holder.get("s"))),
        ("sec5.3", lambda: paper_tables.threshold_sweep(full=False)),
        ("sec2.7", paper_tables.ttl_behaviour),
        ("tenancy", lambda: paper_tables.tenant_table(full=full)),
        ("context", lambda: paper_tables.context_table(full=full)),
        ("near", lambda: paper_tables.near_hit_table(full=full)),
        ("obs", lambda: paper_tables.obs_table(full=full)),
        ("shard", lambda: paper_tables.shard_table(full=full)),
        ("fault", lambda: paper_tables.resilience_table(full=full)),
        ("kernel", kernel_bench.cosine_topk_scaling),
        ("kernel-masked", kernel_bench.masked_lookup_scaling),
        ("kernel-ivf", kernel_bench.fused_ivf_bench),
        ("kernel-crossover", lambda: kernel_bench.ivf_crossover(full=full)),
        ("design3", kernel_bench.hnsw_vs_exact),
        ("beyond", kernel_bench.ivf_bench),
        ("beyond-fused", kernel_bench.fused_step_bench),
        ("roofline", roofline_report.rows_for_run),
        ("dryrun", roofline_report.dryrun_summary_rows),
    ]

    only = [s.strip() for s in args.only.split(",")] if args.only else None
    all_rows, errors = [], []
    for name, fn in groups:
        if only and not any(o and o in name for o in only):
            continue
        try:
            rows, _ = fn()
            _emit(rows)
            all_rows.extend(rows)
        except Exception as e:  # noqa: BLE001 — run the rest, then exit 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
            errors.append({"group": name, "error": f"{type(e).__name__}: {e}"})

    if args.json:
        _write_json(args.json, all_rows, errors, sys.argv[1:])
    if errors:
        print(f"# {len(errors)} group(s) failed: "
              f"{', '.join(e['group'] for e in errors)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
