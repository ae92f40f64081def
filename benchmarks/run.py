"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig3,fig4,engine,roofline]

Prints ``name,us_per_call,derived`` CSV rows and writes
experiments/bench_results.csv.
"""
from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent
                       / "src"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="fig4,fig3,engine,serving,roofline")
    ap.add_argument("--budget-s", type=float, default=90.0)
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    which = set(args.only.split(","))
    rows: list[tuple] = []
    t0 = time.time()

    if "fig4" in which:
        from . import paper_fig4_recursions
        paper_fig4_recursions.run(rows, budget_s=args.budget_s)
    if "fig3" in which:
        from . import paper_fig3_query_time
        paper_fig3_query_time.run(rows, budget_s=args.budget_s)
    if "engine" in which:
        from . import engine_bench
        engine_bench.run(rows, budget_s=args.budget_s)
    if "serving" in which:
        from . import serving_bench
        serving_bench.run(rows, budget_s=args.budget_s)
    if "roofline" in which:
        from . import roofline_report
        roofline_report.run(rows)

    print("name,us_per_call,derived")
    out_lines = ["name,us_per_call,derived"]
    for name, us, derived in rows:
        line = f"{name},{us:.2f},{derived}"
        print(line)
        out_lines.append(line)
    out = pathlib.Path("experiments")
    out.mkdir(exist_ok=True)
    (out / "bench_results.csv").write_text("\n".join(out_lines) + "\n")
    print(f"# total {time.time() - t0:.1f}s, {len(rows)} rows "
          f"-> experiments/bench_results.csv", file=sys.stderr)


if __name__ == "__main__":
    main()
