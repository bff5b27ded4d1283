"""Benchmark driver - one module per paper table/figure + population +
roofline.  Prints ``name,us_per_call,derived`` CSV; detail JSON lands in
results/bench/.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

``--zoo`` adds the cross-architecture zoo round (bench_zoo): a mixed
round over the reduced model zoo — one real backbone per family, each
client flattening through its own TaskVectorSpace manifest — with the
round wall-clock and measured wire bits merged into
results/bench/round_engine.json under the ``zoo`` key.

``--serving`` adds the multi-tenant serving bench (bench_serving):
requests/sec of the task-routed decode subsystem (dense-routed and
fused) vs per-task-checkpoint swapping, plus the T=30 resident-bytes
ratio, recorded in results/bench/serving.json.
"""

from __future__ import annotations

import argparse
import importlib
import sys
import traceback

BENCHES = [
    "bench_table1",      # Table 1: single-task clients
    "bench_table2",      # Table 2: multi-task clients
    "bench_similarity",  # Fig. 2-3: sign similarity vs relatedness
    "bench_30task",      # Fig. 4: 30-task benchmark
    "bench_scaling",     # Fig. 5: tasks-per-client scaling
    "bench_conflicts",   # Fig. 6: conflict groups + cross-task ablation
    "bench_population",  # chunked engine over a 10^6-client population
    "bench_roofline",    # Roofline from the dry-run artifacts
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds/sizes for CI-speed runs")
    ap.add_argument("--only", default=None)
    ap.add_argument("--zoo", action="store_true",
                    help="add the cross-architecture zoo round "
                         "(bench_zoo) to the bench list")
    ap.add_argument("--serving", action="store_true",
                    help="add the multi-tenant serving bench "
                         "(bench_serving) to the bench list")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    all_benches = (BENCHES + (["bench_zoo"] if args.zoo else [])
                   + (["bench_serving"] if args.serving else []))
    benches = [b for b in all_benches
               if args.only in (None, b, b.removeprefix("bench_"))]
    print("name,us_per_call,derived")
    failed = []
    for name in benches:
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            out = mod.run(quick=args.quick)
            for row in out["rows"]:
                print(f"{row[0]},{row[1]:.1f},{row[2]}")
            sys.stdout.flush()
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
