"""Benchmark driver - one module per paper table/figure + kernels +
roofline.  Prints ``name,us_per_call,derived`` CSV; detail JSON lands in
results/bench/.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                            [--devices N] [--code-masks]

``--devices N`` forces N host devices (XLA_FLAGS, set before any jax
import) so benches with a sharded leg (round_engine) can A/B the
taskvec-sharded engine against the single-device one on a CPU host.

``--code-masks`` adds the entropy-coded mask-wire A/B leg to benches
that take a ``code_masks`` kwarg (round_engine): coded uploads +
coded downlink streams, with the measured coded/raw uplink ratio
emitted as a row and recorded in results/bench/round_engine.json.

``--pipeline`` adds the pipelined-vs-sequential ``round_stream`` A/B
leg (plus the ``us_host_codec``/``us_device_step`` split) to benches
that take a ``pipeline`` kwarg (round_engine) — the one-command
reproduction of the pipelined rows in round_engine.json.

``--faults`` adds the async fault-trace A/B leg to benches that take a
``faults`` kwarg (round_engine): rounds/sec of the buffered async
simulator mode under 30% dropout + 2x-latency stragglers vs the
synchronous barrier loop, emitted as the ``engine_async`` row.

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
import inspect
import os
import sys
import traceback

BENCHES = [
    "bench_table1",      # Table 1: single-task clients
    "bench_table2",      # Table 2: multi-task clients
    "bench_similarity",  # Fig. 2-3: sign similarity vs relatedness
    "bench_30task",      # Fig. 4: 30-task benchmark
    "bench_scaling",     # Fig. 5: tasks-per-client scaling
    "bench_conflicts",   # Fig. 6: conflict groups + cross-task ablation
    "bench_kernels",     # Pallas kernel microbench
    "bench_round_engine",  # batched RoundEngine vs legacy server loop
    "bench_population",  # chunked engine over a 10^6-client population
    "bench_roofline",    # Roofline from the dry-run artifacts
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="reduced rounds/sizes for CI-speed runs")
    ap.add_argument("--only", default=None)
    ap.add_argument("--devices", type=int, default=1,
                    help="force N host devices; benches that take a "
                         "``devices`` kwarg add a sharded A/B leg")
    ap.add_argument("--code-masks", action="store_true",
                    help="add the entropy-coded mask-wire A/B leg to "
                         "benches that take a ``code_masks`` kwarg")
    ap.add_argument("--pipeline", action="store_true",
                    help="add the pipelined round_stream A/B leg to "
                         "benches that take a ``pipeline`` kwarg")
    ap.add_argument("--faults", action="store_true",
                    help="add the async fault-trace A/B leg (rounds/sec "
                         "async vs sync under 30%% dropout + 2x-latency "
                         "stragglers) to benches that take a ``faults`` "
                         "kwarg")
    ap.add_argument("--zoo", action="store_true",
                    help="add the cross-architecture zoo round "
                         "(bench_zoo) to the bench list")
    ap.add_argument("--serving", action="store_true",
                    help="add the multi-tenant serving bench "
                         "(bench_serving) to the bench list")
    args = ap.parse_args()

    if args.devices > 1:
        # must land before the first transitive jax import below —
        # jax locks the device count on first init
        assert "jax" not in sys.modules, "--devices needs jax unimported"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

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
            kw = {}
            params = inspect.signature(mod.run).parameters
            if "devices" in params:
                kw["devices"] = args.devices
            if "code_masks" in params:
                kw["code_masks"] = args.code_masks
            if "pipeline" in params:
                kw["pipeline"] = args.pipeline
            if "faults" in params:
                kw["faults"] = args.faults
            out = mod.run(quick=args.quick, **kw)
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
