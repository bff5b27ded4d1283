"""Run one cell of the chip benchmark once.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration file
and a traffic file; the traffic names its runner.  The runner builds
its inputs and weights from ``--seed``, warms up every shape it uses
(set-up), measures for ``--seconds`` and then compares what the window
produced with the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` traces the window and reports its
per-layer metrics, each read by ``metrics/<name>.py``.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``); the compared numbers, each with its limit, end
both standard error and that line.  Without a TPU, or with fewer chips
than the cell asks for, the command exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T0 = time.perf_counter()
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src"))

from benchlib import ROOT, load, manifest  # noqa: E402
from benchlib.context import Context, process_start  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(cell, ctx: Context) -> dict:
    """Drive the cell once and build the result line's object."""
    from benchlib import device
    runner = load("runners", cell.traffic["runner"])
    obs = runner.run(ctx)
    if ctx.trace:
        metrics = {}
        for spec in cell.per_layer:
            value = load("metrics", spec["name"]).read(obs)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
    else:
        metrics = {spec["name"]: {"value": float(obs.end_to_end[spec["name"]]),
                                  "unit": spec["unit"]}
                   for spec in cell.end_to_end}
    dev = device.record(ctx.devices)
    dev["memory_peak_bytes"] = int(obs.memory_peak_bytes)
    out = {"correct": bool(obs.checks) and all(c.ok for c in obs.checks)
           and obs.failed == 0,
           "attempted": int(obs.attempted), "failed": int(obs.failed),
           "metrics": metrics, "device": dev}
    if ctx.trace and obs.trace is not None:
        dev["busy_s"] = obs.trace.busy_s
        dev["window_s"] = obs.trace.window_s
        out["breakdown"] = obs.trace.breakdown()
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in obs.checks}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = manifest.load()
    cell = manifest.cell(bench, args.workload)
    from benchlib import device
    devices = device.require_chips(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)
    device.enable_compile_cache()
    t_start = min(process_start(), _T0)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), devices,
                  peaks, t_start, str(TRACE_DIR / cell.name))
    if args.trace:
        import shutil
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    out = measure(cell, ctx)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
