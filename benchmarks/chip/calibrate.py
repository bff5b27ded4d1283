"""Readings that set a cell's correctness limits, many seeds in one
process (set-up compiles once):

    python benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,13 [--seconds 3] [--control]

For each seed it prints one JSON line: the program's compared numbers
from a short window (the lower readings), and with ``--control`` the
same numbers read off the runner's control, the reference put in the
program's place at the next lower precision (the upper readings).  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(_HERE)), "src"))

from benchlib import ROOT, load, manifest  # noqa: E402
from benchlib.context import Context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = manifest.cell(manifest.load(), args.workload)
    from benchlib import device
    devices = device.require_chips(cell.chips)
    peaks = device.peaks_for(devices[0].device_kind)
    device.enable_compile_cache()
    runner = load("runners", cell.traffic["runner"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = Context(cell, seed, args.seconds, False, devices, peaks,
                      time.perf_counter(), str(ROOT / ".bench_trace" / "cal"))
        line = {"workload": cell.name, "seed": seed}
        obs = runner.run(ctx)
        line["program"] = {c.name: c.value for c in obs.checks}
        line["setup_s"] = obs.end_to_end.get("setup_s")
        if args.control:
            line["control"] = runner.control(ctx)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
