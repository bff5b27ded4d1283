"""What the program was doing in a traced run: its spans and the
device's idle gaps labelled with them.

    python benchmarks/chip/spans.py <trace> [--top 10]

``<trace>`` is an ``.xplane.pb`` or a directory holding one, such as
the ``.bench_trace/<cell>`` a ``--trace 1`` run of ``run.py`` leaves.
Prints one JSON object over the window that ``run.py``'s reduction
uses: ``program`` (by span name, prefix dropped: count, total and self
milliseconds of the spans that start in the window), ``idle_gaps``
(the longest, labelled ``<bench label>/<innermost program span>``, in
seconds), ``idle_s`` and ``uncovered_s`` (idle time that no program
span covers).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)

from benchlib import program_spans, trace  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--top", type=int, default=10)
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace.find_trace(path)
    window = trace.reduce(path).window
    spans = program_spans.summarize(path, window)
    g = program_spans.gaps(path, window, args.top)
    print(json.dumps({
        "window_s": (window[1] - window[0]) * 1e-9,
        "program": {k: {"count": v.count, "total_ms": v.total_ns * 1e-6,
                        "self_ms": v.self_ns * 1e-6}
                    for k, v in sorted(spans.spans.items())},
        "idle_gaps": [[label, ns * 1e-9] for label, ns in g["idle_gaps"]],
        "idle_s": g["idle_ns"] * 1e-9,
        "uncovered_s": g["uncovered_ns"] * 1e-9}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
