"""Profiler traces: capture around the harness's own calls, and the one
reduction from a trace to device busy time, per-op time and idle gaps.

The harness wraps its calls in host spans (``span(name)`` writes a
``jax.profiler.TraceAnnotation`` named ``bench.<name>``) and the traced
window in ``bench.window``.  The reduction reads the ``.xplane.pb``
with ``jax.profiler.ProfileData``:

* device ops are the events of the ``XLA Ops`` line of every
  ``/device:`` plane; busy time is the union of their intervals inside
  the window (nested ops such as a ``while`` and its body count once);
* per-op time sums each op's durations by its HLO name without the
  instance number (``%fusion.688`` -> ``fusion``), leaving out the
  control-flow containers (``while``, ``conditional``, ``call``) whose
  events span their bodies;
* an idle gap is an interval of the window in which no device op runs;
  it is labelled with the ``bench.*`` host span that overlaps it most
  (``-`` when none does).  Host and device clocks of one trace agree to
  about a millisecond, far below the gaps worth naming.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_CONTAINERS = (" while(", " conditional(", " call(")
_INSTANCE = re.compile(r"\.\d+$")
KEEP_GAPS = 64


def span(name: str):
    """Host span around one harness call (cheap when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


@contextlib.contextmanager
def capture(trace_dir: str):
    """Trace everything inside the block into ``trace_dir``; the block
    is marked as the window."""
    import jax
    jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def find_trace(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(event_name: str) -> str:
    """``%fusion.688 = bf16[...] fusion(...)`` -> ``fusion.688``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(event_name: str) -> str:
    return _INSTANCE.sub("", op_name(event_name))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


@dataclass
class TraceSummary:
    window: Tuple[float, float]                 # ns
    n_devices: int
    busy_ns: float                              # mean over devices
    op_ns: Dict[str, float]                     # by op kind, all devices
    op_events: Dict[str, List[float]] = field(default_factory=dict)
    # the longest idle gaps, longest first: (host span label, ns)
    gaps: List[Tuple[str, float]] = field(default_factory=list)
    span_count: Dict[str, int] = field(default_factory=dict)
    custom_calls: set = field(default_factory=set)   # kinds of kernels

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def _kernels(self, prefixes):
        return [k for k in self.custom_calls if k.startswith(prefixes)]

    def kernel_s(self, *prefixes: str) -> float:
        """Device seconds of the kernels (custom calls) whose op name
        starts with one of ``prefixes``."""
        return 1e-9 * sum(self.op_ns[k] for k in self._kernels(prefixes))

    def kernel_calls(self, *prefixes: str) -> int:
        return sum(len(self.op_events[k]) for k in self._kernels(prefixes))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def reduce(path: str) -> TraceSummary:
    """Reduce one ``.xplane.pb`` (a file, or a directory holding one)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    pd = ProfileData.from_file(path)
    device_events: Dict[str, List[Tuple[float, float, str]]] = {}
    spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs += [(e.start_ns, e.end_ns, e.name) for e in line.events]
            if evs:
                device_events[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.end_ns, e.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        ends = [x for evs in device_events.values() for s, e, _ in evs
                for x in (s, e)] + [x for s, e, _ in spans for x in (s, e)]
        window = (min(ends), max(ends)) if ends else (0.0, 0.0)
    w0, w1 = window

    op_ns: Dict[str, float] = {}
    op_events: Dict[str, List[float]] = {}
    custom = set()
    busy_total = 0.0
    raw_gaps: List[Tuple[float, float]] = []
    inner = [(s, e, n) for s, e, n in spans if n != WINDOW_SPAN]
    for evs in device_events.values():
        clipped = []
        for s, e, name in evs:
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            clipped.append((s, e))
            if any(c in name for c in _CONTAINERS):
                continue
            kind = op_kind(name)
            if " custom-call(" in name:
                custom.add(kind)
            op_ns[kind] = op_ns.get(kind, 0.0) + (e - s)
            op_events.setdefault(kind, []).append(e - s)
        busy = _union(clipped)
        busy_total += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        raw_gaps += [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                     if ge > gs]
    raw_gaps.sort(key=lambda g: g[0] - g[1])
    gaps = [(_label(gs, ge, inner), ge - gs) for gs, ge in raw_gaps[:KEEP_GAPS]]
    n_dev = max(len(device_events), 1)
    span_count: Dict[str, int] = {}
    for _, _, n in inner:
        key = n[len(SPAN_PREFIX):]
        span_count[key] = span_count.get(key, 0) + 1
    return TraceSummary(window, len(device_events), busy_total / n_dev,
                        op_ns, op_events, gaps, span_count, custom)


def _label(gs: float, ge: float, spans) -> str:
    best, best_ov = "-", 0.0
    for s, e, n in spans:
        ov = min(e, ge) - max(s, gs)
        if ov > best_ov:
            best, best_ov = n[len(SPAN_PREFIX):], ov
    return best
