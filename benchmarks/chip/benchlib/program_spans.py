"""The program's own spans in a traced run: what the per-layer metrics
of ``source: program_span`` read.

The program opens ``repro.*`` host spans (``repro.common.trace.span``)
around its host boundaries; a ``--trace 1`` run's ``.xplane.pb`` holds
them on the host plane beside the harness's ``bench.*`` spans, on the
device ops' clock.  This module reads them from the same file that
``trace.reduce`` reduced, over the same window:

* spans are summed by name (prefix dropped: ``round.pack``) over those
  that start in the window: count, total time, and self time (total
  less what the spans nested in it on the same host thread cover);
* an idle gap of the device is labelled ``<bench label>/<program
  span>``, naming the innermost span that overlaps it: the one that
  overlaps the gap most, the shorter on a tie (``-`` for the bench
  label when no ``bench.*`` span overlaps, the bench label alone when no
  program span does).  The result line's ``breakdown`` keeps
  ``trace.reduce``'s labels; ``spans.py`` prints these.

A metric reader gets the runner's ``Observations``, which hold the
trace's summary but not its path: :func:`of` finds, under
``.bench_trace``, the trace whose ``bench.window`` is that summary's
window.  A run that was not traced, or a program that opens no such
span, reads None, and the metric is left out of the line.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from benchlib import ROOT
from benchlib.trace import KEEP_GAPS, SPAN_PREFIX, WINDOW_SPAN, _union

PREFIX = "repro."
TRACE_DIR = ROOT / ".bench_trace"      # where run.py traces each cell

Span = Tuple[float, float, str]        # start ns, end ns, name


@dataclass
class ProgramSpan:
    """Program spans of one name that start in the window."""
    count: int = 0
    total_ns: float = 0.0
    self_ns: float = 0.0


@dataclass
class ProgramSpans:
    window: Tuple[float, float]                 # ns
    spans: Dict[str, ProgramSpan] = field(default_factory=dict)

    def ms(self, name: str) -> float:
        """Total milliseconds of the spans ``name`` (no prefix)."""
        span = self.spans.get(name)
        return span.total_ns * 1e-6 if span else 0.0

    def count(self, name: str) -> int:
        span = self.spans.get(name)
        return span.count if span else 0

    def ms_per(self, name: str, per: str) -> Optional[float]:
        """Total milliseconds of the spans ``name`` per span ``per``;
        None when either is absent from the window."""
        if not (self.count(name) and self.count(per)):
            return None
        return self.ms(name) / self.count(per)


@dataclass
class _Trace:
    bench: List[Span]                           # bench.* but the window
    windows: List[Tuple[float, float]]          # bench.window spans
    threads: List[List[Span]]                   # program spans, by line
    device: List[List[Tuple[float, float]]]     # XLA ops, by device

    @property
    def window(self) -> Optional[Tuple[float, float]]:
        if not self.windows:
            return None
        return (min(s for s, _ in self.windows),
                max(e for _, e in self.windows))


def _parse(path: str) -> _Trace:
    from jax.profiler import ProfileData
    t = _Trace([], [], [], [])
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            evs = [(e.start_ns, e.end_ns) for line in plane.lines
                   if line.name == "XLA Ops" for e in line.events]
            if evs:
                t.device.append(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                thread = []
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        t.windows.append((e.start_ns, e.end_ns))
                    elif e.name.startswith(SPAN_PREFIX):
                        t.bench.append((e.start_ns, e.end_ns,
                                        e.name[len(SPAN_PREFIX):]))
                    elif e.name.startswith(PREFIX):
                        thread.append((e.start_ns, e.end_ns,
                                       e.name[len(PREFIX):]))
                if thread:
                    t.threads.append(thread)
    return t


def _sum(threads: List[List[Span]], w0: float,
         w1: float) -> Dict[str, ProgramSpan]:
    """Count, total and self time by name of the program spans that
    start in ``[w0, w1)``; self time leaves out the direct children, the
    spans nested in it on its own thread."""
    out: Dict[str, ProgramSpan] = {}
    for thread in threads:
        # parents before their children: by start, the longer first
        order = sorted(thread, key=lambda sp: (sp[0], -sp[1]))
        child_ns = [0.0] * len(order)
        open_: List[int] = []
        for i, (s, e, _) in enumerate(order):
            while open_ and order[open_[-1]][1] <= s:
                open_.pop()
            if open_:
                parent = open_[-1]
                child_ns[parent] += min(e, order[parent][1]) - s
            open_.append(i)
        for (s, e, name), inner_ns in zip(order, child_ns):
            if w0 <= s < w1:
                agg = out.setdefault(name, ProgramSpan())
                agg.count += 1
                agg.total_ns += e - s
                agg.self_ns += e - s - inner_ns
    return out


def _label(gs: float, ge: float, bench: List[Span],
           program: List[Span]) -> str:
    best, best_ov = "-", 0.0
    for s, e, n in bench:
        ov = min(e, ge) - max(s, gs)
        if ov > best_ov:
            best, best_ov = n, ov
    inner, inner_key = None, (0.0, 0.0)
    for s, e, n in program:
        ov = min(e, ge) - max(s, gs)
        if ov > 0 and (ov, s - e) > inner_key:
            inner, inner_key = n, (ov, s - e)
    return best if inner is None else f"{best}/{inner}"


def summarize(path: str, window: Tuple[float, float]) -> ProgramSpans:
    """The program spans of the trace ``path`` over ``window`` (ns)."""
    return ProgramSpans(tuple(window), _sum(_parse(path).threads, *window))


def gaps(path: str, window: Tuple[float, float],
         keep: int = KEEP_GAPS) -> dict:
    """The device's idle gaps in ``window``, as ``trace.reduce`` finds
    them: the ``keep`` longest with their labels, longest first
    (``[label, ns]``), and over every gap the idle ns and the idle ns
    that no program span covers."""
    t = _parse(path)
    w0, w1 = window
    raw: List[Tuple[float, float]] = []
    for evs in t.device:
        busy = _union([(max(s, w0), min(e, w1)) for s, e in evs
                       if min(e, w1) > max(s, w0)])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        raw += [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                if ge > gs]
    raw.sort(key=lambda g: g[0] - g[1])
    program = [sp for thread in t.threads for sp in thread]
    covered = _union([(s, e) for s, e, _ in program])
    idle = sum(ge - gs for gs, ge in raw)
    uncovered = idle - sum(max(0.0, min(e, ge) - max(s, gs))
                           for gs, ge in raw for s, e in covered)
    return {"idle_gaps": [[_label(gs, ge, t.bench, program), ge - gs]
                          for gs, ge in raw[:keep]],
            "idle_ns": idle, "uncovered_ns": uncovered}


def _traces() -> List[str]:
    files = glob.glob(os.path.join(str(TRACE_DIR), "**", "*.xplane.pb"),
                      recursive=True)
    return sorted(files, key=os.path.getmtime, reverse=True)


_found: Dict[Tuple[float, float], ProgramSpans] = {}


def of(obs) -> Optional[ProgramSpans]:
    """The program spans of the traced run behind ``obs``: those of the
    newest trace under ``TRACE_DIR`` whose window is ``obs.trace``'s;
    None when the run was not traced or no such trace is there."""
    if obs.trace is None:
        return None
    window = tuple(obs.trace.window)
    if window not in _found:
        for path in _traces():
            t = _parse(path)
            if t.window == window:
                _found[window] = ProgramSpans(window, _sum(t.threads,
                                                           *window))
                break
        else:
            return None
    return _found[window]
