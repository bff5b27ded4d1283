"""Program spans: the one timing mechanism of the round and serving
host paths.

``span(name)`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``.  When a profiler trace is running the span lands in
its ``.xplane.pb`` on the host plane, on the same clock as the device
ops, so a trace reduction can say what the host was doing in each idle
gap of the device; when none is running it costs about a microsecond.

``span(name, phase_us, key)`` also adds the block's *self* host
microseconds to ``phase_us[key]``: its wall time less the time of the
spans opened inside it on the same thread.  That is the quantity a
trace reduction calls self time, and it keeps a phase exclusive of its
children (``pack`` leaves out ``decode`` and the host-to-device puts).

Spans belong in eager host code only: never inside a jitted or traced
function, where they would time the trace, not the work.  The span
names the round and serving paths open are a contract read by the chip
benchmark's per-layer metrics (``benchmarks/chip/metrics``):

* ``round``: ``RoundEngine.round`` / ``round_chunked``, one per round;
  children ``round.pack`` (``pack_uploads``; its children
  ``round.decode`` and ``round.h2d``), ``round.meta`` (the chunked
  round's metadata pass and scalar folds), ``round.dispatch`` (each
  jitted engine call), ``round.wait`` (the chunked round's per-chunk
  block) and ``round.assemble`` (``_assemble_downlinks``; its
  children ``round.dispatch``, one per distinct task count, and
  ``round.encode``);
* ``serve.generate``: ``MultiTenantDecoder.generate``; children
  ``serve.route`` (``route_batch``, with ``serve.rebuild`` once per LRU
  miss of ``ModulatorStore.adapter``) and ``serve.decode``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class _OpenSpans(threading.local):
    """The spans open on each thread, innermost last."""

    def __init__(self):
        self.stack = []


_open = _OpenSpans()


class span:
    """Context manager: a profiler span named ``repro.<name>``, and with
    ``phase_us`` the block's self microseconds added under ``key``
    (default ``name``)."""

    __slots__ = ("_name", "_phase_us", "_key", "_ann", "_t0", "_inner")

    def __init__(self, name: str, phase_us: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None):
        self._name = PREFIX + name
        self._phase_us = phase_us
        self._key = key or name

    def __enter__(self) -> "span":
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        _open.stack.append(self)
        self._inner = 0.0
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        stack = _open.stack
        stack.pop()
        if stack:
            stack[-1]._inner += dt
        if self._phase_us is not None:
            self._phase_us[self._key] = (self._phase_us.get(self._key, 0.0)
                                         + (dt - self._inner) * 1e6)
        self._ann.__exit__(*exc)
