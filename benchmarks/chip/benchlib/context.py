"""What a runner gets (``Context``) and what it gives back
(``Observations``)."""

from __future__ import annotations

import contextlib
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from benchlib import trace as tr


def process_start() -> float:
    """``time.perf_counter()`` reading of this process's start (Linux
    ``/proc``; falls back to now)."""
    now = time.perf_counter()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return now - max(uptime - ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return now


@dataclass
class Check:
    """One number compared with its limit; the run is correct only
    when every value is at most its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Observations:
    end_to_end: Dict[str, float] = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    checks: List[Check] = field(default_factory=list)
    memory_peak_bytes: int = 0
    # what per-layer readers read
    trace: Optional[tr.TraceSummary] = None
    counters: Dict[str, float] = field(default_factory=dict)
    work: Dict[str, Any] = field(default_factory=dict)
    peaks: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Context:
    cell: Any                      # manifest.Cell
    seed: int
    seconds: float
    trace: bool
    devices: List[Any]
    peaks: Dict[str, Any]
    t_start: float                 # process start, perf_counter clock
    trace_dir: str
    t_setup: Optional[float] = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def setup_done(self) -> float:
        """Mark the end of set-up: everything before the first timed
        operation, compilation included."""
        self.t_setup = time.perf_counter()
        return self.t_setup - self.t_start

    @property
    def setup_s(self) -> float:
        return self.t_setup - self.t_start

    def window_seconds(self) -> float:
        """Measured length: the run's ``--seconds``, or in a traced run
        the traffic's ``trace_seconds`` when that is shorter."""
        if self.trace:
            return min(self.seconds, float(self.traffic.get("trace_seconds",
                                                            self.seconds)))
        return self.seconds

    @contextlib.contextmanager
    def window(self):
        """The measured window; traced in a ``--trace 1`` run."""
        if self.trace:
            with tr.capture(self.trace_dir):
                yield
        else:
            yield

    def memory_peak(self) -> int:
        from benchlib.device import memory_peak
        return memory_peak(self.devices)
