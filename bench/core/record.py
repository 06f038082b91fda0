"""What one run hands to the metric readers.

Readers take every number from here: host times the benchmark stamped,
the program's counters read at the window's open and close, the
program's queue and prefill spans (traced run), and the reduced device
trace (traced run). Times are host ``time.monotonic`` seconds unless a
name says otherwise.
"""

from __future__ import annotations

import dataclasses

from bench.core.config import ModelConfig
from bench.core.peaks import Peaks
from bench.core.trace import TraceSummary
from bench.core.traffic import Workload
from bench.core.window import Window


@dataclasses.dataclass
class Run:
    cell: str
    cfg: ModelConfig
    workload: Workload
    window: Window
    setup_s: float
    peaks: Peaks | None  # None off the chip (rehearsal)
    spans: list[dict] | None = None  # the program's span records (traced)
    trace: TraceSummary | None = None
    # (start, stop) host times of the traced part of the window
    traced: tuple[float, float] | None = None
    # the program's counters when the profiler started and stopped
    traced_counts: tuple[dict, dict] | None = None

    # ---------------- helpers the readers share ----------------

    def in_window(self, t: float) -> bool:
        return self.window.t_open <= t <= self.window.t_close

    def delta(self, counter: str) -> int:
        w = self.window
        return w.at_close[counter] - w.at_open[counter]

    def traced_delta(self, counter: str) -> int:
        a, b = self.traced_counts
        return b[counter] - a[counter]

    def token_events(self):
        """(rid, n, time) of every token of every request sent."""
        for rid, times in self.window.tokens.items():
            for n, t in enumerate(times):
                yield rid, n, t

    def spans_of(self, phase: str) -> list[dict]:
        return [s for s in (self.spans or ()) if s.get("phase") == phase]
