"""From a run's record to its metrics: the arithmetic every metric
reader shares. A reader is ``chipbench/metrics/<name>.py`` (end to end)
or ``chipbench/layers/<name>.py`` (one layer), found by the metric's
name in BENCHMARK.json, with one function ``read(run)`` that returns the
number, or ``None`` where the run holds nothing for it to read.

``run`` is a ``Run`` below: what the driver recorded, untouched.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional


@dataclass
class Run:
    config: dict
    traffic: dict
    seconds: float            # the window's length
    t0: float                 # window start, monotonic clock
    setup_s: float
    ops: list                 # the client's RPCs since the window opened
    units: list               # units acknowledged since the window opened
    spans: list               # the program's spans that START in the window
    peaks: dict               # the device kind's row of peaks.json
    trace: Optional[dict] = None   # trace_reduce.reduce(...) of the slice

    @property
    def t1(self) -> float:
        return self.t0 + self.seconds


def percentile(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of ALL the values; None of none."""
    if not values:
        return None
    ranked = sorted(values)
    return ranked[max(0, math.ceil(q / 100.0 * len(ranked)) - 1)]


def latencies(run: Run, kind: str) -> List[float]:
    """Seconds from send to reply of every RPC of ``kind`` SENT inside
    the window, whenever it was answered (the drain waits for each, so a
    stall at the close shows at its full length); failed ones left out
    (they count in ``failed``)."""
    return [op.done - op.sent for op in run.ops
            if op.kind == kind and op.ok and run.t0 <= op.sent < run.t1]


def span_ms(run: Run, name: str) -> List[float]:
    return [s["duration_ms"] for s in run.spans if s["name"] == name]


def launched(run: Run) -> tuple:
    """(real shards, launched groups, launched shard places) over the
    window's ``tpu.compact_stream`` spans: each carries ``shards`` and
    ``group_size`` and launches ceil(shards / group_size) fixed-shape
    groups, short ones padded with empty shards."""
    real = groups = places = 0
    for s in run.spans:
        if s["name"] == "tpu.compact_stream":
            shards = int(s["annotations"]["shards"])
            size = int(s["annotations"]["group_size"])
            real += shards
            groups += -(-shards // size)
            places += -(-shards // size) * size
    return real, groups, places
