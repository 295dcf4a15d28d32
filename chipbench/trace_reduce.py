"""From a profiler recording to numbers: device busy time, time per XLA
module and op, and the idle gaps set against what the host was doing.

Two steps, so that the second can be checked on a small recording kept
as plain data (``tests/fixtures``):

- ``load_xplane(path)`` reads the profiler's ``.xplane.pb`` with nothing
  but jax into ``{"planes": [{"name", "lines": [{"name", "events":
  [[name, start_ns, duration_ns], ...]}]}]}``. Event times are
  nanoseconds since the start of the recording.
- ``reduce(recording, ...)`` is pure arithmetic on that.

On a TPU the device is a plane named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per executed op and ``XLA Modules`` one per
executed program. The host's planes hold ``TraceAnnotation`` events, one
of which (``CLOCK_MARK``) the harness writes at a known wall-clock time:
that fixes the offset between the recording's clock and the wall clock
of the program's spans.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

CLOCK_MARK = "chipbench.clock_mark"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
MAX_GAPS_BLAMED = 2000  # idle gaps set against host spans one by one

Interval = Tuple[float, float]


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str, keep_host: Iterable[str] = (CLOCK_MARK,)) -> dict:
    """The recording as plain data. Of the host's planes only the events
    named in ``keep_host`` are kept (they hold every Python call)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name in keep_host]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def names(recording: dict) -> List[str]:
    """``plane / line: events, first names`` — for a look by hand."""
    out = []
    for plane in recording["planes"]:
        for line in plane["lines"]:
            seen = list(dict.fromkeys(e[0] for e in line["events"]))
            out.append(f"{plane['name']} / {line['name']}: "
                       f"{len(line['events'])} events; {seen[:12]}")
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


def _clip(events, lo: float, hi: float):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def clock_mark_ns(recording: dict) -> Optional[float]:
    for plane in recording["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, _dur in line["events"]:
                if name == CLOCK_MARK:
                    return start
    return None


def op_group(op_name: str) -> str:
    """An op's group in the breakdown. The TPU's ``XLA Ops`` events are
    named by their HLO text (``%sort.11 = (u32[...]) sort(...)``): the
    group is the instruction's name without the number XLA gives each
    instance (``sort``, ``fusion``, ``copy-start``)."""
    head = op_name.split(" = ", 1)[0].strip().lstrip("%")
    stem, _, tail = head.rpartition(".")
    return stem if stem and tail.isdigit() else head


def blame(gaps: List[Interval], spans: list) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing. ``spans`` are
    ``(name, start, end, span_id, parent_id)``. Many requests are in
    flight at once, so at any instant several spans are open: the time
    of a gap is split equally among the spans open then that have no
    child open (the innermost of each request); where none is open it is
    ``unattributed``."""
    out: Dict[str, float] = {}
    for a, b in gaps:
        inside = [s for s in spans if s[1] < b and s[2] > a]
        edges = sorted({a, b, *(t for s in inside for t in s[1:3]
                                if a < t < b)})
        for lo, hi in zip(edges, edges[1:]):
            live = [s for s in inside if s[1] <= lo and s[2] >= hi]
            parents = {s[4] for s in live}
            leaves = [s for s in live if s[3] not in parents]
            for s in leaves:
                out[s[0]] = out.get(s[0], 0.0) + (hi - lo) / len(leaves)
            if not leaves:
                out["unattributed"] = out.get("unattributed", 0.0) + hi - lo
    return out


def reduce(recording: dict, slice_ns: Tuple[float, float],
           host_spans: Iterable[tuple] = (),
           top: int = 10) -> dict:
    """Numbers of the traced slice ``slice_ns`` (recording clock).

    ``host_spans`` are ``(name, start_ns, end_ns, span_id, parent_id)``
    of the program's own spans on the recording's clock (see ``blame``).
    Returns ``window_s``; ``busy_s`` (the union of device-op intervals,
    averaged over the device planes);
    ``modules`` ``{name: {"count", "seconds"}}`` (events that START in
    the slice, whole durations, summed over devices); ``device_ops`` and
    ``idle_gaps`` (each at most ``top`` ``[name, seconds]``)."""
    lo, hi = slice_ns
    devices = [p for p in recording["planes"]
               if p["name"].startswith(DEVICE_PLANE)]
    if not devices:
        raise ValueError("the recording has no device plane")
    busy_ns, ops_ns, modules, gaps = 0.0, {}, {}, []
    for plane in devices:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        ops = list(_clip(lines.get(OPS_LINE, ()), lo, hi))
        merged = union((a, b) for _n, a, b in ops)
        busy_ns += sum(b - a for a, b in merged)
        for name, a, b in ops:
            key = op_group(name)
            ops_ns[key] = ops_ns.get(key, 0.0) + (b - a)
        for name, start, dur in lines.get(MODULES_LINE, ()):
            if lo <= start < hi:
                m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
                m["count"] += 1
                m["seconds"] += dur / 1e9
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])  # longest first
    idle = blame(gaps[:MAX_GAPS_BLAMED], list(host_spans))
    if len(gaps) > MAX_GAPS_BLAMED:
        idle["gaps shorter than the longest %d" % MAX_GAPS_BLAMED] = sum(
            b - a for a, b in gaps[MAX_GAPS_BLAMED:])
    n = len(devices)

    def ranked(d: Dict[str, float]) -> List[list]:
        return [[k, v / 1e9 / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n,
        "modules": modules,
        "device_ops": ranked(ops_ns),
        "idle_gaps": ranked(idle),
    }
