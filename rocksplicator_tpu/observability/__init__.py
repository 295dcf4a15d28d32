"""Distributed tracing subsystem (spans, propagation, collection, export).

One trace follows one request across the rpc → replication → storage
layers (and across processes via the RPC frame header); the per-process
:class:`SpanCollector` ring retains recent sampled spans for the status
server's ``/traces`` (JSON) and ``/traces.txt`` (waterfall) endpoints.

The instrument the perf PRs cite: per-phase attribution of the semi-sync
write (leader receive → WAL fsync → follower ACK), the backup/restore
round trip (checkpoint → upload batches → download), and compaction
(plan → merge → install).
"""

from .collector import SpanCollector, render_trace
from .context import TRACE_KEY, current_span, wire_context
from .hop import run_in_executor
from .span import NOOP_SPAN, Span, phase, request_phases, start_span

__all__ = [
    "NOOP_SPAN",
    "Span",
    "SpanCollector",
    "TRACE_KEY",
    "current_span",
    "phase",
    "render_trace",
    "request_phases",
    "run_in_executor",
    "start_span",
    "wire_context",
]
