"""Trace context: contextvar-carried active span + wire (de)serialization.

The active span rides a :mod:`contextvars` ContextVar, which gives both
propagation models this codebase needs for free:

- **asyncio**: ``asyncio.create_task`` / ``ensure_future`` snapshot the
  creating task's context, so request-handler subtasks inherit the active
  span without plumbing (the fbthrift RequestContext analog);
- **threads**: each thread has its own context, so the leader write path
  (called from arbitrary writer threads) and background flush/compaction
  threads trace independently.

The one seam contextvars do NOT cross is ``loop.run_in_executor`` (asyncio
submits the bare callable). Callers that hop onto the executor capture
:func:`wire_context` on the event-loop side and reattach it via
``start_span(..., remote=ctx)`` executor-side. A served request's hops
go through :func:`~.hop.run_in_executor`, which carries the request's
ROOT across on a contextvar of its own (and times the hop as the root's
phases): an ``always=True`` span opened there joins it with no
``remote=`` (see admin/handler.py).

Cross-process propagation uses the same dict: a sampled caller injects
``{"trace_id", "span_id", "sampled"}`` into the RPC message's JSON frame
header under the reserved top-level key ``"trace"`` (rpc/client.py), and
the server reattaches it before dispatch (rpc/server.py).
"""

from __future__ import annotations

import contextvars
import random
from typing import Any, Dict, Optional

# Holds the active Span (sampled; or a recorded root-only root, which is
# not) or the NOOP sentinel (an unsampled root was opened: descendants
# must not re-roll sampling or they'd emit orphan partial traces).
# None = no tracing decision made yet at this point.
_current: contextvars.ContextVar = contextvars.ContextVar(
    "rstpu_active_span", default=None
)

# The served request's boundary root (``start_span(..., boundary=True)``:
# the RPC server's dispatch), whatever span is current below it: where
# ``span.phase()`` and the executor hop (hop.py) find the root, and an
# ``always=True`` span its parent where nothing is current (the pool
# thread the hop carried the root to). It is NOT the current span: an
# ordinary span never sees it, and one that has ended (``phases`` None)
# is nobody's root, whoever still holds a copy of this context.
_root: contextvars.ContextVar = contextvars.ContextVar(
    "rstpu_request_root", default=None
)

TRACE_KEY = "trace"  # reserved top-level key in the RPC message header


def new_id() -> str:
    """64-bit random hex id. random.getrandbits is atomic under the GIL
    and ~10x cheaper than os.urandom — these ids are correlation keys,
    not secrets."""
    return f"{random.getrandbits(64):016x}"


def current_span():
    """The active span object, or None. The unsampled sentinel is
    returned as-is (callers check ``.sampled``)."""
    return _current.get()


def wire_context() -> Optional[Dict[str, Any]]:
    """The active context as a wire/header dict: that of a SAMPLED span,
    or of a recorded root-only root (flagged ``root_only``: only an
    ``always=True`` span joins it), else None. This is the injection
    half of cross-process (and cross-executor) propagation."""
    span = _current.get()
    # the unsampled sentinels (NOOP, a deferred tail root) have no ids
    if span is None or not (span.sampled or span.trace_id):
        return None
    return span.to_wire()


def valid_wire_context(ctx: Any) -> bool:
    """Defensive validation of a peer-supplied trace header: ids must be
    short alphanumeric strings — they end up verbatim in /traces JSON,
    the /traces.txt waterfall, rpcgrep lines, and the bench's
    marker-delimited trace block, so control characters/newlines would
    let a peer forge output lines in all of those sinks."""
    if not isinstance(ctx, dict) or ctx.get("sampled") is not True:
        return False
    tid, sid = ctx.get("trace_id"), ctx.get("span_id")
    return (
        isinstance(tid, str) and isinstance(sid, str)
        and 0 < len(tid) <= 64 and 0 < len(sid) <= 64
        and tid.isalnum() and sid.isalnum()
    )
