"""Spans + the ``start_span`` context manager (the only tracing API most
code touches).

Design constraints (ISSUE: the read path targets ~10M Get()/s; the
reference made even *stats* optional there):

- the **unsampled** path must be near-free: one contextvar read, one
  ``random.random()`` roll (roots only), one contextvar set/reset. No
  Span object, no dict copies, no collector traffic.
- spans inside an unsampled trace short-circuit on the NOOP sentinel
  without touching the contextvar at all.
- all cost that exists only for sampled spans (id generation, wall-clock
  read, annotation dict, collector record) is paid at ~sample_rate.

Usage::

    with start_span("repl.write", db=name) as sp:
        ...
        sp.annotate(seq=seq)

``always=True`` marks control-plane operations (backup, restore, manual
compaction) that are rare enough to trace unconditionally. ``remote=ctx``
reattaches a wire/executor context captured via
:func:`~.context.wire_context` — the server-side restore half.
``boundary=True`` marks the root of a served request (the RPC server's
dispatch, and nothing else): head-unsampled it is still recorded, ALONE
(:class:`_TailRoot`), so every request has a named owner on the
timeline and the always-on operations it starts have a parent.

**Phases of a root.** A boundary root also takes phases: three atoms
each (name, begin, end) on the root's own flat record, and nothing else
kept. The request's root rides its own contextvar, so ``with
phase("db.open"):`` stamps it from anywhere in the request (also below
a head-sampled child, and on a pool thread that
:func:`~.hop.run_in_executor` carried the root to); with no root, or
under the kill switch, it is the shared no-op. A hot path asks
``request_phases()`` once, reads the clock only where that is a list,
and extends it with its own ``(name, begin, end)``. That contextvar
parents nothing but an ``always=True`` span opened where no span is
current (the pool thread), and a root that has ended (``phases`` is
None again) is no root: a task spawned under a request (a follower's
pull loop, by ``add_db``) keeps a copy of the context, and must trace
as if it had none.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from .context import _current, _root, new_id, valid_wire_context


class _NoopPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False


_NOOP_PHASE = _NoopPhase()


class _Phase:
    """``with phase(name):`` — both ends on one thread."""

    __slots__ = ("_phases", "_name", "_t0")

    def __init__(self, phases: list, name: str):
        self._phases = phases
        self._name = name

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self._phases.extend((self._name, self._t0, time.perf_counter()))
        return False


class Span:
    """One finished-or-running span. Mutable annotations; immutable ids."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name",
        "start_ms", "_t0", "duration_ms", "annotations", "error", "phases",
    )

    sampled = True

    def __init__(
        self,
        name: str,
        trace_id: str,
        parent_id: Optional[str],
        annotations: Optional[Dict[str, Any]] = None,
    ):
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_id()
        self.parent_id = parent_id
        self.start_ms = time.time() * 1000.0
        self._t0 = time.perf_counter()
        self.duration_ms: Optional[float] = None
        self.annotations = annotations or {}
        self.error: Optional[str] = None
        # a boundary root's alone, while it is open: FLAT and raw
        # (name, begin, end, name, ...: ``time.perf_counter()`` readings,
        # turned into an offset from the root's start and a duration
        # only when a reader asks)
        self.phases: Optional[list] = None

    def annotate(self, **kv: Any) -> None:
        self.annotations.update(kv)

    def to_wire(self) -> Dict[str, Any]:
        """This span as a wire/header context dict — the ONE place the
        wire shape is built (context.wire_context and every injection
        site use it, so shape changes cannot drift per-site)."""
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "sampled": True,
        }

    def finish(self) -> None:
        if self.duration_ms is None:
            self.duration_ms = (time.perf_counter() - self._t0) * 1000.0

    def to_dict(self, process: str) -> Dict[str, Any]:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "name": self.name,
            "process": process,
            "start_ms": round(self.start_ms, 3),
            "duration_ms": round(self.duration_ms or 0.0, 3),
            "annotations": self.annotations,
            "error": self.error,
        }


class _NoopSpan:
    """Sentinel for 'tracing decided OFF for this subtree'. All methods
    are no-ops; shared singleton, never recorded."""

    __slots__ = ()
    sampled = False
    boundary = False
    trace_id = ""
    span_id = ""
    phases = None

    def annotate(self, **kv: Any) -> None:
        pass


NOOP_SPAN = _NoopSpan()


class _TailRoot:
    """A head-UNSAMPLED root whose record is decided at exit. Cheap
    enough for every root op — one small object and one perf_counter
    read going in; a kept one leaves behind ONE flat tuple in the
    collector's ring (``SpanCollector.record_root``: no ids, no
    rounding, no dict until a reader asks, and nothing the garbage
    collector has to walk). ``sampled`` is False so ordinary
    descendants still take the NOOP fast path. Two kinds:

    - under tail-keep (round 14), any root: a root slower than the
      collector's ``tail_ms`` is retained in the tail ring — the
      1023/1024 head-unsampled p99 outlier becomes inspectable on
      /traces instead of invisible. Root-only by design: the decision
      can't be made until the duration is known, by which time the
      children are gone;
    - ``boundary`` (``start_span(..., boundary=True)``, the RPC server's
      dispatch): ALWAYS kept, alone, in the main ring, so every served
      request has a named owner on the timeline. An ``always=True`` span
      opened under it — in the same context, or through
      ``wire_context()`` → ``remote=`` across an executor hop — becomes
      its child (the ids are minted when the first one asks) and carries
      a full trace below itself. A slow one is held in the tail ring
      too: the same record with the same ids, never a second orphan.
      Its phases go onto that one record."""

    __slots__ = ("name", "_t0", "collector", "tail_ms", "boundary",
                 "annotations", "_trace_id", "_span_id", "phases")
    sampled = False

    def __init__(self, name: str, collector, boundary: bool,
                 annotations: Optional[Dict[str, Any]]):
        self.name = name
        self._t0 = time.perf_counter()
        # collector and threshold cached here so the exit never looks
        # the singleton up; wall-clock start is reconstructed at record
        # time (start = now - duration) — one fewer syscall per
        # unsampled root
        self.collector = collector
        self.tail_ms = collector.tail_ms
        self.boundary = boundary
        self.annotations = annotations or {}
        self._trace_id = self._span_id = ""
        self.phases: Optional[list] = None

    @property
    def trace_id(self) -> str:
        """Minted on first use, for a boundary root only: a root of any
        other kind is nobody's parent, and reads as unsampled ("")."""
        if not self._trace_id and self.boundary:
            self._trace_id = new_id()
        return self._trace_id

    @property
    def span_id(self) -> str:
        if not self._span_id and self.boundary:
            self._span_id = new_id()
        return self._span_id

    def minted_ids(self):
        """(trace_id, span_id) as far as anything has asked for them."""
        return self._trace_id, self._span_id

    def annotate(self, **kv: Any) -> None:
        self.annotations.update(kv)

    def to_wire(self) -> Dict[str, Any]:
        # root_only: only an always=True span may attach (start_span)
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "sampled": True, "root_only": True}


class start_span:
    """Context manager creating a span under the active one (or a new
    sampled/unsampled root). See module docstring for the fast-path
    contract."""

    __slots__ = ("_name", "_always", "_remote", "_boundary", "_ann",
                 "_span", "_token", "_root_token")

    def __init__(self, name: str, always: bool = False,
                 remote: Optional[dict] = None, boundary: bool = False,
                 **annotations: Any):
        self._name = name
        self._always = always
        self._remote = remote
        self._boundary = boundary
        self._ann = annotations
        self._span = NOOP_SPAN
        self._token = None
        self._root_token = None

    def __enter__(self):
        remote = self._remote
        if remote is not None and valid_wire_context(remote) \
                and (self._always or not remote.get("root_only")) \
                and _enabled():
            # (_enabled(): the RSTPU_TRACING=0 kill switch must silence
            # remotely-initiated spans too, or a disabled node would keep
            # recording and re-propagating peers' trace contexts)
            # (root_only: the context of a boundary root, which only an
            # always=True span may join; any other span goes on as if
            # no context had been handed over)
            # An explicit remote context wins over any local parent: the
            # caller is continuing a trace that crossed a process (RPC
            # header) or executor boundary — e.g. a follower's apply span
            # joins the LEADER's write trace even while a local pull span
            # is active (replicated_db._apply_updates).
            span = Span(self._name, remote["trace_id"],
                        remote["span_id"], self._ann)
        else:
            parent = _current.get()
            if parent is None and self._always:
                # on the pool thread a served request's hop carried its
                # root to, nothing is current: the always-on operation
                # joins the request's root while that is open
                parent = _root.get()
                if parent is not None and parent.phases is None:
                    parent = None
            if parent is not None:
                if not parent.sampled and not (
                        self._always and parent.boundary):
                    # inside an unsampled trace: nothing to set or reset
                    return NOOP_SPAN
                span = Span(self._name, parent.trace_id, parent.span_id,
                            self._ann)
            else:
                from .collector import SpanCollector

                col = SpanCollector.get()
                if (self._always and col.enabled) or col.sample():
                    span = Span(self._name, new_id(), None, self._ann)
                elif col.enabled and (self._boundary or col.tail_ms > 0.0):
                    # head-unsampled ROOT at a service boundary, or under
                    # tail-keep: the record is made at __exit__ (duration
                    # known). sampled is False, so ordinary descendants
                    # still take the NOOP branch.
                    root = _TailRoot(self._name, col, self._boundary,
                                     self._ann)
                    self._span = root
                    self._token = _current.set(root)
                    if self._boundary:
                        # its request's root: it takes phases, and
                        # phase() / the executor hop find it on its own
                        # contextvar whatever span is current below it
                        root.phases = []
                        self._root_token = _root.set(root)
                    return root
                else:
                    # unsampled ROOT: park the sentinel so descendants
                    # take the cheap branch above instead of re-rolling
                    # sampling
                    self._token = _current.set(NOOP_SPAN)
                    return NOOP_SPAN
        self._span = span
        self._token = _current.set(span)
        if self._boundary:  # a sampled request's root: as above
            span.phases = []
            self._root_token = _root.set(span)
        return span

    def __exit__(self, exc_type, exc, tb):
        if self._token is not None:
            _current.reset(self._token)
        span = self._span
        if span is NOOP_SPAN:
            return False
        if self._root_token is not None:
            _root.reset(self._root_token)
        if type(span) is _TailRoot:
            duration_ms = (time.perf_counter() - span._t0) * 1000.0
            # tail_exempt: the operation declared its slowness is BY
            # DESIGN (a parked long-poll serve, a long-poll pull RTT) —
            # keeping those would fill the tail ring with waits and
            # evict the genuine outliers the ring exists for
            tail = 0.0 < span.tail_ms <= duration_ms \
                and "tail_exempt" not in span.annotations
            if tail or span.boundary:
                col = span.collector
                if col.enabled:
                    col.record_root(
                        span, duration_ms, tail,
                        repr(exc) if exc_type is not None else None)
            # ended: no root to whoever still holds a copy of the
            # request's context (a task it spawned)
            span.phases = None
            return False
        if exc_type is not None and span.error is None:
            span.error = repr(exc)
        span.finish()
        from .collector import SpanCollector

        SpanCollector.get().record(span)
        span.phases = None  # ended, as above
        return False


def detached_span(name: str, parent, **annotations: Any):
    """A child span that outlives the creating stack frame — for
    operations whose completion lands on another thread (an ack-window
    waiter resolved by the loop's expiry timer or a follower ack), where
    ``with start_span(...)`` cannot scope the lifetime.

    Returns ``None`` when the parent is unsampled (callers keep the
    usual near-free unsampled path). The CALLER OWNS COMPLETION: every
    resolution path must call ``.finish()`` and hand the span to
    ``SpanCollector.get().record(...)`` — keep exactly one resolution
    funnel, as AckWindow does. This is the only sanctioned way to build
    a Span outside observability/ (rstpu-check span-manual)."""
    if parent is None or not parent.sampled:
        return None
    return Span(name, parent.trace_id, parent.span_id, dict(annotations))


def request_phases() -> Optional[list]:
    """The open served request's phases (the root's own flat list), or
    None: outside a served request, under the kill switch, or once the
    request has ended. What a hot path asks before it reads the clock::

        ph = request_phases()
        ...
        if ph is not None:
            ph.extend(("parse", t0, t1))
    """
    root = _root.get()
    return None if root is None else root.phases


def phase(name: str):
    """``with phase("db.open"):`` — a phase of the served request's
    root, from anywhere in the request; the shared no-op where there is
    no open root."""
    phases = request_phases()
    return _NOOP_PHASE if phases is None else _Phase(phases, name)


def _enabled() -> bool:
    from .collector import SpanCollector

    return SpanCollector.get().enabled
