"""The executor hop of a served request, as phases of its root.

``loop.run_in_executor`` is the seam contextvars do not cross
(context.py), and the place a served request waits for two hand-overs of
the GIL. :func:`run_in_executor` stands in for it where a request
handler hops (``ReplicatedDB.handle_read_request`` /
``handle_write_request``, ``AdminHandler._run``): it carries the
request's root to the pool thread for the length of ``fn`` (on its own
contextvar, context.py ``_root``: ``phase(...)`` works there, an
``always=True`` span opened there is the root's child, an ordinary one
traces as on any thread with no span current), and records on the root:

- ``hop_in``: the loop submits -> ``fn`` starts on the pool thread (the
  pool's wake-up and the GIL);
- ``exec``: ``fn`` start -> ``fn`` end on the pool thread; on one root in
  ``CPU_TIMED_EVERY`` also the annotation ``exec_cpu_ms`` from
  ``time.thread_time()`` at the same two points: ``exec_ms -
  exec_cpu_ms`` is the time the thread held a request and did not run
  (GIL wait, locks, blocking IO). One in eight, because the thread's
  clock is a real syscall (6 us a read on the chip's host, PERF.md §6
  PR 37: two of them cost more than every other stamp of the request
  together), and every served RPC pays for its stamps under the GIL;
- ``hop_out``: ``fn`` end -> the coroutine runs again on the loop
  (``call_soon_threadsafe``, the selector's wake-up, the GIL, the loop's
  backlog).

With no open root (no served request, the kill switch, or a task that
outlived the request that spawned it) it IS the bare
``loop.run_in_executor``.
"""

from __future__ import annotations

import itertools
from time import perf_counter, thread_time

from .context import _root

CPU_TIMED_EVERY = 8  # roots; a root of several hops is timed whole or not
_tick = itertools.count()


class _Hop:
    """``fn(*args)`` as the pool thread runs it: between two readings of
    the clock, with the request's root carried across. An object with
    slots, not a closure: a third less to build for every served RPC."""

    __slots__ = ("root", "fn", "args", "timed", "t_start", "t_end", "cpu")

    def __init__(self, root, fn, args, timed: bool):
        self.root = root
        self.fn = fn
        self.args = args
        self.timed = timed
        self.t_end = 0.0

    def __call__(self):
        self.t_start = perf_counter()
        timed = self.timed
        if timed:
            cpu = thread_time()
        token = _root.set(self.root)
        try:
            return self.fn(*self.args)
        finally:
            _root.reset(token)
            if timed:
                self.cpu = thread_time() - cpu
            self.t_end = perf_counter()


async def run_in_executor(loop, executor, fn, *args):
    root = _root.get()
    phases = None if root is None else root.phases
    if phases is None:
        return await loop.run_in_executor(executor, fn, *args)
    ann = root.annotations
    hop = _Hop(root, fn, args,
               "exec_cpu_ms" in ann if "exec" in phases
               else next(_tick) % CPU_TIMED_EVERY == 0)
    t_submit = perf_counter()
    try:
        return await loop.run_in_executor(executor, hop)
    finally:
        t_end = hop.t_end
        if t_end:  # else cancelled before fn ended: the loop did not wait
            t_start = hop.t_start
            phases.extend((  # one extend for the three
                "hop_in", t_submit, t_start, "exec", t_start, t_end,
                "hop_out", t_end, perf_counter()))
            if hop.timed:  # a root of several hops sums them
                ann["exec_cpu_ms"] = ann.get("exec_cpu_ms", 0.0) \
                    + hop.cpu * 1000.0
