"""The executor hop of a served request: one hand-back, and its phases.

:func:`run_in_executor` is the one way a request handler hops to a pool
(``ReplicatedDB.handle_read_request`` / ``handle_write_request``,
``AdminHandler._run``). It stands in for ``loop.run_in_executor``, whose
``wrap_future`` wakes the waiting task two loop iterations after the pool
thread ends: the concurrent future's callback queues ``_set_state`` with
``call_soon_threadsafe``, that iteration sets the asyncio future's result,
which queues the task's wake-up with ``call_soon``, and the task runs in
the iteration after. Each iteration passes through ``select()``, which
drops the GIL, and a loop that has to take the GIL back from busy pool
threads waits milliseconds for it (PERF.md §5). Here the pool thread ends
``fn`` and queues ONE callback, which wakes the task in the iteration that
runs it (:class:`HandBack`). :func:`wait_future` is the same hand-back for
a ``concurrent.futures.Future`` that some other thread resolves.

The hop also carries the request's root to the pool thread for the length
of ``fn`` (on its own contextvar, context.py ``_root``: ``phase(...)``
works there, an ``always=True`` span opened there is the root's child, an
ordinary one traces as on any thread with no span current), and records
on the root:

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
- ``hop_out``: ``fn`` end -> the coroutine runs again on the loop (the
  selector's wake-up, the GIL, the loop's backlog).

With no open root (no served request, the kill switch, or a task that
outlived the request that spawned it) the hop takes the same hand-back
and stamps nothing.
"""

from __future__ import annotations

import itertools
from asyncio import CancelledError, InvalidStateError
from concurrent.futures import ThreadPoolExecutor
from contextvars import copy_context
from time import perf_counter, thread_time

from .context import _root

CPU_TIMED_EVERY = 8  # roots; a root of several hops is timed whole or not
_tick = itertools.count()

_PENDING, _FINISHED, _CANCELLED = "PENDING", "FINISHED", "CANCELLED"


class HandBack:
    """A result that another thread hands back to a task on ``loop``.

    What a ``Task`` needs of the object it awaits (``asyncio.isfuture``:
    ``_asyncio_future_blocking``, ``get_loop``, ``add_done_callback``,
    ``remove_done_callback``, ``cancel``, ``cancelled``, ``done``,
    ``result``, ``exception``), and nothing of ``asyncio.Future``, whose
    ``set_result`` queues the task's wake-up for the NEXT iteration. The
    other thread stores the result and queues :meth:`_deliver` once
    (:meth:`_post`); ``_deliver`` runs the waiting task's wake-up itself,
    in the iteration that delivers the result. State changes on the loop's
    thread only. ``cancel`` wakes the task with ``CancelledError`` at
    once, through ``call_soon`` as ``asyncio.Future`` does (it is called
    from inside another task's step), and a later delivery drops the
    result."""

    __slots__ = ("_loop", "_state", "_result", "_exc", "_callbacks",
                 "_cancel_msg", "_asyncio_future_blocking")

    def __init__(self, loop):
        self._loop = loop
        self._state = _PENDING
        self._result = None
        self._exc = None
        self._callbacks = []
        self._cancel_msg = None
        self._asyncio_future_blocking = False

    # -- the other thread -------------------------------------------------

    def _post(self) -> None:
        try:
            self._loop.call_soon_threadsafe(self._deliver)
        except RuntimeError:  # the loop is closed: nobody waits
            pass

    def _settle_from(self, future) -> None:
        """``future``'s done-callback, on the thread that resolved it."""
        try:
            self._result = future.result()
        except BaseException as e:  # the awaiting task raises it
            self._exc = e
        self._post()

    # -- the loop's thread ------------------------------------------------

    def _deliver(self) -> None:
        if self._state is not _PENDING:  # cancelled: the result is dropped
            self._result = self._exc = None
            return
        self._state = _FINISHED
        callbacks, self._callbacks = self._callbacks, []
        for fn, ctx in callbacks:  # the task's wake-up, in its context
            ctx.run(fn, self)

    def get_loop(self):
        return self._loop

    def done(self) -> bool:
        return self._state is not _PENDING

    def cancelled(self) -> bool:
        return self._state is _CANCELLED

    def cancel(self, msg=None) -> bool:
        if self._state is not _PENDING:
            return False
        self._state = _CANCELLED
        self._cancel_msg = msg
        callbacks, self._callbacks = self._callbacks, []
        for fn, ctx in callbacks:
            self._loop.call_soon(fn, self, context=ctx)
        return True

    def result(self):
        if self._state is _CANCELLED:
            raise CancelledError(*(() if self._cancel_msg is None
                                   else (self._cancel_msg,)))
        if self._state is _PENDING:
            raise InvalidStateError("Result is not ready.")
        if self._exc is not None:
            raise self._exc
        return self._result

    def exception(self):
        if self._state is _CANCELLED or self._state is _PENDING:
            self.result()  # raises
        return self._exc

    def add_done_callback(self, fn, *, context=None) -> None:
        if context is None:
            context = copy_context()
        if self._state is _PENDING:
            self._callbacks.append((fn, context))
        else:
            self._loop.call_soon(fn, self, context=context)

    def remove_done_callback(self, fn) -> int:
        kept = [(f, c) for f, c in self._callbacks if f != fn]
        removed = len(self._callbacks) - len(kept)
        self._callbacks = kept
        return removed

    def __await__(self):
        if self._state is _PENDING:
            self._asyncio_future_blocking = True
            yield self
        return self.result()


def wait_future(loop, future) -> HandBack:
    """Await ``future`` (a ``concurrent.futures.Future``, resolved on any
    thread) with one hand-back: its done-callback queues the delivery.
    Cancelling the wait leaves ``future`` as it is."""
    hand_back = HandBack(loop)
    future.add_done_callback(hand_back._settle_from)
    return hand_back


class _Hop(HandBack):
    """``fn(*args)`` as the pool thread runs it (between two readings of
    the clock, with the request's root carried across), and the hand-back
    the request's task awaits. An object with slots, not a closure: less
    to build for every served RPC."""

    __slots__ = ("root", "fn", "args", "timed", "t_start", "t_end", "cpu",
                 "work")

    def __init__(self, loop, root, fn, args, timed: bool):
        HandBack.__init__(self, loop)
        self.root = root
        self.fn = fn
        self.args = args
        self.timed = timed
        self.t_end = 0.0
        self.work = None

    def __call__(self) -> None:
        self.t_start = perf_counter()
        timed = self.timed
        if timed:
            cpu = thread_time()
        token = _root.set(self.root)
        try:
            self._result = self.fn(*self.args)
        except BaseException as e:  # the awaiting task raises it
            self._exc = e
        finally:
            _root.reset(token)
            if timed:
                self.cpu = thread_time() - cpu
            self.t_end = perf_counter()
        self._post()

    def cancel(self, msg=None) -> bool:
        if not HandBack.cancel(self, msg):
            return False
        self.work.cancel()  # not started yet: ``fn`` never runs
        return True


def _pool(loop, executor):
    """``executor``, or the loop's default pool where it is None (as
    ``loop.run_in_executor(None, ...)`` makes it, so that the loop's
    ``shutdown_default_executor`` joins it)."""
    if executor is not None:
        return executor
    pool = loop._default_executor
    if pool is None:
        pool = loop._default_executor = ThreadPoolExecutor(
            thread_name_prefix="asyncio")
    return pool


async def run_in_executor(loop, executor, fn, *args):
    """``fn(*args)`` on ``executor`` (the loop's default pool where None);
    its result or exception is handed back to the awaiting task in the
    loop iteration that receives it."""
    root = _root.get()
    phases = None if root is None else root.phases
    if phases is None:
        hop = _Hop(loop, None, fn, args, False)
        hop.work = _pool(loop, executor).submit(hop)
        return await hop
    ann = root.annotations
    hop = _Hop(loop, root, fn, args,
               "exec_cpu_ms" in ann if "exec" in phases
               else next(_tick) % CPU_TIMED_EVERY == 0)
    t_submit = perf_counter()
    hop.work = _pool(loop, executor).submit(hop)
    try:
        return await hop
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
