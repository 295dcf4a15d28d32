"""Bulk-ingest pipeline plumbing: admission gate + cross-shard batched
post-load compaction.

The pipelined load_sst path (ISSUE 3) is three bounded stages:

- **download/validate** — outside the per-db admin lock, globally bounded
  by :class:`IngestGate` (the reference's
  ``num_current_s3_sst_downloadings_`` TOO_MANY_REQUESTS gate,
  admin_handler.cpp:1692-1706) so shard k+1's object-store fetch overlaps
  shard k's engine ingest;
- **ingest + meta** — back under the per-db admin lock with a staleness
  re-check (the lock-narrowing half; see admin/handler.py);
- **post-load compact** — :class:`BatchCompactor`: concurrent shards'
  compactions coalesce AckWindow/group-commit style; one submitter
  becomes the dispatch leader, waits (at most one dispatch time) for the
  ingests the handler has already admitted, and dispatches the queue as
  a batch (one padded device launch on the TPU backend via
  tpu.compaction_service.compact_dbs_batched; thread-pool fan-out on
  CPU), every submitter just waits on its shard's future.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Deque, Dict, List, Optional, Set, Tuple

from ..observability.span import start_span
from ..testing import failpoints as fp
from ..utils.stats import Stats

log = logging.getLogger(__name__)


def default_sst_loading_concurrency() -> int:
    """CPU-derived default for the ingest admission gate. The reference
    gflag defaulted to 999 — dead code as a gate; download+validate is
    IO-plus-checksum work, so ~2 slots per core keeps the pipeline full
    without letting an ingest storm starve serving threads."""
    return max(4, 2 * (os.cpu_count() or 2))


class IngestGate:
    """Counting admission gate for in-flight SST loads. ``try_enter``
    never blocks — over-capacity callers are REJECTED (the handler maps
    that to TOO_MANY_REQUESTS, matching the reference's behavior of
    telling the orchestrator to back off rather than queueing)."""

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._free = threading.Condition(self._lock)
        self._in_flight = 0
        self._waiting = 0

    @property
    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight

    def try_enter(self) -> bool:
        with self._lock:
            if self._in_flight >= self.capacity:
                return False
            self._in_flight += 1
            return True

    def enter_wait(self, timeout: float, max_waiting: int = 2) -> bool:
        """Blocking admission for callers that should QUEUE rather than
        bounce: snapshot-restore downloads in a live shard move (a
        drain-node moving N shards pipelines its bulk transfers through
        this gate, exactly like the SST-load path, instead of saturating
        the NIC/disk N-wide). Returns False when no slot freed within
        ``timeout`` — or IMMEDIATELY when ``max_waiting`` callers are
        already parked: each waiter occupies a shared admin-executor
        thread, and an unbounded queue of 10-minute waits would starve
        every other admin RPC on the host (the PR-9 WRITE_WINDOW_FULL
        fail-fast lesson). The SST-load RPC keeps try_enter's
        reject-don't-queue contract."""
        deadline = time.monotonic() + timeout
        with self._free:
            if self._in_flight >= self.capacity \
                    and self._waiting >= max_waiting:
                return False
            self._waiting += 1
            try:
                while self._in_flight >= self.capacity:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not self._free.wait(remaining):
                        if self._in_flight < self.capacity:
                            break
                        return False
                self._in_flight += 1
                return True
            finally:
                self._waiting -= 1

    def exit(self) -> None:
        with self._lock:
            self._in_flight -= 1
            self._free.notify()


# The device launch's shard places (compact_dbs_batched's ``group_size``):
# a queue that fills whole launches has nobody left worth waiting for.
LAUNCH_GROUP = 8


class _Taken:
    """A queued shard's signal that a dispatch has taken it (set by the
    leader, with the batch's size), and when it was queued."""

    __slots__ = ("event", "batch", "since")

    def __init__(self):
        self.event = threading.Event()
        self.batch = 0
        self.since = time.monotonic()


class BatchCompactor:
    """Group-commit for post-load compactions.

    ``compact(db_name, db)`` blocks until the shard's full compaction is
    done, but concurrent callers are BATCHED: the first submitter into an
    idle compactor becomes the leader and dispatches what is queued,
    batch after batch, until the queue is empty (shards that arrive
    while a batch runs form the next batch — the same natural coalescing
    as WAL group commit).

    The leader does not launch alone while siblings are on their way.
    The handler announces an ingest that will end in ``compact`` as soon
    as it is admitted (``expect``); before each batch the leader lingers
    while announced callers have not queued yet, and goes when none is
    left, when the queue fills whole launches (``LAUNCH_GROUP``), or
    when the oldest queued shard has waited as long as a dispatch lately
    takes: a sibling that misses the batch waits one dispatch, so a
    longer wait for it cannot pay. That bound is the mean of this
    compactor's own recent dispatch times; with none observed, or
    nothing announced, there is no linger.

    Dispatch goes through the configured backend: one padded device
    launch per batch when ``use_tpu`` (compact_dbs_batched), thread-pool
    fan-out of per-db ``compact_range`` otherwise (and for shards the
    lane representation declines).
    """

    def __init__(self, use_tpu: bool = False,
                 compact_parallelism: Optional[int] = None,
                 max_batch: int = 64):
        self._use_tpu = use_tpu
        self._max_batch = max_batch
        self._lock = threading.Lock()
        # the leader's linger: a caller queued, an announcement retired
        self._arrival = threading.Condition(self._lock)
        self._queue: List[Tuple[str, object, Future, "_Taken"]] = []
        self._expected: Set[object] = set()  # announced, not yet queued
        self._dispatching = False
        # wall seconds of the latest dispatches (the leader's alone to
        # write and read): their mean bounds a linger
        self._dispatch_s: Deque[float] = deque(maxlen=8)
        # compaction releases the GIL in its numpy/zlib/fsync phases, so
        # more workers than cores still overlaps usefully
        self._pool = ThreadPoolExecutor(
            max_workers=compact_parallelism or max(4, os.cpu_count() or 2),
            thread_name_prefix="post-load-compact",
        )
        # observability: batches dispatched and their sizes (tests + the
        # bench's "did the batching actually batch" assertion)
        self.dispatch_count = 0
        self.batch_sizes: List[int] = []

    def expect(self) -> object:
        """Announce a caller that will reach ``compact``: the ticket to
        hand it. Whoever announces retires the ticket exactly once:
        ``compact`` does as it queues the shard, ``retire`` when the
        caller leaves any other way."""
        ticket = object()
        with self._lock:
            self._expected.add(ticket)
        return ticket

    def retire(self, ticket: Optional[object]) -> None:
        """The announced caller is not coming (or has queued already:
        then this does nothing)."""
        with self._arrival:
            if ticket in self._expected:
                self._expected.remove(ticket)
                self._arrival.notify()  # the leader, in its linger

    def compact(self, db_name: str, db,
                ticket: Optional[object] = None) -> int:
        """Compact ``db`` (a storage.engine.DB), batched with concurrent
        callers. ``ticket``: this caller's ``expect()``. Returns the
        size of the batch this shard rode in."""
        fut: Future = Future()
        taken = _Taken()
        leader, batch = False, None
        try:
            # enqueue → the start of the dispatch that takes this shard
            # (a leader's linger for its siblings included)
            with start_span("admin.compact.wait") as wsp:
                with self._arrival:
                    self._expected.discard(ticket)
                    self._queue.append((db_name, db, fut, taken))
                    leader = not self._dispatching
                    if leader:
                        self._dispatching = True
                    else:
                        self._arrival.notify()
                if leader:
                    # the queue was empty: this shard heads the batch
                    batch = self._take_batch()
                else:
                    taken.event.wait()
                wsp.annotate(batch=taken.batch)
            if not leader:
                # a rider: the leader's dispatch, whose trace holds the
                # phases, compacts this shard too
                with start_span("admin.compact.ride"):
                    return fut.result()
            while batch:
                t0 = time.monotonic()
                try:
                    self._dispatch(batch)
                except BaseException as e:
                    # a dispatch blow-up (e.g. pool shutdown mid-close)
                    # must fail ITS batch loudly and keep draining —
                    # never strand waiters or the leadership flag
                    log.exception("compact dispatch failed")
                    for _n, _d, f in batch:
                        if not f.done():
                            f.set_exception(e)
                self._dispatch_s.append(time.monotonic() - t0)
                batch = self._take_batch()
        except BaseException:
            # pathological (queue handling itself raised): hand
            # leadership back so the compactor is not wedged forever
            if leader:
                with self._lock:
                    self._dispatching = False
            raise
        return fut.result()

    def _take_batch(self) -> List[Tuple[str, object, Future]]:
        """The leader's next batch off the queue (after the linger for
        announced siblings), its callers told that their wait is over;
        empty hands leadership back."""
        self._linger()
        with self._lock:
            entries = self._queue[: self._max_batch]
            del self._queue[: self._max_batch]
            if not entries:
                self._dispatching = False
        for _n, _d, _f, taken in entries:
            taken.batch = len(entries)
            taken.event.set()
        return [(n, d, f) for n, d, f, _t in entries]

    def _linger_left(self) -> Optional[float]:
        """Seconds the leader may still wait for announced siblings
        (``_lock`` held); None when there is nobody to wait for."""
        if not (self._queue and self._expected and self._dispatch_s) \
                or len(self._queue) % LAUNCH_GROUP == 0:
            return None
        bound = sum(self._dispatch_s) / len(self._dispatch_s)
        return self._queue[0][3].since + bound - time.monotonic()

    def _linger(self) -> None:
        with self._lock:
            left = self._linger_left()
            if left is None or left <= 0:
                # a batch that formed while a dispatch ran has waited
                # that long already
                return
            expected, had = len(self._expected), len(self._queue)
        t0 = time.monotonic()
        with start_span("admin.compact.linger", expected=expected) as sp:
            with self._arrival:
                while left is not None and left > 0:
                    self._arrival.wait(left)
                    left = self._linger_left()
                joined = len(self._queue) - had
            # the bound ran out with siblings still on their way
            timed_out = left is not None
            sp.annotate(joined=joined, timed_out=timed_out)
        stats = Stats.get()
        stats.incr("compact.linger.joined", joined)
        stats.incr("compact.linger.timeouts", int(timed_out))
        stats.incr("compact.linger.ms", (time.monotonic() - t0) * 1000.0)

    # -- dispatch ---------------------------------------------------------

    def _dispatch(self, batch: List[Tuple[str, object, Future]]) -> None:
        with start_span("admin.compact_dispatch", always=True,
                        shards=len(batch), tpu=self._use_tpu):
            self._dispatch_spanned(batch)

    def _dispatch_spanned(self, batch: List[Tuple[str, object, Future]]) -> None:
        fp.hit("compact.dispatch")  # a raise must fail the batch loudly,
        # release every waiter, and keep the leader loop draining
        self.dispatch_count += 1
        self.batch_sizes.append(len(batch))
        # Deduplicate by DB identity: the same db can legally ride one
        # batch twice (back-to-back ingests), one full compaction
        # satisfies every waiter — and a duplicate would deadlock the
        # batched plan stage on the db's compaction mutex.
        futures: Dict[int, List[Future]] = {}
        by_db: Dict[int, Tuple[str, object]] = {}
        for name, db, fut in batch:
            futures.setdefault(id(db), []).append(fut)
            by_db.setdefault(id(db), (name, db))
        remaining = list(by_db.values())

        def resolve(db, result=None, exc=None) -> None:
            for fut in futures[id(db)]:
                if fut.done():
                    continue
                if exc is not None:
                    fut.set_exception(exc)
                else:
                    fut.set_result(result)

        if self._use_tpu:
            from ..storage.compaction import record_host_fallback
            from ..tpu.compaction_service import compact_dbs_batched

            try:
                # host stages (plan/lane-read, SST write/install) fan out
                # over this pool; only the device launch is centralized
                handled, remaining = compact_dbs_batched(
                    remaining, group_size=LAUNCH_GROUP, pool=self._pool)
            except Exception:  # launch machinery itself blew up
                record_host_fallback(
                    "batched_dispatch",
                    f"{len(by_db)} shards; re-compacting per-db",
                    exc_info=True)
                remaining = list(by_db.values())
            # everything not handed back for per-db fallback was compacted
            rem_ids = {id(db) for _n, db in remaining}
            for _name, db in by_db.values():
                if id(db) not in rem_ids:
                    resolve(db, result=len(batch))
        # per-db fan-out: CPU backends, declined shards, single shards.
        # DBs running the adaptive compaction scheduler take its manual
        # queue (DB.schedule_compaction) so the post-ingest compaction
        # obeys the same PRIORITY order as background picks — an
        # L0-storm drain outranks it; schedule_compaction returns None
        # for engines without an adaptive compaction thread (inline
        # mode, scheduler off), which keep the direct compact_range.
        def one(name: str, db) -> None:
            try:
                fut = None
                submit = getattr(db, "schedule_compaction", None)
                if submit is not None:
                    fut = submit()
                if fut is not None:
                    fut.result()
                else:
                    db.compact_range()
                resolve(db, result=len(batch))
            except BaseException as e:
                resolve(db, exc=e)

        waits = [self._pool.submit(one, name, db) for name, db in remaining]
        for w in waits:
            w.result()

    def close(self) -> None:
        self._pool.shutdown(wait=False)
