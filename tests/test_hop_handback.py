"""The executor hop's hand-back (``observability/hop.py``) and the write
RPC's ack (``ReplicatedDB.handle_write_request``).

A pool thread's result, or an exception, must resume the awaiting task in
the loop iteration that delivers it, and a cancelled wait must wake the
task at once and drop what comes later. A served ``write`` whose ack was
met at commit (RF 1) takes no trip through the loop after its executor
half; one whose ack is pending (mode 1) resumes through the same hand-back
when a follower acks, when the ack times out, or when a fence fails it.
"""

import asyncio
import dataclasses
import logging
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import pytest

from rocksplicator_tpu.observability import request_phases, run_in_executor
from rocksplicator_tpu.observability.context import _root
from rocksplicator_tpu.observability.hop import wait_future
from rocksplicator_tpu.replication import ReplicaRole
from rocksplicator_tpu.rpc import RpcClientPool
from rocksplicator_tpu.storage import WriteBatch
from rocksplicator_tpu.utils.stats import Stats

from test_replication import FAST, hosts, wait_until  # noqa: F401


class _Root:
    """What the hop reads of a served request's root."""

    def __init__(self):
        self.phases = []
        self.annotations = {}


class _MarkAfter:
    """An executor that runs each job on a thread of its own and then
    queues ``order.append("next")`` on the loop: the callback the loop
    gets right after whatever the job itself queued."""

    def __init__(self, loop, order):
        self.loop = loop
        self.order = order

    def submit(self, fn, *args):
        done = Future()

        def work():
            try:
                done.set_result(fn(*args))
            except BaseException as e:
                done.set_exception(e)
            self.loop.call_soon_threadsafe(self.order.append, "next")

        threading.Thread(target=work, daemon=True).start()
        return done


def _run(coro_fn, with_root=False):
    """``coro_fn(loop)`` on a fresh loop, under a request root or none."""
    async def main():
        if with_root:
            _root.set(_Root())
        return await coro_fn(asyncio.get_running_loop())

    return asyncio.run(main())


@pytest.fixture()
def pool():
    with ThreadPoolExecutor(2) as ex:
        yield ex


@pytest.fixture()
def loop_errors():
    """What reaches the loop's exception handler, or asyncio's log."""
    seen = []
    handler = logging.Handler()
    handler.emit = seen.append
    log = logging.getLogger("asyncio")
    log.addHandler(handler)
    yield seen
    log.removeHandler(handler)


# ---------------------------------------------------------------------------
# the hand-back
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("with_root", [False, True], ids=["no_root", "root"])
def test_result_and_exception_are_handed_back(pool, with_root):
    class Boom(Exception):
        pass

    def fail():
        raise Boom("from the pool")

    async def go(loop):
        got = await run_in_executor(loop, pool, lambda a, b: a + b, 2, 3)
        with pytest.raises(Boom, match="from the pool"):
            await run_in_executor(loop, pool, fail)
        # None is the loop's default pool, as for loop.run_in_executor
        default = await run_in_executor(loop, None, threading.get_ident)
        root = _root.get()
        return got, default, None if root is None else root.phases

    got, default, phases = _run(go, with_root)
    assert got == 5 and default != threading.get_ident()
    if with_root:  # three hops, three phases each: the failed one too
        assert phases[0::3] == ["hop_in", "exec", "hop_out"] * 3
    else:
        assert phases is None


@pytest.mark.parametrize("with_root", [False, True], ids=["no_root", "root"])
def test_task_resumes_in_the_iteration_that_delivers(with_root):
    """The pool thread's one callback wakes the task: a callback queued
    right after it runs after the task has resumed. (With
    ``wrap_future`` the task ran two iterations later, after it.)"""
    order = []

    async def go(loop):
        ex = _MarkAfter(loop, order)
        assert await run_in_executor(loop, ex, lambda: 7) == 7
        order.append("resumed")
        await asyncio.sleep(0.05)  # let "next" run
        return list(order)

    assert _run(go, with_root) == ["resumed", "next"]


def test_root_is_carried_and_phases_are_in_order(pool):
    def work():
        time.sleep(0.002)
        return request_phases()

    async def go(loop):
        root = _root.get()
        t0 = time.perf_counter()
        seen = await run_in_executor(loop, pool, work)
        return root, seen, t0, time.perf_counter()

    root, seen, t0, t1 = _run(go, with_root=True)
    assert seen is root.phases  # the pool thread found the root
    names, starts, ends = root.phases[0::3], root.phases[1::3], \
        root.phases[2::3]
    assert names == ["hop_in", "exec", "hop_out"]
    assert t0 <= starts[0] <= ends[0] == starts[1] <= ends[1] == starts[2] \
        <= ends[2] <= t1
    assert ends[1] - starts[1] >= 0.002


@pytest.mark.parametrize("late", ["result", "exception"])
def test_cancel_while_fn_runs_wakes_the_task_at_once(pool, loop_errors,
                                                     late):
    """The task sees ``CancelledError`` while ``fn`` still runs; what
    ``fn`` hands back later is dropped, with nothing logged."""
    release = threading.Event()
    ended = threading.Event()

    def work():
        release.wait(10)
        ended.set()
        if late == "exception":
            raise RuntimeError("nobody is waiting")
        return 1

    async def go(loop):
        loop.set_exception_handler(
            lambda _l, ctx: loop_errors.append(ctx))
        task = asyncio.ensure_future(run_in_executor(loop, pool, work))
        await asyncio.sleep(0.01)  # fn is running
        t0 = time.monotonic()
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        waited = time.monotonic() - t0
        still_running = not ended.is_set()
        release.set()
        await loop.run_in_executor(None, ended.wait, 10)
        await asyncio.sleep(0.05)  # the late delivery runs, and is dropped
        return waited, still_running, _root.get().phases

    waited, still_running, phases = _run(go, with_root=True)
    assert still_running and waited < 1.0
    assert phases == []  # fn had not ended: the loop did not wait for it
    assert loop_errors == []


def test_cancel_before_fn_starts_never_runs_it():
    ran = []
    busy = threading.Event()

    async def go(loop):
        with ThreadPoolExecutor(1) as one:
            one.submit(busy.wait, 10)  # the one worker is taken
            task = asyncio.ensure_future(
                run_in_executor(loop, one, ran.append, "ran"))
            await asyncio.sleep(0.01)
            task.cancel()
            with pytest.raises(asyncio.CancelledError):
                await task
            busy.set()
        return await run_in_executor(loop, None, lambda: "after")

    assert _run(go) == "after"
    assert ran == []


def test_wait_for_times_out_around_a_hop(pool):
    async def go(loop):
        t0 = time.monotonic()
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(
                run_in_executor(loop, pool, time.sleep, 0.5), 0.02)
        waited = time.monotonic() - t0
        # the task that timed out goes on hopping
        return waited, await run_in_executor(loop, pool, lambda: "next")

    waited, after = _run(go, with_root=True)
    assert waited < 0.4 and after == "next"


def test_wait_future_resumes_in_the_iteration_that_delivers():
    """A future resolved on another thread: the same hand-back; a wait
    that is cancelled leaves the future as it was."""
    order = []

    async def go(loop):
        fut = Future()

        def resolve():
            fut.set_result("acked")
            loop.call_soon_threadsafe(order.append, "next")

        threading.Timer(0.01, resolve).start()
        got = await wait_future(loop, fut)
        order.append("resumed")
        await asyncio.sleep(0.05)
        # cancelled while pending: the future stays pending, and a late
        # result is set (and dropped) with no error
        late = Future()
        task = asyncio.ensure_future(wait_future(loop, late))
        await asyncio.sleep(0.01)
        task.cancel()
        with pytest.raises(asyncio.CancelledError):
            await task
        pending = not late.done()
        late.set_result("late")
        await asyncio.sleep(0.01)
        return got, list(order), pending

    got, seen, pending = _run(go)
    assert got == "acked" and seen == ["resumed", "next"] and pending


# ---------------------------------------------------------------------------
# the write RPC's ack
# ---------------------------------------------------------------------------


def _acks():
    stats = Stats.get()
    return (stats.get_counter("write.ack.at_commit"),
            stats.get_counter("write.ack.awaited"))


def test_write_met_at_commit_takes_no_trip_after_its_executor_half(hosts):
    """RF 1: the task that commits returns its reply in the iteration that
    hands the executor half back; ``ack_wait`` is two clock readings."""
    host = hosts("l")
    _, rdb = host.add_db("seg00001", ReplicaRole.LEADER)
    raw = WriteBatch().put(b"k", b"v").encode()
    order = []
    before = _acks()

    async def go():
        loop = asyncio.get_running_loop()
        rdb._executor = _MarkAfter(loop, order)
        _root.set(_Root())
        reply = await rdb.handle_write_request(raw)
        order.append("returned")
        await asyncio.sleep(0.05)
        return reply, _root.get().phases

    reply, phases = host.replicator.ioloop.run_sync(go(), timeout=10)
    assert order == ["returned", "next"]
    assert reply == {"seq": 1, "acked": True, "epoch": rdb.epoch}
    names = phases[0::3]  # the pool thread's two first, the hop's at resume
    assert names == ["parse", "commit", "hop_in", "exec", "hop_out",
                     "ack_wait"]
    i = names.index("ack_wait")
    assert phases[3 * i + 2] - phases[3 * i + 1] < 1e-4
    at_commit, awaited = _acks()
    assert (at_commit - before[0], awaited - before[1]) == (1, 0)


@pytest.mark.parametrize("how", ["follower_acks", "expiry", "fence"])
def test_pending_ack_resumes_through_the_hand_back(hosts, how):
    """Mode 1: the served ``write`` waits for its ack, and resumes when a
    follower acks (the ack is posted on the loop), when the ack times out
    (the loop's expiry timer) or when a newer epoch fences the leader (on
    another thread); the reply says which."""
    flags = dataclasses.replace(
        FAST, ack_timeout_ms=300 if how == "expiry" else 10_000)
    leader = hosts("l", flags)
    _, lrdb = leader.add_db("seg00001", ReplicaRole.LEADER, mode=1)
    if how == "follower_acks":
        follower = hosts("f", flags)
        fdb, _ = follower.add_db("seg00001", ReplicaRole.FOLLOWER,
                                 upstream=leader.addr)
    raw = WriteBatch().put(b"k", b"v").encode()
    before = _acks()

    async def go():
        pool = RpcClientPool()
        call = asyncio.ensure_future(pool.call(
            "127.0.0.1", leader.replicator.port, "write",
            {"db_name": "seg00001", "raw_batch": raw}))
        if how == "fence":
            while lrdb.ack_window_depth == 0:
                await asyncio.sleep(0.005)
            await asyncio.to_thread(lrdb._reject_stale_epoch,
                                    lrdb.epoch + 1)
        try:
            return await call
        finally:
            await pool.close()

    t0 = time.monotonic()
    reply = leader.replicator.ioloop.run_sync(go(), timeout=20)
    took = time.monotonic() - t0
    assert reply["seq"] == 1
    assert reply["acked"] is (how == "follower_acks")
    if how == "follower_acks":
        assert wait_until(lambda: fdb.get(b"k") == b"v")
    if how == "expiry":
        assert 0.25 <= took < 5.0
    if how == "fence":
        assert lrdb.fenced and took < 5.0
    at_commit, awaited = _acks()
    assert (at_commit - before[0], awaited - before[1]) == (0, 1)
    assert wait_until(lambda: lrdb.ack_window_depth == 0)
