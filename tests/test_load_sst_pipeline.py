"""Pipelined SST bulk-ingest tests (ISSUE 3).

Covers the narrowed per-db admin lock (download/validate outside, ingest +
meta re-locked with staleness re-checks), the ingest admission gate, the
cross-shard BatchCompactor, the object-store zero-copy/link hazards, and
the get_objects failure contract. Everything here is tier-1-fast: tiny
SSTs, in-process admin nodes, no full bench run.
"""

import os
import struct
import threading
import time

import pytest

from rocksplicator_tpu.admin import AdminHandler
from rocksplicator_tpu.admin.ingest_pipeline import (
    BatchCompactor, default_sst_loading_concurrency)
from rocksplicator_tpu.replication import ReplicationFlags, Replicator
from rocksplicator_tpu.rpc import (IoLoop, RpcApplicationError, RpcClientPool,
                                   RpcServer)
from rocksplicator_tpu.storage import DB, OpType, WriteBatch
from rocksplicator_tpu.storage.sst import SSTWriter
from rocksplicator_tpu.testing import failpoints as fp
from rocksplicator_tpu.utils.objectstore import (LocalObjectStore,
                                                 ObjectStoreError)

pack64 = struct.Struct("<q").pack

FAST = ReplicationFlags(
    server_long_poll_ms=400, pull_error_delay_min_ms=50,
    pull_error_delay_max_ms=120,
)


class GatedStore(LocalObjectStore):
    """LocalObjectStore whose downloads park on an event — lets tests hold
    an ingest in its download stage (which must NOT hold the per-db admin
    lock) while racing other admin ops against it."""

    def __init__(self, root):
        super().__init__(root)
        self.release = threading.Event()
        self.started = threading.Semaphore(0)
        self.concurrent = 0
        self.max_concurrent = 0
        self._clock = threading.Lock()

    def get_object(self, key, local_path, direct_io=False):
        with self._clock:
            self.concurrent += 1
            self.max_concurrent = max(self.max_concurrent, self.concurrent)
        self.started.release()
        try:
            assert self.release.wait(timeout=30), "gated download never freed"
            return super().get_object(key, local_path, direct_io=direct_io)
        finally:
            with self._clock:
                self.concurrent -= 1


class Node:
    def __init__(self, tmp_path, name="node", **kw):
        self.replicator = Replicator(port=0, flags=FAST)
        self.handler = AdminHandler(
            str(tmp_path / name), self.replicator, **kw)
        self.server = RpcServer(port=0, ioloop=self.replicator.ioloop)
        self.server.add_handler(self.handler)
        self.server.start()
        self.ioloop = IoLoop.default()
        self.pool = RpcClientPool()

    def call(self, method, **args):
        return self.call_async(method, **args).result(30)

    def call_async(self, method, **args):
        """Issue the RPC on the ioloop; returns a concurrent future."""
        async def go():
            return await self.pool.call(
                "127.0.0.1", self.server.port, method, args, timeout=30)

        return self.ioloop.run_coro(go())

    def stop(self):
        self.ioloop.run_sync(self.pool.close())
        self.server.stop()
        self.handler.close()
        self.replicator.stop()


@pytest.fixture()
def node_factory(tmp_path):
    made = []

    def make(**kw):
        n = Node(tmp_path, name=f"node{len(made)}", **kw)
        made.append(n)
        return n

    yield make
    for n in made:
        n.stop()


def put_sst(store, prefix, items, tmp_path, name="bulk.tsst"):
    local = tmp_path / f"_mk_{prefix.replace('/', '_')}_{name}"
    w = SSTWriter(str(local))
    for k, v in items:
        w.add(k, 0, OpType.PUT, v)
    w.finish()
    store.put_object(str(local), f"{prefix}/{name}")
    os.remove(local)


# ---------------------------------------------------------------------------
# admission gate
# ---------------------------------------------------------------------------


def test_gate_default_is_cpu_derived(node_factory):
    n = node_factory()
    assert n.handler._ingest_gate.capacity == default_sst_loading_concurrency()
    assert n.handler._ingest_gate.capacity < 999
    assert default_sst_loading_concurrency() >= 4


def test_gate_trips_too_many_requests(node_factory, tmp_path):
    n = node_factory(max_sst_loading_concurrency=1)
    store = GatedStore(str(tmp_path / "bucket"))
    put_sst(store, "sst/a", [(b"a", b"1")], tmp_path)
    put_sst(store, "sst/b", [(b"b", b"2")], tmp_path)
    n.handler._store = lambda uri: store
    n.call("add_db", db_name="seg00001", role="LEADER")
    n.call("add_db", db_name="seg00002", role="LEADER")
    fut1 = n.call_async("add_s3_sst_files_to_db", db_name="seg00001",
                        s3_bucket="b", s3_path="sst/a")
    assert store.started.acquire(timeout=10)  # first holds the gate slot
    with pytest.raises(RpcApplicationError) as ei:
        n.call("add_s3_sst_files_to_db", db_name="seg00002",
               s3_bucket="b", s3_path="sst/b")
    assert ei.value.code == "TOO_MANY_REQUESTS"
    store.release.set()
    assert fut1.result(30)["ingested_files"] == 1
    # slot released: the rejected ingest now goes through
    r = n.call("add_s3_sst_files_to_db", db_name="seg00002",
               s3_bucket="b", s3_path="sst/b")
    assert r["ingested_files"] == 1


# ---------------------------------------------------------------------------
# lock narrowing: races that were impossible when the whole chain held the
# per-db admin lock
# ---------------------------------------------------------------------------


def test_concurrent_same_path_ingest_hits_idempotency_skip(
        node_factory, tmp_path):
    n = node_factory()
    store = GatedStore(str(tmp_path / "bucket"))
    put_sst(store, "sst/v1", [(b"a", b"1"), (b"b", b"2")], tmp_path)
    n.handler._store = lambda uri: store
    n.call("add_db", db_name="seg00001", role="LEADER")
    f1 = n.call_async("add_s3_sst_files_to_db", db_name="seg00001",
                      s3_bucket="bkt", s3_path="sst/v1")
    f2 = n.call_async("add_s3_sst_files_to_db", db_name="seg00001",
                      s3_bucket="bkt", s3_path="sst/v1")
    # both passed admission (meta was empty) and are parked in download
    assert store.started.acquire(timeout=10)
    assert store.started.acquire(timeout=10)
    store.release.set()
    results = [f1.result(30), f2.result(30)]
    # exactly one ingested; the other saw the meta staleness re-check and
    # skipped (admin_handler.cpp:1655-1667 idempotency, now also raced)
    assert sorted(r.get("skipped", False) for r in results) == [False, True]
    assert [r.get("ingested_files") for r in results].count(1) == 1
    app_db = n.handler.db_manager.get_db("seg00001")
    assert app_db.get(b"a") == b"1"


def test_ingest_racing_close_db_gets_db_not_found(node_factory, tmp_path):
    n = node_factory()
    store = GatedStore(str(tmp_path / "bucket"))
    put_sst(store, "sst/v1", [(b"a", b"1")], tmp_path)
    n.handler._store = lambda uri: store
    n.call("add_db", db_name="seg00001", role="LEADER")
    fut = n.call_async("add_s3_sst_files_to_db", db_name="seg00001",
                       s3_bucket="bkt", s3_path="sst/v1")
    assert store.started.acquire(timeout=10)
    # download holds NO admin lock now — closeDB must proceed immediately
    n.call("close_db", db_name="seg00001")
    store.release.set()
    with pytest.raises(RpcApplicationError) as ei:
        fut.result(30)
    assert ei.value.code == "DB_NOT_FOUND"


def test_pipelined_multi_shard_ingest(node_factory, tmp_path):
    """N shards ingested concurrently: downloads overlap (the lock
    narrowing at work) and every shard ends with exactly its own data."""
    shards = 4
    n = node_factory()
    store = GatedStore(str(tmp_path / "bucket"))
    store.release.set()  # no parking — just record concurrency
    for s in range(shards):
        put_sst(store, f"sst/{s:05d}",
                [(f"s{s}-k{i:03d}".encode(), pack64(s * 100 + i))
                 for i in range(50)],
                tmp_path)
    n.handler._store = lambda uri: store
    for s in range(shards):
        n.call("add_db", db_name=f"seg{s:05d}", role="LEADER")
    futs = [
        n.call_async("add_s3_sst_files_to_db", db_name=f"seg{s:05d}",
                     s3_bucket="bkt", s3_path=f"sst/{s:05d}",
                     compact_db_after_load=True)
        for s in range(shards)
    ]
    for f in futs:
        assert f.result(60)["ingested_files"] == 1
    for s in range(shards):
        app_db = n.handler.db_manager.get_db(f"seg{s:05d}")
        assert app_db.get(f"s{s}-k049".encode()) == pack64(s * 100 + 49)
        # no cross-shard bleed
        other = (s + 1) % shards
        assert app_db.get(f"s{other}-k000".encode()) is None
        assert n.handler.get_meta_data(f"seg{s:05d}").s3_path == f"sst/{s:05d}"


def test_close_racing_post_load_compact_is_benign(
        node_factory, tmp_path, monkeypatch):
    """Post-load compaction runs outside the admin lock; a closeDB that
    tears the db down mid-compact must NOT fail the RPC — the ingest and
    meta write already durably committed, and a closed db needs no
    compaction."""
    from rocksplicator_tpu.admin.ingest_pipeline import BatchCompactor
    from rocksplicator_tpu.storage.errors import StorageError

    n = node_factory()
    store = LocalObjectStore(str(tmp_path / "bucket"))
    put_sst(store, "sst/v1", [(b"a", b"1")], tmp_path)
    n.handler._store = lambda uri: store
    n.call("add_db", db_name="seg00001", role="LEADER")

    def torn_down_compact(self, db_name, db, ticket=None):
        # simulate the race outcome: close lands first, compact then
        # sees a closed engine
        n.handler.db_manager.remove_db(db_name)
        raise StorageError("db is closed")

    monkeypatch.setattr(BatchCompactor, "compact", torn_down_compact)
    r = n.call("add_s3_sst_files_to_db", db_name="seg00001",
               s3_bucket="bkt", s3_path="sst/v1",
               compact_db_after_load=True)
    assert r["ingested_files"] == 1  # ingest committed; no error surfaced


# ---------------------------------------------------------------------------
# batched post-load compaction
# ---------------------------------------------------------------------------


class StubDB:
    def __init__(self, log_list, name, block=None):
        self._log = log_list
        self._name = name
        self._block = block

    def compact_range(self):
        if self._block is not None:
            assert self._block.wait(timeout=30)
        self._log.append(self._name)


def test_batch_compactor_coalesces_concurrent_shards():
    compactor = BatchCompactor(use_tpu=False, compact_parallelism=2)
    try:
        done = []
        gate = threading.Event()
        sizes = {}

        def submit(name, db):
            sizes[name] = compactor.compact(name, db)

        # leader dispatches shard0 alone (its compact blocks on `gate`);
        # shards 1+2 queue up meanwhile and must ride ONE batch
        t0 = threading.Thread(
            target=submit, args=("db0", StubDB(done, "db0", block=gate)))
        t0.start()
        while compactor.dispatch_count == 0:
            time.sleep(0.01)
        ts = [
            threading.Thread(target=submit, args=(f"db{i}", StubDB(done, f"db{i}")))
            for i in (1, 2)
        ]
        for t in ts:
            t.start()
        while len(compactor._queue) < 2:
            time.sleep(0.01)
        gate.set()
        for t in [t0] + ts:
            t.join(30)
        assert sorted(done) == ["db0", "db1", "db2"]
        assert compactor.batch_sizes == [1, 2]
        assert sizes["db1"] == sizes["db2"] == 2
    finally:
        compactor.close()


def test_batch_compactor_propagates_per_db_errors():
    compactor = BatchCompactor(use_tpu=False, compact_parallelism=2)
    try:
        class Boom:
            def compact_range(self):
                raise RuntimeError("disk on fire")

        ok = []
        with pytest.raises(RuntimeError, match="disk on fire"):
            compactor.compact("bad", Boom())
        compactor.compact("good", StubDB(ok, "good"))
        assert ok == ["good"]
    finally:
        compactor.close()


# ---------------------------------------------------------------------------
# the leader's linger for the siblings the admin plane has admitted
# ---------------------------------------------------------------------------

LONG = 60.0  # a dispatch time no linger in this file may run out


def wait_until(pred, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.005)
    return pred()


def linger_counters():
    from rocksplicator_tpu.utils.stats import Stats

    return {k: Stats.get().get_counter("compact.linger." + k)
            for k in ("joined", "timeouts", "ms")}


def lingering(compactor):
    """The leader has been chosen and has not taken its batch yet."""
    return compactor._dispatching and compactor.dispatch_count == 0


class Callers:
    """Threads through ``BatchCompactor.compact``, each over a StubDB."""

    def __init__(self, compactor):
        self.compactor = compactor
        self.done, self.sizes, self.threads = [], {}, []

    def submit(self, name, ticket=None):
        def run():
            self.sizes[name] = self.compactor.compact(
                name, StubDB(self.done, name), ticket)

        t = threading.Thread(target=run)
        t.start()
        self.threads.append(t)

    def join(self):
        for t in self.threads:
            t.join(30)
            assert not t.is_alive()


@pytest.fixture()
def compactor():
    c = BatchCompactor(use_tpu=False, compact_parallelism=2)
    yield c
    c.close()


@pytest.mark.parametrize("n", [2, 5, 7])
def test_announced_callers_arriving_staggered_ride_one_dispatch(
        compactor, n):
    compactor._dispatch_s.append(LONG)
    tickets = [compactor.expect() for _ in range(n)]
    callers = Callers(compactor)
    t0 = time.monotonic()
    for i, ticket in enumerate(tickets):
        callers.submit(f"db{i}", ticket)
        time.sleep(0.02)  # an arrival spread, not a dispatch time
    callers.join()
    assert compactor.batch_sizes == [n]
    assert set(callers.sizes.values()) == {n}
    assert time.monotonic() - t0 < LONG / 4  # the last arrival ended it
    assert not compactor._expected
    c = linger_counters()
    assert c["joined"] == n - 1 and c["timeouts"] == 0 and c["ms"] > 0


@pytest.mark.parametrize("announced, observed", [
    (0, True),    # a direct caller, nobody announced: as before PR 30
    (0, False),
    (1, False),   # a sibling is on its way, but no dispatch was timed yet
])
def test_no_linger_without_an_announcement_or_an_estimate(
        compactor, announced, observed):
    if observed:
        compactor._dispatch_s.append(LONG)
    tickets = [compactor.expect() for _ in range(announced)]
    callers = Callers(compactor)
    t0 = time.monotonic()
    callers.submit("db0")
    callers.join()
    assert time.monotonic() - t0 < LONG / 4
    assert compactor.batch_sizes == [1]
    assert linger_counters() == {"joined": 0, "timeouts": 0, "ms": 0}
    # the dispatch itself was timed: the next leader has an estimate
    assert len(compactor._dispatch_s) == 1 + observed
    for ticket in tickets:
        compactor.retire(ticket)
    assert not compactor._expected


def test_straggler_past_the_bound_rides_the_next_batch(compactor):
    compactor._dispatch_s.append(0.05)  # the injected dispatch time
    on_time, late = compactor.expect(), compactor.expect()
    callers = Callers(compactor)
    callers.submit("db0", on_time)
    # the leader gives the straggler one dispatch time, then goes
    assert wait_until(lambda: compactor.dispatch_count == 1)
    assert compactor.batch_sizes == [1]
    c = linger_counters()
    assert c["timeouts"] == 1 and c["joined"] == 0 and c["ms"] >= 50.0
    callers.submit("db1", late)
    callers.join()
    assert compactor.batch_sizes == [1, 1] and callers.done == ["db0", "db1"]
    assert not compactor._expected
    assert linger_counters()["timeouts"] == 1


def test_leaked_announcement_costs_each_leader_the_bound_and_is_counted(
        compactor):
    """What a handler that forgot to retire would do: every later leader
    waits the full bound, and the timeouts counter says so."""
    compactor.expect()  # planted: never retired
    for i in range(3):
        compactor._dispatch_s.clear()
        compactor._dispatch_s.append(0.03)
        callers = Callers(compactor)
        callers.submit(f"db{i}")
        callers.join()
    assert compactor.batch_sizes == [1, 1, 1]
    c = linger_counters()
    assert c["timeouts"] == 3 and c["ms"] >= 3 * 30.0


def test_full_launch_group_does_not_wait_for_a_ninth(compactor):
    from rocksplicator_tpu.admin.ingest_pipeline import LAUNCH_GROUP

    compactor._dispatch_s.append(LONG)
    tickets = [compactor.expect() for _ in range(LAUNCH_GROUP + 1)]
    callers = Callers(compactor)
    t0 = time.monotonic()
    for i in range(LAUNCH_GROUP):
        callers.submit(f"db{i}", tickets[i])
        time.sleep(0.01)
    callers.join()
    assert time.monotonic() - t0 < LONG / 4
    assert compactor.batch_sizes == [LAUNCH_GROUP]
    assert len(compactor._expected) == 1  # the ninth, still on its way
    assert linger_counters()["timeouts"] == 0
    compactor.retire(tickets[-1])
    compactor.retire(tickets[-1])  # retiring twice retires once
    assert not compactor._expected


def test_retire_of_the_last_sibling_releases_the_leader(compactor):
    compactor._dispatch_s.append(LONG)
    mine, sibling = compactor.expect(), compactor.expect()
    callers = Callers(compactor)
    t0 = time.monotonic()
    callers.submit("db0", mine)
    assert wait_until(lambda: lingering(compactor))
    time.sleep(0.05)
    assert compactor.dispatch_count == 0  # held by the sibling alone
    compactor.retire(sibling)
    callers.join()
    assert time.monotonic() - t0 < LONG / 4
    assert compactor.batch_sizes == [1]
    assert linger_counters()["timeouts"] == 0


def test_linger_under_thread_churn_loses_no_caller_and_no_ticket(compactor):
    """More threads than cores, a short switch interval: every caller
    that announced either compacts or retires, in any interleaving; each
    shard is dispatched exactly once, nothing stays announced, and the
    leadership is handed back."""
    import random
    import sys

    workers, rounds = 24, 12
    compactor._dispatch_s.append(0.002)
    done, errors = [], []

    def churn(w):
        rng = random.Random(w)
        try:
            for r in range(rounds):
                ticket = compactor.expect() if rng.random() < 0.8 else None
                if rng.random() < 0.25:
                    compactor.retire(ticket)  # left before the compactor
                    compactor.retire(ticket)
                    continue
                compactor.compact(f"w{w}r{r}", StubDB(done, (w, r)), ticket)
        except BaseException as e:  # surfaced by the assert below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=churn, args=(w,))
                   for w in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert len(done) == len(set(done)) == sum(compactor.batch_sizes)
    assert not compactor._expected and not compactor._queue
    assert not compactor._dispatching


class PerBucketStores:
    """``handler._store`` by bucket name: ``gated`` parks its downloads,
    every other bucket reads the same directory ungated."""

    def __init__(self, root):
        self.gated = GatedStore(root)
        self.plain = LocalObjectStore(root)

    def __call__(self, uri):
        return self.gated if uri == "gated" else self.plain


def _die_at_failpoint(site):
    def arm(n):
        fp.activate(site, "fail_first:1")
    return arm


def _die_of_db_not_found(n):
    n.call("close_db", db_name="seg00002")


def _die_of_bad_sst(n):
    bad = n.stores.plain._path("sst/b/bulk.tsst")
    os.remove(bad)  # the download's hardlink would share the inode
    with open(bad, "wb") as f:
        f.write(b"not an sst")


def _leave_by_the_idempotent_skip(n):
    n.handler.write_meta_data("seg00002", "gated", "sst/b")


@pytest.mark.parametrize("how, code", [
    (_die_at_failpoint("admin.ingest.engine"), "INTERNAL"),
    (_die_at_failpoint("admin.ingest.meta"), "INTERNAL"),
    (_die_of_bad_sst, "DB_ADMIN_ERROR"),
    (_die_of_db_not_found, "DB_NOT_FOUND"),
    (_leave_by_the_idempotent_skip, None),
], ids=["fp_engine", "fp_meta", "bad_sst", "db_not_found", "skipped"])
def test_sibling_that_never_reaches_the_compactor_retires_its_announcement(
        node_factory, tmp_path, how, code):
    """Two admitted ingests; one leads the compactor and lingers for the
    other, which leaves its RPC before it enqueues: the leader goes at
    once, alone, and nothing stays announced."""
    n = node_factory()
    n.stores = PerBucketStores(str(tmp_path / "bucket"))
    put_sst(n.stores.plain, "sst/a", [(b"a", b"1")], tmp_path)
    put_sst(n.stores.plain, "sst/b", [(b"b", b"2")], tmp_path)
    n.handler._store = n.stores
    compactor = n.handler._batch_compactor
    compactor._dispatch_s.append(LONG)
    n.call("add_db", db_name="seg00001", role="LEADER")
    n.call("add_db", db_name="seg00002", role="LEADER")
    t0 = time.monotonic()
    doomed = n.call_async(
        "add_s3_sst_files_to_db", db_name="seg00002", s3_bucket="gated",
        s3_path="sst/b", compact_db_after_load=True)
    assert n.stores.gated.started.acquire(timeout=10)  # admitted: announced
    healthy = n.call_async(
        "add_s3_sst_files_to_db", db_name="seg00001", s3_bucket="plain",
        s3_path="sst/a", compact_db_after_load=True)
    assert wait_until(lambda: lingering(compactor))
    assert len(compactor._expected) == 1
    try:
        how(n)
        n.stores.gated.release.set()
        if code is None:
            assert doomed.result(30) == {"skipped": True}
        else:
            with pytest.raises(RpcApplicationError) as ei:
                doomed.result(30)
            assert ei.value.code == code
        assert healthy.result(30)["ingested_files"] == 1
    finally:
        fp.reset_for_test()
    assert time.monotonic() - t0 < LONG / 4
    assert compactor.batch_sizes == [1]
    assert not compactor._expected
    assert linger_counters()["timeouts"] == 0


def test_eight_concurrent_served_ingests_make_one_dispatch(
        node_factory, tmp_path):
    """Through the served RPC on the CPU backend: eight admitted ingests
    with compact_db_after_load are one dispatch, an ingest without it
    announces nothing."""
    shards = 8
    n = node_factory(max_sst_loading_concurrency=shards + 1,
                     executor_threads=shards + 1)
    store = GatedStore(str(tmp_path / "bucket"))
    for s in range(shards + 1):
        put_sst(store, f"sst/{s:05d}",
                [(f"s{s}-k{i:03d}".encode(), pack64(s * 100 + i))
                 for i in range(20)], tmp_path)
        n.call("add_db", db_name=f"seg{s:05d}", role="LEADER")
    n.handler._store = lambda uri: store
    compactor = n.handler._batch_compactor
    compactor._dispatch_s.append(LONG)
    plain = n.call_async(
        "add_s3_sst_files_to_db", db_name=f"seg{shards:05d}",
        s3_bucket="bkt", s3_path=f"sst/{shards:05d}")
    assert store.started.acquire(timeout=10)
    assert not compactor._expected
    futs = [
        n.call_async("add_s3_sst_files_to_db", db_name=f"seg{s:05d}",
                     s3_bucket="bkt", s3_path=f"sst/{s:05d}",
                     compact_db_after_load=True)
        for s in range(shards)
    ]
    for _ in range(shards):
        assert store.started.acquire(timeout=10)
    assert len(compactor._expected) == shards  # all admitted, none queued
    store.release.set()
    for f in futs + [plain]:
        assert f.result(60)["ingested_files"] == 1
    assert compactor.batch_sizes == [shards]
    assert not compactor._expected
    c = linger_counters()
    assert c["joined"] == shards - 1 and c["timeouts"] == 0
    for s in range(shards):
        app_db = n.handler.db_manager.get_db(f"seg{s:05d}")
        assert app_db.get(f"s{s}-k019".encode()) == pack64(s * 100 + 19)


def test_compact_dbs_batched_tpu_parity(tmp_path):
    """The one-padded-device-call path produces the same post-compaction
    state as per-db compact_range: overlapping preload writes resolved
    against ingested data, tombstones dropped."""
    from rocksplicator_tpu.tpu.compaction_service import compact_dbs_batched

    dbs = []
    for s in range(2):
        db = DB(str(tmp_path / f"db{s}"))
        for i in range(30):
            db.write(WriteBatch().put(f"k{i:03d}".encode(), pack64(-1)))
        db.write(WriteBatch().delete(b"k000"))
        sst = tmp_path / f"in{s}.tsst"
        w = SSTWriter(str(sst))
        for i in range(10, 40):
            w.add(f"k{i:03d}".encode(), 0, OpType.PUT, pack64(s * 1000 + i))
        w.finish()
        db.ingest_external_file([str(sst)], move_files=True,
                                allow_global_seqno=True)
        dbs.append((f"db{s}", db))
    handled, remaining = compact_dbs_batched(dbs)
    assert sorted(handled) == ["db0", "db1"] and remaining == []
    for s, (_name, db) in enumerate(dbs):
        assert db.get(b"k000") is None              # tombstone dropped
        assert db.get(b"k005") == pack64(-1)        # preload-only key kept
        assert db.get(b"k015") == pack64(s * 1000 + 15)  # SST (newer) wins
        assert db.get(b"k039") == pack64(s * 1000 + 39)
        # fully compacted: everything in one bottom-level run
        levels = db._levels
        assert all(not files for files in levels[:-1])
        db.close()


def test_compact_dbs_batched_declines_unsupported(tmp_path):
    """A DB the lane format can't express (>48B keys; >24B until keys of
    up to 48 bytes took twelve key words) is declined UNTOUCHED (plan
    aborted, compact_range still works on it)."""
    from rocksplicator_tpu.tpu.compaction_service import compact_dbs_batched

    db = DB(str(tmp_path / "wide"))
    db.write(WriteBatch().put(b"k" * 49, b"v"))
    db.flush()
    handled, remaining = compact_dbs_batched([("wide", db)])
    assert handled == [] and [n for n, _ in remaining] == ["wide"]
    db.compact_range()  # mutex was released by the abort
    assert db.get(b"k" * 49) == b"v"
    db.close()


# ---------------------------------------------------------------------------
# object store: failure contract + zero-copy fast path
# ---------------------------------------------------------------------------


def test_get_objects_propagates_failing_key_and_cleans_partials(tmp_path):
    store = LocalObjectStore(str(tmp_path / "bucket"))
    for i in range(4):
        store.put_object_bytes(f"batch/f{i}.bin", b"x" * 128)

    real = LocalObjectStore.get_object

    def flaky(self, key, local_path, direct_io=False):
        if key.endswith("f2.bin"):
            raise ObjectStoreError("injected transport error")
        return real(self, key, local_path, direct_io=direct_io)

    store.get_object = flaky.__get__(store)
    dest = tmp_path / "dl"
    with pytest.raises(ObjectStoreError) as ei:
        store.get_objects("batch", str(dest))
    assert "f2.bin" in str(ei.value)  # the failing KEY is named
    # all-or-nothing: no partial batch left behind
    assert list(dest.iterdir()) == []


def test_local_get_object_zero_copy_link(tmp_path):
    store = LocalObjectStore(str(tmp_path / "bucket"))
    store.put_object_bytes("a/obj.bin", b"payload")
    sink = tmp_path / "dl" / "obj.bin"
    store.get_object("a/obj.bin", str(sink))
    assert sink.read_bytes() == b"payload"
    src_ino = os.stat(tmp_path / "bucket" / "a" / "obj.bin").st_ino
    assert os.stat(sink).st_ino == src_ino  # hardlink, not a copy
    # refetch over an existing sink still works
    store.get_object("a/obj.bin", str(sink))
    assert sink.read_bytes() == b"payload"


def test_ingest_breaks_hardlink_before_footer_rewrite(tmp_path):
    """The global-seqno footer rewrite must never write through a
    download hardlink into the bucket object."""
    store = LocalObjectStore(str(tmp_path / "bucket"))
    sst = tmp_path / "mk.tsst"
    w = SSTWriter(str(sst))
    w.add(b"k", 0, OpType.PUT, b"v")
    w.finish()
    store.put_object(str(sst), "sst/bulk.tsst")
    bucket_file = tmp_path / "bucket" / "sst" / "bulk.tsst"
    original = bucket_file.read_bytes()

    local = store.get_objects("sst", str(tmp_path / "dl"))
    assert os.stat(local[0]).st_nlink > 1  # zero-copy download happened
    db = DB(str(tmp_path / "db"))
    db.ingest_external_file(local, move_files=True, allow_global_seqno=True)
    assert db.get(b"k") == b"v"
    db.close()
    assert bucket_file.read_bytes() == original  # bucket never mutated


# ---------------------------------------------------------------------------
# bench-path smoke (tier-1-safe: tiny config, cpu backend, in-process)
# ---------------------------------------------------------------------------


def test_load_sst_bench_pipeline_smoke(tmp_path):
    from benchmarks.load_sst_bench import build_sst_sets, run_load

    store_uri = str(tmp_path / "bucket")
    store = LocalObjectStore(store_uri)
    total = build_sst_sets(store, 3, 200, str(tmp_path))
    assert total > 0
    run = run_load({}, store_uri, 3, 200, 0.2, "cpu",
                   str(tmp_path / "dbs"), window=2)
    assert run["spot_check_failures"] == 0
    assert run["phase_ms"].get("admin.add_s3_sst", {}).get("count") == 3
    assert run["slowest_shard_trace"] is not None
    assert sum(run["compact_batch_sizes"]) == 3
