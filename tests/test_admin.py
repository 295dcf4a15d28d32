"""Admin plane integration tests.

Reference: rocksdb_admin/tests/admin_handler_test.cpp — AdminHandlerTestBase
spins a real AdminHandler + server + client pool per test with a temp
rocksdb_dir. Same here, plus CDC observer coverage (cdc_admin/tests).
"""

import json
import struct
import time

import pytest

from rocksplicator_tpu.admin import (
    AdminHandler,
    ApplicationDBManager,
    CdcAdminHandler,
)
from rocksplicator_tpu.admin.backup_manager import ApplicationDBBackupManager
from rocksplicator_tpu.admin.cdc import MemoryPublisher
from rocksplicator_tpu.replication import ReplicationFlags, Replicator
from rocksplicator_tpu.rpc import IoLoop, RpcApplicationError, RpcClientPool, RpcServer
from rocksplicator_tpu.storage import DBOptions, OpType, WriteBatch
from rocksplicator_tpu.storage.records import decode_batch
from rocksplicator_tpu.storage.sst import SSTWriter
from rocksplicator_tpu.utils.objectstore import LocalObjectStore

FAST = ReplicationFlags(
    server_long_poll_ms=400, pull_error_delay_min_ms=50,
    pull_error_delay_max_ms=120,
)


class AdminNode:
    """One admin node: replicator + admin handler + admin RPC server."""

    def __init__(self, tmp_path, name, options_generator=None):
        self.replicator = Replicator(port=0, flags=FAST)
        self.handler = AdminHandler(
            str(tmp_path / name), self.replicator,
            options_generator=options_generator,
        )
        self.server = RpcServer(port=0, ioloop=self.replicator.ioloop)
        self.server.add_handler(self.handler)
        self.server.start()

    @property
    def admin_port(self):
        return self.server.port

    @property
    def repl_addr(self):
        return ("127.0.0.1", self.replicator.port)

    def stop(self):
        self.server.stop()
        self.handler.close()
        self.replicator.stop()


@pytest.fixture()
def nodes(tmp_path):
    created = []

    def make(name, **kw):
        n = AdminNode(tmp_path, name, **kw)
        created.append(n)
        return n

    yield make
    for n in created:
        n.stop()


@pytest.fixture()
def call():
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def do(node, method, **args):
        async def go():
            return await pool.call("127.0.0.1", node.admin_port, method, args,
                                   timeout=30)

        return ioloop.run_sync(go())

    yield do
    ioloop.run_sync(pool.close())


def wait_until(pred, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# ---------------------------------------------------------------------------


def test_ping_and_not_found(nodes, call):
    n = nodes("a")
    assert call(n, "ping")["ok"] is True
    with pytest.raises(RpcApplicationError) as ei:
        call(n, "get_sequence_number", db_name="nope")
    assert ei.value.code == "DB_NOT_FOUND"


def test_set_tenant_quota_live_raise(nodes, call, monkeypatch):
    """Runtime-mutable per-tenant quotas (round-19 residual closed): a
    noisy tenant starved at the static env tier gets its quota RAISED
    via the ``set_tenant_quota`` admin RPC and serves on the very next
    call — no restart, no waiting out the starved bucket's refill
    horizon — while its shed counters carry over unchanged. Zero/zero
    clears the override back to the env default tier."""
    from rocksplicator_tpu.rpc.admission import TenantAdmission
    from rocksplicator_tpu.utils.stats import Stats, tagged

    monkeypatch.setenv("RSTPU_TENANT_OPS", "2")
    TenantAdmission.reset_for_test()
    n = nodes("q")
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def ping(tenant):
        async def go():
            return await pool.call("127.0.0.1", n.admin_port, "ping", {},
                                   tenant=tenant, timeout=10)
        try:
            ioloop.run_sync(go())
            return True
        except RpcApplicationError as e:
            assert e.code == "RETRY_LATER"
            return False

    def shed_count():
        s = Stats.get()
        s.flush()
        return s.get_counter(tagged("rpc.tenant_shed", tenant="noisy",
                                    reason="quota"))

    try:
        outcomes = [ping("noisy") for _ in range(8)]
        assert not all(outcomes)  # the 2-op env tier starves it
        sheds_before = shed_count()
        assert sheds_before >= 1
        # the RAISE, over the wire (the admin RPC itself is internal
        # plane — untagged, never metered)
        out = call(n, "set_tenant_quota", tenant="noisy",
                   ops_per_sec=1000.0)
        assert out == {"tenant": "noisy", "ops_per_sec": 1000.0,
                       "bytes_per_sec": 0.0}
        assert TenantAdmission.get().quota_for("noisy") == (1000.0, 0.0)
        # effective immediately, and the raise rebuilt ONLY this
        # tenant's buckets — other tenants stay on the env tier
        assert all(ping("noisy") for _ in range(8))
        assert TenantAdmission.get().quota_for("other") == (2.0, 0.0)
        # per-tenant counters survived the rebuild: no resets, and no
        # new sheds after the raise
        assert shed_count() == sheds_before
        # zero/zero clears the override back to the env default
        call(n, "set_tenant_quota", tenant="noisy")
        assert TenantAdmission.get().quota_for("noisy") == (2.0, 0.0)
    finally:
        ioloop.run_sync(pool.close())


def test_add_db_write_read_seq(nodes, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    with pytest.raises(RpcApplicationError) as ei:
        call(n, "add_db", db_name="seg00001", role="LEADER")
    assert ei.value.code == "DB_ALREADY_EXISTS"
    app_db = n.handler.db_manager.get_db("seg00001")
    app_db.write(WriteBatch().put(b"k", b"v"))
    assert call(n, "get_sequence_number", db_name="seg00001")["seq_num"] == 1
    check = call(n, "check_db", db_name="seg00001")
    assert check["seq_num"] == 1
    assert check["role"] == "LEADER"


def test_add_db_follower_requires_upstream(nodes, call):
    n = nodes("a")
    with pytest.raises(RpcApplicationError) as ei:
        call(n, "add_db", db_name="seg00001", role="FOLLOWER")
    assert ei.value.code == "INVALID_UPSTREAM"
    with pytest.raises(RpcApplicationError):
        call(n, "add_db", db_name="seg00001", role="WIZARD")


def test_leader_follower_via_admin(nodes, call):
    a, b = nodes("a"), nodes("b")
    call(a, "add_db", db_name="seg00001", role="LEADER")
    call(b, "add_db", db_name="seg00001", role="SLAVE",  # alias coverage
         upstream_ip=a.repl_addr[0], upstream_port=a.repl_addr[1])
    app_db = a.handler.db_manager.get_db("seg00001")
    for i in range(10):
        app_db.write(WriteBatch().put(f"k{i}".encode(), b"v"))
    assert wait_until(
        lambda: call(b, "get_sequence_number", db_name="seg00001")["seq_num"] == 10
    )


def test_close_and_clear_db(nodes, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app_db = n.handler.db_manager.get_db("seg00001")
    app_db.write(WriteBatch().put(b"k", b"v"))
    call(n, "close_db", db_name="seg00001")
    assert n.handler.db_manager.get_db("seg00001") is None
    # closed but not destroyed: re-add sees the data
    call(n, "add_db", db_name="seg00001", role="LEADER")
    assert call(n, "get_sequence_number", db_name="seg00001")["seq_num"] == 1
    # clearDB destroys and reopens fresh
    call(n, "clear_db", db_name="seg00001")
    assert call(n, "get_sequence_number", db_name="seg00001")["seq_num"] == 0


def test_change_db_role_and_upstream_failover(nodes, call):
    a, b = nodes("a"), nodes("b")
    call(a, "add_db", db_name="seg00001", role="LEADER")
    call(b, "add_db", db_name="seg00001", role="FOLLOWER",
         upstream_ip=a.repl_addr[0], upstream_port=a.repl_addr[1])
    a.handler.db_manager.get_db("seg00001").write(WriteBatch().put(b"k1", b"v1"))
    assert wait_until(
        lambda: call(b, "get_sequence_number", db_name="seg00001")["seq_num"] == 1
    )
    # failover: promote b to leader, demote a to follower of b
    call(a, "close_db", db_name="seg00001")
    call(b, "change_db_role_and_upstream", db_name="seg00001", new_role="MASTER")
    call(a, "add_db", db_name="seg00001", role="FOLLOWER",
         upstream_ip=b.repl_addr[0], upstream_port=b.repl_addr[1])
    b.handler.db_manager.get_db("seg00001").write(WriteBatch().put(b"k2", b"v2"))
    assert wait_until(
        lambda: call(a, "get_sequence_number", db_name="seg00001")["seq_num"] == 2
    )
    assert a.handler.db_manager.get_db("seg00001").get(b"k2") == b"v2"


def test_backup_restore_roundtrip(nodes, call, tmp_path):
    n = nodes("a")
    store_uri = str(tmp_path / "bucket")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app_db = n.handler.db_manager.get_db("seg00001")
    for i in range(50):
        app_db.write(WriteBatch().put(f"k{i}".encode(), f"v{i}".encode()))
    r = call(n, "backup_db_to_s3", db_name="seg00001",
             s3_bucket=store_uri, s3_backup_dir="backups/seg00001")
    assert r["seq"] == 50
    # wipe and restore
    call(n, "clear_db", db_name="seg00001", reopen_db=False)
    call(n, "restore_db_from_s3", db_name="seg00001",
         s3_bucket=store_uri, s3_backup_dir="backups/seg00001")
    assert call(n, "get_sequence_number", db_name="seg00001")["seq_num"] == 50
    assert n.handler.db_manager.get_db("seg00001").get(b"k49") == b"v49"


def test_backup_restore_to_peer(nodes, call, tmp_path):
    """Rebuild-from-peer flow (§3.4): backup on A, restore on B as follower."""
    a, b = nodes("a"), nodes("b")
    store_uri = str(tmp_path / "bucket")
    call(a, "add_db", db_name="seg00001", role="LEADER")
    adb = a.handler.db_manager.get_db("seg00001")
    for i in range(20):
        adb.write(WriteBatch().put(f"k{i}".encode(), b"v"))
    call(a, "backup_db", db_name="seg00001", hdfs_backup_dir=store_uri)
    call(b, "restore_db", db_name="seg00001", hdfs_backup_dir=store_uri,
         upstream_ip=a.repl_addr[0], upstream_port=a.repl_addr[1])
    # restored as follower: catches up with new leader writes
    adb.write(WriteBatch().put(b"new", b"x"))
    assert wait_until(
        lambda: call(b, "get_sequence_number", db_name="seg00001")["seq_num"] == 21
    )
    assert b.handler.db_manager.get_db("seg00001").get(b"new") == b"x"


def _make_sst_in_store(store, path_prefix, items, tmp_path, name="bulk.tsst"):
    local = tmp_path / name
    w = SSTWriter(str(local))
    for k, v in items:
        w.add(k, 0, OpType.PUT, v)
    w.finish()
    store.put_object(str(local), f"{path_prefix}/{name}")


def test_add_sst_files_ingest(nodes, call, tmp_path):
    n = nodes("a")
    store_uri = str(tmp_path / "bucket")
    store = LocalObjectStore(store_uri)
    _make_sst_in_store(store, "sst/v1",
                       [(b"a", b"1"), (b"b", b"2")], tmp_path)
    call(n, "add_db", db_name="seg00001", role="LEADER")
    r = call(n, "add_s3_sst_files_to_db", db_name="seg00001",
             s3_bucket=store_uri, s3_path="sst/v1")
    assert r["ingested_files"] == 1
    app_db = n.handler.db_manager.get_db("seg00001")
    assert app_db.get(b"a") == b"1"
    # idempotency: same bucket+path skips (admin_handler.cpp:1655-1667)
    r2 = call(n, "add_s3_sst_files_to_db", db_name="seg00001",
              s3_bucket=store_uri, s3_path="sst/v1")
    assert r2.get("skipped") is True
    # meta_db recorded the hosting
    meta = n.handler.get_meta_data("seg00001")
    assert meta.s3_path == "sst/v1"


def test_add_sst_files_full_replace_and_compact(nodes, call, tmp_path):
    n = nodes("a")
    store_uri = str(tmp_path / "bucket")
    store = LocalObjectStore(store_uri)
    _make_sst_in_store(store, "sst/v2", [(b"new", b"data")], tmp_path)
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app_db = n.handler.db_manager.get_db("seg00001")
    app_db.write(WriteBatch().put(b"old", b"x"))
    call(n, "add_s3_sst_files_to_db", db_name="seg00001",
         s3_bucket=store_uri, s3_path="sst/v2",
         allow_overlapping_keys=False, compact_db_after_load=True)
    app_db2 = n.handler.db_manager.get_db("seg00001")
    assert app_db2.get(b"old") is None  # full replace dropped old data
    assert app_db2.get(b"new") == b"data"


def test_add_sst_files_ingest_behind(nodes, call, tmp_path):
    def opts_gen(segment):
        return DBOptions(allow_ingest_behind=True)

    n = nodes("a", options_generator=opts_gen)
    store_uri = str(tmp_path / "bucket")
    store = LocalObjectStore(store_uri)
    _make_sst_in_store(store, "sst/vb", [(b"base", b"bulk"), (b"k", b"bulk")],
                       tmp_path)
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app_db = n.handler.db_manager.get_db("seg00001")
    app_db.write(WriteBatch().put(b"k", b"live"))
    call(n, "add_s3_sst_files_to_db", db_name="seg00001",
         s3_bucket=store_uri, s3_path="sst/vb", ingest_behind=True)
    assert app_db.get(b"k") == b"live"   # live shadows behind-ingest
    assert app_db.get(b"base") == b"bulk"


def test_add_sst_files_ingest_behind_rejected_without_option(nodes, call, tmp_path):
    n = nodes("a")
    store_uri = str(tmp_path / "bucket")
    store = LocalObjectStore(store_uri)
    _make_sst_in_store(store, "sst/vx", [(b"a", b"1")], tmp_path)
    call(n, "add_db", db_name="seg00001", role="LEADER")
    with pytest.raises(RpcApplicationError) as ei:
        call(n, "add_s3_sst_files_to_db", db_name="seg00001",
             s3_bucket=store_uri, s3_path="sst/vx", ingest_behind=True)
    assert ei.value.code == "DB_ADMIN_ERROR"


def test_set_db_options_and_compact(nodes, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    call(n, "set_db_options", db_name="seg00001",
         options={"disable_auto_compaction": True, "memtable_bytes": 4096})
    app_db = n.handler.db_manager.get_db("seg00001")
    assert app_db.db.options.disable_auto_compaction is True
    with pytest.raises(RpcApplicationError):
        call(n, "set_db_options", db_name="seg00001", options={"num_levels": 2})
    for i in range(10):
        app_db.write(WriteBatch().put(f"k{i}".encode(), b"v"))
        app_db.write(WriteBatch().delete(f"k{i}".encode()))
    call(n, "compact_db", db_name="seg00001")
    assert list(app_db.new_iterator()) == []


def test_message_ingestion_error_paths(nodes, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    # unknown topic on the embedded broker
    with pytest.raises(RpcApplicationError) as ei:
        call(n, "start_message_ingestion", db_name="seg00001",
             topic_name="no-such-topic")
    assert ei.value.code == "DB_ADMIN_ERROR"
    # an unparseable broker address (no host:port, no such serverset file)
    with pytest.raises(RpcApplicationError) as ei3:
        call(n, "start_message_ingestion", db_name="seg00001",
             topic_name="t", kafka_broker_serverset_path="/etc/brokers")
    assert ei3.value.code == "DB_ADMIN_ERROR"
    assert "bad broker address" in ei3.value.message
    with pytest.raises(RpcApplicationError) as ei2:
        call(n, "stop_message_ingestion", db_name="seg00001")
    assert ei2.value.code == "DB_NOT_FOUND"


def test_storage_info_text(nodes, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    text = n.handler.storage_info_text()
    assert "db=seg00001" in text
    assert "role=LEADER" in text


# ---------------------------------------------------------------------------
# CDC observer (cdc_admin tests)
# ---------------------------------------------------------------------------


def test_cdc_observer_publishes_updates(nodes, call):
    a = nodes("a")
    call(a, "add_db", db_name="seg00001", role="LEADER")
    adb = a.handler.db_manager.get_db("seg00001")
    adb.write(WriteBatch().put(b"before", b"x"))  # before observer attaches

    cdc_node = nodes("cdc")
    publisher = MemoryPublisher()
    cdc = CdcAdminHandler(cdc_node.replicator, publisher)
    cdc_server = RpcServer(port=0, ioloop=cdc_node.replicator.ioloop)
    cdc_server.add_handler(cdc)
    cdc_server.start()
    try:
        ioloop = IoLoop.default()
        pool = RpcClientPool()

        def cdc_call(method, **args):
            async def go():
                return await pool.call("127.0.0.1", cdc_server.port, method, args)

            return ioloop.run_sync(go())

        r = cdc_call("add_observer", db_name="seg00001",
                     upstream_ip=a.repl_addr[0], upstream_port=a.repl_addr[1])
        assert r["start_seq"] == 1  # starts from "now", skipping history
        with pytest.raises(RpcApplicationError):
            cdc_call("add_observer", db_name="seg00001",
                     upstream_ip=a.repl_addr[0], upstream_port=a.repl_addr[1])
        # new writes flow to the publisher
        adb.write(WriteBatch().put(b"k1", b"v1"))
        adb.write(WriteBatch().delete(b"k0"))
        assert wait_until(lambda: len(publisher.buffer) >= 2)
        db_name, start_seq, raw, ts = publisher.buffer[0]
        assert db_name == "seg00001"
        assert start_seq == 2
        ops = list(decode_batch(raw).ops())
        assert (OpType.PUT, b"k1", b"v1") in ops
        check = cdc_call("check_observer", db_name="seg00001")
        assert check["seq_num"] == 3
        assert check["published_count"] == 2
        assert cdc_call("get_sequence_number", db_name="seg00001")["seq_num"] == 3
        cdc_call("remove_observer", db_name="seg00001")
        with pytest.raises(RpcApplicationError):
            cdc_call("check_observer", db_name="seg00001")
        ioloop.run_sync(pool.close())
    finally:
        cdc_server.stop()


# ---------------------------------------------------------------------------
# incremental backup manager
# ---------------------------------------------------------------------------


def test_backup_manager_incremental(nodes, tmp_path, call):
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    call(n, "add_db", db_name="seg00002", role="LEADER")
    for name in ("seg00001", "seg00002"):
        app_db = n.handler.db_manager.get_db(name)
        for i in range(10):
            app_db.write(WriteBatch().put(f"k{i}".encode(), b"v"))
    store = LocalObjectStore(str(tmp_path / "bucket"))
    mgr = ApplicationDBBackupManager(n.handler.db_manager, store, "inc")
    assert mgr.backup_all_dbs() == 2
    files_before = set(store.list_objects("inc/seg00001/"))
    assert any("sst-" in f for f in files_before)
    # second pass with no new writes: SSTs are skipped (incremental)
    app_db = n.handler.db_manager.get_db("seg00001")
    app_db.write(WriteBatch().put(b"more", b"x"))
    assert mgr.backup_all_dbs() == 2
    files_after = set(store.list_objects("inc/seg00001/"))
    assert files_before.issubset(files_after)
    # restore from the incremental prefix works
    from rocksplicator_tpu.storage import backup as backup_mod

    dbmeta = backup_mod.restore_db(store, "inc/seg00001", str(tmp_path / "r1"))
    from rocksplicator_tpu.storage import DB

    with DB(str(tmp_path / "r1")) as restored:
        assert restored.get(b"more") == b"x"
        assert restored.latest_sequence_number() == dbmeta["seq"] == 11


# ---------------------------------------------------------------------------
# regression tests from code review (round 2)
# ---------------------------------------------------------------------------


def test_backup_after_clear_not_corrupted_by_name_collision(nodes, call, tmp_path):
    """clearDB resets file ids; incremental backup must not skip the new
    same-numbered SST (fixed by per-creation incarnation ids)."""
    n = nodes("a")
    store_uri = str(tmp_path / "bucket")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app = n.handler.db_manager.get_db("seg00001")
    app.write(WriteBatch().put(b"old", b"1"))
    call(n, "backup_db_to_s3", db_name="seg00001",
         s3_bucket=store_uri, s3_backup_dir="b/seg00001")
    call(n, "clear_db", db_name="seg00001")  # fresh incarnation
    app2 = n.handler.db_manager.get_db("seg00001")
    app2.write(WriteBatch().put(b"new", b"2"))
    call(n, "backup_db_to_s3", db_name="seg00001",
         s3_bucket=store_uri, s3_backup_dir="b/seg00001")
    call(n, "clear_db", db_name="seg00001", reopen_db=False)
    call(n, "restore_db_from_s3", db_name="seg00001",
         s3_bucket=store_uri, s3_backup_dir="b/seg00001")
    restored = n.handler.db_manager.get_db("seg00001")
    assert restored.get(b"new") == b"2"
    assert restored.get(b"old") is None  # no stale pre-clear data


def test_cdc_publisher_failure_is_at_least_once(nodes, call):
    a = nodes("a")
    call(a, "add_db", db_name="seg00001", role="LEADER")
    adb = a.handler.db_manager.get_db("seg00001")

    failures = [2]  # fail the first two publish attempts
    published = []

    def flaky_publisher(db_name, start_seq, raw, ts):
        if failures[0] > 0:
            failures[0] -= 1
            raise RuntimeError("broker down")
        published.append((start_seq, raw))

    cdc_node = nodes("cdc")
    cdc = CdcAdminHandler(cdc_node.replicator, flaky_publisher)
    ioloop = cdc_node.replicator.ioloop
    import asyncio

    fut = ioloop.run_coro(cdc.handle_add_observer(
        db_name="seg00001", upstream_ip=a.repl_addr[0],
        upstream_port=a.repl_addr[1]))
    fut.result(10)
    adb.write(WriteBatch().put(b"k", b"v"))
    # the batch must eventually be published despite the two failures
    assert wait_until(lambda: len(published) == 1, timeout=20)
    assert published[0][0] == 1


def test_concurrent_duplicate_add_observer_typed_error(nodes, monkeypatch):
    a = nodes("a")
    cdc = CdcAdminHandler(a.replicator, MemoryPublisher())
    ioloop = a.replicator.ioloop
    import asyncio

    real = CdcAdminHandler._do_add_observer

    async def slow(self, *args, **kw):
        await asyncio.sleep(0.5)  # hold the first call in flight
        return await real(self, *args, **kw)

    monkeypatch.setattr(CdcAdminHandler, "_do_add_observer", slow)

    async def both():
        t1 = asyncio.ensure_future(cdc.handle_add_observer(
            db_name="segX", upstream_ip="127.0.0.1", upstream_port=1))
        await asyncio.sleep(0.05)
        try:
            await cdc.handle_add_observer(
                db_name="segX", upstream_ip="127.0.0.1", upstream_port=1)
            code = None
        except RpcApplicationError as e:
            code = e.code
        t1.cancel()
        try:
            await t1
        except (asyncio.CancelledError, Exception):
            pass
        return code

    code = ioloop.run_coro(both()).result(10)
    assert code == "OBSERVER_ALREADY_EXISTS"


def test_tpu_compaction_flag_installs_backend(nodes, call, tmp_path):
    n = AdminNode(tmp_path, "tpunode")
    n.handler._tpu_compaction = True
    try:
        call(n, "add_db", db_name="seg00001", role="LEADER")
        app_db = n.handler.db_manager.get_db("seg00001")
        from rocksplicator_tpu.tpu.backend import TpuCompactionBackend

        assert isinstance(app_db.db.options.compaction_backend,
                          TpuCompactionBackend)
        # the TPU-backed compaction produces correct results end-to-end
        app_db.write(WriteBatch().put(b"a", b"1"))
        app_db.write(WriteBatch().delete(b"a"))
        app_db.write(WriteBatch().put(b"b", b"2"))
        call(n, "compact_db", db_name="seg00001")
        assert app_db.get(b"a") is None
        assert app_db.get(b"b") == b"2"
    finally:
        n.stop()


def test_admin_plane_over_mutual_tls(tmp_path):
    """Admin RPCs (add_db / put / get / checkpoint paths) work over a
    mutual-TLS RpcServer + client pool (VERDICT item 8)."""
    pytest.importorskip(
        "cryptography",
        reason="TLS tests need the 'cryptography' package to mint the "
               "test CA (not installed in this image)")
    from rocksplicator_tpu.utils.ssl_context_manager import (
        SslContextManager, make_test_ca,
    )

    certs = make_test_ca(str(tmp_path / "certs"))
    server_mgr = SslContextManager(
        certs["server_cert"], certs["server_key"],
        ca_path=certs["ca_cert"], server_side=True)
    client_mgr = SslContextManager(
        certs["client_cert"], certs["client_key"],
        ca_path=certs["ca_cert"], server_side=False)
    replicator = Replicator(port=0, flags=FAST)
    handler = AdminHandler(str(tmp_path / "node"), replicator)
    server = RpcServer(port=0, ioloop=replicator.ioloop,
                       ssl_manager=server_mgr)
    server.add_handler(handler)
    server.start()
    ioloop = IoLoop.default()
    pool = RpcClientPool(ssl_manager=client_mgr)

    def call(method, **args):
        async def go():
            return await pool.call("127.0.0.1", server.port, method, args)

        return ioloop.run_sync(go(), timeout=30)

    try:
        assert call("ping")["ok"] is True
        call("add_db", db_name="seg00001", role="LEADER")
        app_db = handler.db_manager.get_db("seg00001")
        app_db.write(WriteBatch().put(b"k", b"v"))
        assert call("get_sequence_number", db_name="seg00001")["seq_num"] == 1
        assert call("check_db", db_name="seg00001")["seq_num"] == 1
    finally:
        ioloop.run_sync(pool.close())
        server.stop()
        handler.close()
        replicator.stop()


def test_backup_manager_wal_archive_and_admin_pitr(nodes, tmp_path, call):
    """archive_wal rider + restore RPC to_seq: the admin-plane PITR flow
    (backup manager ships WAL continuously; restore_db_from_s3 with
    to_seq replays the archive over the checkpoint)."""
    n = nodes("a")
    call(n, "add_db", db_name="seg00001", role="LEADER")
    app_db = n.handler.db_manager.get_db("seg00001")
    for i in range(10):
        app_db.write(WriteBatch().put(f"k{i}".encode(), b"v1"))
    store = LocalObjectStore(str(tmp_path / "bucket"))
    mgr = ApplicationDBBackupManager(
        n.handler.db_manager, store, "inc", archive_wal=True)
    assert mgr.backup_all_dbs() == 1  # checkpoint at seq 10 + WAL archive
    # the archiver was installed as the DB's TTL-purge sink
    assert app_db.db.options.wal_archive_sink is not None
    for i in range(5):
        app_db.write(WriteBatch().put(f"mid{i}".encode(), b"v2"))
    mid_seq = app_db.db.latest_sequence_number()
    for i in range(5):
        app_db.write(WriteBatch().put(f"late{i}".encode(), b"v3"))
    assert mgr.backup_all_dbs() == 1  # second pass ships the WAL tail
    # restore to the mid-history point through the admin RPC
    call(n, "restore_db_from_s3", db_name="seg00002",
         s3_bucket=str(tmp_path / "bucket"), s3_backup_dir="inc/seg00001",
         to_seq=mid_seq)
    rdb = n.handler.db_manager.get_db("seg00002")
    assert rdb.get(b"mid4") == b"v2"
    assert rdb.get(b"k0") == b"v1"
    assert call(n, "get_sequence_number",
                db_name="seg00002")["seq_num"] == mid_seq
    assert rdb.get(b"late0") is None  # beyond the restore point


def test_the_process_base_is_frozen_once(tmp_path, monkeypatch):
    """The first handler of a process moves what lives then to the
    permanent generation (a full collection no longer walks the node's
    modules: PERF.md section 6, PR 35); a second one freezes nothing."""
    import gc

    from rocksplicator_tpu.admin import handler as handler_mod

    calls = []
    monkeypatch.setattr(handler_mod, "_base_frozen", False)
    monkeypatch.setattr(handler_mod.gc, "freeze", lambda: calls.append(1))
    replicator = Replicator(port=0)
    try:
        for n in range(2):
            handler_mod.AdminHandler(
                str(tmp_path / f"h{n}"), replicator).close()
    finally:
        replicator.stop()
    assert calls == [1] and handler_mod._base_frozen is True
    assert gc.isenabled()
