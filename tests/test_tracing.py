"""Distributed tracing subsystem tests (observability/).

Covers the ISSUE's test checklist: contextvar inheritance across
``asyncio.create_task``, trace-context round-trip through a real RPC
server, a 3-process leader→follower chain producing ONE stitched trace,
ring-buffer overflow drop-counting, the unsampled-path overhead smoke
test, and the two acceptance breakdowns ((a) semi-sync write, (b)
backup_db round trip) retrieved from the status server's ``/traces``
endpoint.
"""

import asyncio
import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from rocksplicator_tpu.observability import (
    SpanCollector,
    current_span,
    start_span,
)
from rocksplicator_tpu.replication import (
    ReplicaRole,
    ReplicationFlags,
    Replicator,
    StorageDbWrapper,
)
from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
from rocksplicator_tpu.storage import DB, DBOptions, WriteBatch
from rocksplicator_tpu.utils.status_server import StatusServer

FAST = ReplicationFlags(
    server_long_poll_ms=400,
    pull_error_delay_min_ms=50,
    pull_error_delay_max_ms=120,
    ack_timeout_ms=2000,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def wait_until(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return False


def _spans_by_name(name):
    return [s for s in SpanCollector.get().snapshot() if s["name"] == name]


# ---------------------------------------------------------------------------
# core span/context semantics
# ---------------------------------------------------------------------------


def test_contextvar_inheritance_across_create_task():
    """asyncio.create_task snapshots the creating task's context: spans
    opened inside the subtask must parent under the span active at
    task-creation time, with no explicit plumbing."""
    SpanCollector.get().configure(sample_rate=1.0)
    seen = {}

    async def child():
        sp = current_span()
        seen["inherited_trace"] = sp.trace_id if sp else None
        with start_span("child.work") as c:
            seen["child_parent"] = c.parent_id
            seen["child_trace"] = c.trace_id

    async def main():
        with start_span("parent.op") as p:
            seen["parent"] = (p.trace_id, p.span_id)
            t = asyncio.create_task(child())
            await t

    asyncio.run(main())
    trace_id, span_id = seen["parent"]
    assert seen["inherited_trace"] == trace_id
    assert seen["child_trace"] == trace_id
    assert seen["child_parent"] == span_id


def test_unsampled_root_suppresses_descendants():
    """An unsampled root must park the NOOP sentinel so descendants do
    not re-roll sampling (orphan partial traces) and nothing records."""
    col = SpanCollector.get()
    col.configure(sample_rate=0.0)
    with start_span("root") as r:
        assert not r.sampled
        with start_span("inner") as i:
            assert not i.sampled
    assert current_span() is None
    assert col.recorded == 0
    # always=True bypasses the roll only at the ROOT of a new trace
    with start_span("ctl", always=True) as r:
        assert r.sampled
    assert col.recorded == 1


def test_ring_buffer_overflow_drop_counting():
    col = SpanCollector.get()
    col.configure(sample_rate=0.0, capacity=32)
    for _ in range(100):
        with start_span("s", always=True):
            pass
    assert col.recorded == 100
    assert col.dropped == 68
    assert len(col.snapshot()) == 32
    # the export surfaces the truncation so a partial window is never
    # read as complete coverage
    payload = json.loads(col.to_json_text())
    assert payload["dropped"] == 68 and payload["recorded"] == 100


def test_unsampled_path_overhead_smoke():
    """With sampling disabled the instrumentation must be near-free: no
    Span objects, no collector traffic, just a contextvar set/reset and
    one roll per would-be root. Bound is deliberately generous (CI noise)
    — the acceptance criterion's <5% on the replication microbench rides
    on this being single-digit microseconds."""
    col = SpanCollector.get()
    col.configure(sample_rate=0.0)
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        with start_span("hot.op", db="x"):
            pass
    per_op_us = (time.perf_counter() - t0) / n * 1e6
    assert col.recorded == 0
    assert per_op_us < 50.0, f"unsampled span cost {per_op_us:.1f}µs"


# ---------------------------------------------------------------------------
# cross-process propagation: RPC round trip
# ---------------------------------------------------------------------------


class _EchoHandler:
    async def handle_echo(self, text=""):
        return {"text": text}


def test_rpc_trace_context_roundtrip():
    """A sampled caller's context must ride the JSON frame header and
    reattach server-side: the rpc.server span joins the caller's trace,
    and the pool/client spans give the queue-wait/connect/RTT split."""
    SpanCollector.get().configure(sample_rate=1.0)
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    server.add_handler(_EchoHandler())
    server.start()
    try:
        async def go():
            pool = RpcClientPool()
            with start_span("test.client_op") as root:
                await pool.call("127.0.0.1", server.port, "echo",
                                {"text": "hi"})
                tid = root.trace_id
            await pool.close()
            return tid

        tid = ioloop.run_sync(go())
        # server span sampled and stitched onto the client's trace id
        assert wait_until(lambda: any(
            s["trace_id"] == tid for s in _spans_by_name("rpc.server")))
        server_span = [s for s in _spans_by_name("rpc.server")
                       if s["trace_id"] == tid][0]
        assert server_span["annotations"]["method"] == "echo"
        rtt = [s for s in _spans_by_name("rpc.rtt")
               if s["trace_id"] == tid][0]
        # parent chain: client_op -> rtt -> server
        assert server_span["parent_id"] == rtt["span_id"]
        # slow path spans: first call to a fresh addr connects
        acquire = [s for s in _spans_by_name("rpc.pool.acquire")
                   if s["trace_id"] == tid]
        assert acquire and "queue_wait_ms" in acquire[0]["annotations"]
        assert any(s["trace_id"] == tid
                   for s in _spans_by_name("rpc.pool.connect"))
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# acceptance (a): semi-sync write breakdown via /traces
# ---------------------------------------------------------------------------


def test_semisync_write_breakdown_via_traces_endpoint(tmp_path):
    """One mode-1 write's per-phase trace — leader receive → WAL fsync →
    follower-ACK wait — retrievable as JSON from /traces."""
    SpanCollector.get().configure(sample_rate=1.0)
    leader = Replicator(port=0, flags=FAST)
    follower = Replicator(port=0, flags=FAST)
    ldb = DB(str(tmp_path / "l"), DBOptions())
    fdb = DB(str(tmp_path / "f"), DBOptions())
    status = StatusServer(port=0)
    status.start()
    try:
        leader.add_db("shard1", StorageDbWrapper(ldb), ReplicaRole.LEADER,
                      replication_mode=1)
        follower.add_db("shard1", StorageDbWrapper(fdb),
                        ReplicaRole.FOLLOWER,
                        upstream_addr=("127.0.0.1", leader.port),
                        replication_mode=1)
        leader.write("shard1", WriteBatch().put(b"k", b"v"))
        payload = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{status.port}/traces", timeout=10
        ).read().decode())
        write_traces = [
            t for t in payload["traces"]
            if any(s["name"] == "repl.write" for s in t["spans"])
        ]
        assert write_traces, "no repl.write trace on /traces"
        spans = write_traces[0]["spans"]
        by_name = {s["name"]: s for s in spans}
        root = by_name["repl.write"]
        assert root["parent_id"] is None
        assert root["annotations"]["db"] == "shard1"
        # the two phases of the 4.6ms mystery: fsync vs ack wait, both
        # children of the write root with real durations
        for phase in ("repl.wal_write", "repl.ack_wait"):
            assert by_name[phase]["parent_id"] == root["span_id"]
            assert by_name[phase]["duration_ms"] >= 0.0
        assert by_name["repl.ack_wait"]["annotations"]["acked"] is True
        # human view renders the same trace
        txt = urllib.request.urlopen(
            f"http://127.0.0.1:{status.port}/traces.txt", timeout=10
        ).read().decode()
        assert "repl.write" in txt and "repl.ack_wait" in txt
    finally:
        status.stop()
        leader.stop()
        follower.stop()
        ldb.close()
        fdb.close()


# ---------------------------------------------------------------------------
# acceptance (b): backup_db round trip breakdown via /traces
# ---------------------------------------------------------------------------


def test_backup_restore_roundtrip_trace_via_endpoint(tmp_path):
    """A backup_db + restore_db round trip must leave per-phase traces
    (checkpoint → upload batches; dbmeta → download) on /traces."""
    from rocksplicator_tpu.admin.handler import AdminHandler

    SpanCollector.get().configure(sample_rate=1.0)
    repl = Replicator(port=0, flags=FAST)
    handler = AdminHandler(str(tmp_path / "node"), repl)
    server = RpcServer(port=0, ioloop=repl.ioloop)
    server.add_handler(handler)
    server.start()
    status = StatusServer(port=0)
    status.start()
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def call(method, **args):
        async def go():
            return await pool.call("127.0.0.1", server.port, method, args,
                                   timeout=30)
        return ioloop.run_sync(go())

    try:
        store_uri = str(tmp_path / "bucket")
        call("add_db", db_name="seg00001", role="LEADER")
        app_db = handler.db_manager.get_db("seg00001")
        for i in range(20):
            app_db.write(WriteBatch().put(f"k{i}".encode(), b"v" * 64))
        call("backup_db", db_name="seg00001", hdfs_backup_dir=store_uri)
        call("clear_db", db_name="seg00001", reopen_db=False)
        call("restore_db", db_name="seg00001", hdfs_backup_dir=store_uri)
        assert handler.db_manager.get_db("seg00001").get(b"k19") == b"v" * 64

        payload = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{status.port}/traces", timeout=10
        ).read().decode())
        backup_traces = [
            t for t in payload["traces"]
            if any(s["name"] == "admin.backup_db" for s in t["spans"])
        ]
        assert backup_traces, "no admin.backup_db trace on /traces"
        names = {s["name"] for s in backup_traces[0]["spans"]}
        # checkpoint → upload phases, nested under the backup root
        assert {"admin.backup_db", "storage.checkpoint",
                "backup.upload"} <= names
        by_name = {s["name"]: s for s in backup_traces[0]["spans"]}
        # the checkpoint now nests under the lock-held phase span so the
        # waterfall shows exactly how long the per-db admin lock is held
        # (the upload phase runs outside it)
        assert by_name["storage.checkpoint"]["parent_id"] == \
            by_name["admin.backup.checkpoint"]["span_id"]
        assert by_name["admin.backup.checkpoint"]["parent_id"] == \
            by_name["admin.backup_db"]["span_id"]
        assert by_name["backup.upload"]["annotations"]["files"] > 0
        restore_traces = [
            t for t in payload["traces"]
            if any(s["name"] == "admin.restore_db" for s in t["spans"])
        ]
        assert restore_traces, "no admin.restore_db trace on /traces"
        rnames = {s["name"] for s in restore_traces[0]["spans"]}
        assert {"admin.restore_db", "restore.dbmeta_get",
                "restore.download"} <= rnames
    finally:
        ioloop.run_sync(pool.close())
        status.stop()
        server.stop()
        handler.close()
        repl.stop()


# ---------------------------------------------------------------------------
# 3-process leader→follower chain: one stitched trace
# ---------------------------------------------------------------------------

_FOLLOWER_SCRIPT = """
import sys, time
sys.path.insert(0, sys.argv[1])
from rocksplicator_tpu.observability.collector import SpanCollector
from rocksplicator_tpu.replication import (
    ReplicaRole, ReplicationFlags, Replicator, StorageDbWrapper)
from rocksplicator_tpu.storage import DB, DBOptions
from rocksplicator_tpu.utils.status_server import StatusServer

repo, db_dir, upstream_port, label = sys.argv[1:5]
# local sampling OFF: every span this process records must come from a
# REMOTE (stitched) context carried by the replication stream
SpanCollector.get().configure(sample_rate=0.0, process=label)
flags = ReplicationFlags(server_long_poll_ms=400,
                         pull_error_delay_min_ms=50,
                         pull_error_delay_max_ms=120)
repl = Replicator(port=0, flags=flags)
db = DB(db_dir, DBOptions())
repl.add_db("chain1", StorageDbWrapper(db), ReplicaRole.FOLLOWER,
            upstream_addr=("127.0.0.1", int(upstream_port)))
status = StatusServer(port=0)
status.start()
print(f"PORTS repl={repl.port} http={status.port}", flush=True)
time.sleep(180)
"""


def _spawn_follower(tmp_path, name, upstream_port):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-c", _FOLLOWER_SCRIPT, REPO_ROOT,
         str(tmp_path / name), str(upstream_port), name],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO_ROOT, env=env,
    )
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if line.startswith("PORTS"):
            parts = dict(p.split("=") for p in line.split()[1:])
            return proc, int(parts["repl"]), int(parts["http"])
        if not line and proc.poll() is not None:
            break
    raise AssertionError(f"follower {name} never reported ports")


def _fetch_trace_spans(http_port, trace_id):
    payload = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{http_port}/traces", timeout=10).read().decode())
    for t in payload["traces"]:
        if t["trace_id"] == trace_id:
            return t["spans"]
    return []


def test_three_process_chain_one_stitched_trace(tmp_path):
    """leader (this process) → follower f1 → follower f2, three OS
    processes. One sampled leader write must produce ONE trace whose
    spans live in three different processes, stitched by fetching each
    process's /traces and joining on the trace id — with the apply spans
    forming a parent CHAIN (leader write ← f1 apply ← f2 apply)."""
    SpanCollector.get().configure(sample_rate=0.0, process="leader")
    leader = Replicator(port=0, flags=FAST)
    ldb = DB(str(tmp_path / "l"), DBOptions())
    f1 = f2 = None
    try:
        leader.add_db("chain1", StorageDbWrapper(ldb), ReplicaRole.LEADER)
        f1, f1_repl, f1_http = _spawn_follower(tmp_path, "f1", leader.port)
        f2, f2_repl, f2_http = _spawn_follower(tmp_path, "f2", f1_repl)

        # always=True root: the ONE write we trace end to end
        with start_span("test.traced_write", always=True) as root:
            tid = root.trace_id
            leader.write("chain1", WriteBatch().put(b"hello", b"chain"))

        # the stitched trace reaches f2 once the update has flowed
        # leader → f1 → f2 (each hop re-attaching the context in-band)
        assert wait_until(
            lambda: any(s["name"] == "repl.apply"
                        for s in _fetch_trace_spans(f2_http, tid)),
            timeout=30), "write trace never reached f2"

        local = [s for s in SpanCollector.get().snapshot()
                 if s["trace_id"] == tid]
        spans = (local + _fetch_trace_spans(f1_http, tid)
                 + _fetch_trace_spans(f2_http, tid))
        procs = {s["process"] for s in spans}
        assert {"leader", "f1", "f2"} <= procs, procs
        by_id = {s["span_id"]: s for s in spans}
        write = next(s for s in spans if s["name"] == "repl.write")
        f1_apply = next(s for s in spans
                        if s["name"] == "repl.apply"
                        and s["process"] == "f1")
        f2_apply = next(s for s in spans
                        if s["name"] == "repl.apply"
                        and s["process"] == "f2")
        # the parent CHAIN crosses both process hops
        assert f1_apply["parent_id"] == write["span_id"]
        assert f2_apply["parent_id"] == f1_apply["span_id"]
        assert by_id[write["parent_id"]]["name"] == "test.traced_write"
        # and the union renders as one waterfall
        from rocksplicator_tpu.observability import render_trace

        text = "\n".join(render_trace(spans))
        assert "repl.write" in text and "[f2]" in text
    finally:
        for p in (f1, f2):
            if p is not None:
                p.terminate()
                try:
                    p.wait(timeout=10)
                except Exception:
                    pass
        leader.stop()
        ldb.close()
