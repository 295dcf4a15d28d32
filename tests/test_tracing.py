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
            s["trace_id"] == tid for s in _spans_by_name("rpc.server.echo")))
        server_span = [s for s in _spans_by_name("rpc.server.echo")
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


# ---------------------------------------------------------------------------
# one root per served RPC (root-only when head-unsampled)
# ---------------------------------------------------------------------------


class _RootHandler:
    """Handlers that open what a served RPC can open under its root."""

    def __init__(self):
        self.seen = {}

    async def handle_plain(self):
        with start_span("plain.child") as c:  # ordinary: stays free
            self.seen["child_sampled"] = c.sampled
        return {}

    async def handle_control(self, sleep_ms=0):
        from rocksplicator_tpu.observability import wire_context

        root = current_span()
        self.seen["root"] = (root.trace_id, root.span_id)
        with start_span("ctl.direct", always=True):
            pass
        tctx = wire_context()  # the executor drops contextvars

        def hop():
            with start_span("ctl.hop", always=True, remote=tctx):
                with start_span("ctl.hop.child"):
                    pass
            # not always-on: the root-only context is not its to join
            with start_span("ctl.hop.plain", remote=tctx) as p:
                self.seen["plain_sampled"] = p.sampled

        await asyncio.get_running_loop().run_in_executor(None, hop)
        if sleep_ms:
            await asyncio.sleep(sleep_ms / 1000.0)
        return {}


def _serve_calls(calls, handler=None):
    """Serve ``calls`` ``[(method, args)]`` from a fresh server; returns
    the handler once every reply is in (and so every root finished:
    wait for the records, the reply is sent inside the span)."""
    ioloop = IoLoop.default()
    server = RpcServer(port=0, ioloop=ioloop)
    handler = handler or _RootHandler()
    server.add_handler(handler)
    server.start()
    try:
        async def go():
            pool = RpcClientPool()
            for method, args in calls:
                try:
                    await pool.call("127.0.0.1", server.port, method, args)
                except Exception:
                    pass  # an application error is a served RPC too
            await pool.close()

        ioloop.run_sync(go())
    finally:
        server.stop()
    return handler


def test_unsampled_rpc_leaves_one_root_named_by_method():
    col = SpanCollector.get()
    col.configure(sample_rate=0.0)
    handler = _serve_calls([("plain", {}), ("nope", {}), ("a b\n", {})])
    assert wait_until(lambda: col.recorded == 3)
    assert handler.seen["child_sampled"] is False
    snap = col.snapshot()
    assert [s["name"] for s in snap] == [
        "rpc.server.plain", "rpc.server.nope", "rpc.server.invalid"]
    plain, nope, _bad = snap
    assert plain["parent_id"] is None and plain["error"] is None
    assert plain["annotations"]["method"] == "plain"
    assert plain["annotations"]["queue_wait_ms"] >= 0.0
    assert "error_code" not in plain["annotations"]
    assert nope["annotations"]["error_code"] == "NO_SUCH_METHOD"
    assert col.tail_kept == 0


def test_always_span_joins_root_only_root_directly_and_across_a_hop():
    col = SpanCollector.get()
    col.configure(sample_rate=0.0)
    handler = _serve_calls([("control", {})])
    assert wait_until(lambda: _spans_by_name("rpc.server.control"))
    (root,) = _spans_by_name("rpc.server.control")
    assert handler.seen["root"] == (root["trace_id"], root["span_id"])
    assert handler.seen["plain_sampled"] is False
    for name in ("ctl.direct", "ctl.hop"):
        (child,) = _spans_by_name(name)
        assert child["trace_id"] == root["trace_id"]
        assert child["parent_id"] == root["span_id"]
    # below an always-on span the trace is a full one
    (grandchild,) = _spans_by_name("ctl.hop.child")
    assert grandchild["parent_id"] == _spans_by_name("ctl.hop")[0]["span_id"]
    assert grandchild["trace_id"] == root["trace_id"]
    assert not _spans_by_name("ctl.hop.plain")
    assert {s["trace_id"] for s in col.snapshot()} == {root["trace_id"]}


def test_slow_root_is_kept_once_with_the_ids_its_children_carry():
    col = SpanCollector.get()
    col.configure(sample_rate=0.0, tail_ms=30.0)
    _serve_calls([("control", {"sleep_ms": 60}), ("plain", {})])
    assert wait_until(lambda: _spans_by_name("rpc.server.plain"))
    roots = _spans_by_name("rpc.server.control")
    (root,) = roots  # once, though the main and the tail ring hold it
    # (the other slow root is the caller's own, client side)
    assert sorted(s["name"] for s in col.snapshot()
                  if s["annotations"].get("tail_kept")) == [
        "rpc.rtt", "rpc.server.control"]
    assert root["annotations"]["tail_kept"] is True
    assert root["duration_ms"] >= 30.0
    assert _spans_by_name("ctl.direct")[0]["parent_id"] == root["span_id"]
    assert "tail_kept" not in _spans_by_name(
        "rpc.server.plain")[0]["annotations"]
    # /traces shows it as one trace, the root with its children
    (trace,) = [t for t in json.loads(col.to_json_text())["traces"]
                if t["trace_id"] == root["trace_id"]]
    assert trace["span_count"] == 4


def test_kill_switch_silences_roots_and_what_runs_under_them():
    col = SpanCollector.get()
    col.configure(sample_rate=1.0, tail_ms=1.0)
    col.enabled = False  # RSTPU_TRACING=0
    try:
        _serve_calls([("control", {"sleep_ms": 5}), ("plain", {})])
    finally:
        col.enabled = True
    assert col.recorded == 0 and col.tail_kept == 0
    assert col.snapshot() == []


def test_kept_root_records_leave_the_garbage_collectors_lists():
    """One record is kept for every served RPC. A full collection stops
    every thread for as long as it takes to walk what is tracked (on the
    chip, PR 27: ~70 ms, 63 times a run, and 25,000 kept dicts moved
    write_p95_ms by 14 %), so a kept record is a flat tuple of atoms,
    which a collection drops from its lists."""
    import gc

    col = SpanCollector.get()
    col.configure(sample_rate=0.0, tail_ms=1.0)
    _serve_calls([("plain", {}), ("control", {"sleep_ms": 5}), ("nope", {})])
    assert wait_until(lambda: col.recorded >= 3)
    gc.collect()
    roots = [e for e in col._ring + col._tail_ring if type(e) is tuple]
    assert len(roots) >= 4  # three in the ring, the slow one kept twice
    assert not any(gc.is_tracked(e) for e in roots)
    # and a reader still gets a span's dict, the same on every call
    assert _spans_by_name("rpc.server.nope") == _spans_by_name(
        "rpc.server.nope")


# ---------------------------------------------------------------------------
# the post-load compaction, phase by phase, across its thread hops
# ---------------------------------------------------------------------------

PER_SHARD = ("admin.compact.plan", "tpu.lanes.decode",
             "admin.compact.install_db", "tpu.bloom", "tpu.planar.write")
PER_LAUNCH = ("tpu.h2d", "tpu.dispatch", "tpu.readback", "tpu.unpack")


def test_batched_compaction_is_one_trace_across_the_pool(tmp_path,
                                                         monkeypatch):
    """A dispatch of three shards over the BatchCompactor's pool: every
    phase is in the dispatch's trace, the per-shard ones once a shard,
    though they ran on pool threads; a rider's admin.compact.wait ends
    where the dispatch that took it starts."""
    import struct
    import threading

    from rocksplicator_tpu.admin.ingest_pipeline import BatchCompactor
    from rocksplicator_tpu.storage.sst import SSTWriter
    from rocksplicator_tpu.storage.records import OpType
    from rocksplicator_tpu.testing import failpoints as fp
    from rocksplicator_tpu.tpu import compaction_service as cs

    pack64 = struct.Struct("<q").pack
    col = SpanCollector.get()
    col.configure(sample_rate=0.0, capacity=4096)
    dbs = []
    for s in range(4):
        db = DB(str(tmp_path / f"db{s}"))
        for i in range(30):
            db.write(WriteBatch().put(f"k{i:03d}".encode(), pack64(-1)))
        sst = tmp_path / f"in{s}.tsst"
        w = SSTWriter(str(sst))
        for i in range(10, 40):
            w.add(f"k{i:03d}".encode(), 0, OpType.PUT, pack64(s * 1000 + i))
        w.finish()
        db.ingest_external_file([str(sst)], move_files=True,
                                allow_global_seqno=True)
        dbs.append(db)

    decode_threads = []
    real_lanes = cs.read_runs_as_lanes

    def db_lanes(*args, **kwargs):
        decode_threads.append(threading.current_thread().name)
        return real_lanes(*args, **kwargs)

    monkeypatch.setattr(cs, "read_runs_as_lanes", db_lanes)
    compactor = BatchCompactor(use_tpu=True, compact_parallelism=3)

    def submit(s):
        # an ingest RPC's place: the always-on trace the caller is in
        with start_span("test.caller", always=True, shard=s):
            compactor.compact(f"db{s}", dbs[s])

    # every dispatch starts with a 600 ms pause: the riders queue behind
    # the leader's own, and their dispatch is long beside a thread's
    # wake-up
    fp.activate("compact.dispatch", "delay_ms:600")
    try:
        threads = [threading.Thread(target=submit, args=(s,))
                   for s in range(4)]
        threads[0].start()
        assert wait_until(lambda: compactor.dispatch_count == 1)
        for t in threads[1:]:
            t.start()
        for t in threads:
            t.join(120)
            assert not t.is_alive()
    finally:
        fp.deactivate("compact.dispatch")
        compactor.close()
    assert compactor.batch_sizes == [1, 3]
    for s, db in enumerate(dbs):
        assert db.get(b"k015") == pack64(s * 1000 + 15)
        db.close()

    snap = col.snapshot()
    (dispatch,) = [s for s in snap if s["name"] == "admin.compact_dispatch"
                   and s["annotations"]["shards"] == 3]
    under = {dispatch["span_id"]}
    inside = []
    for s in snap:  # sorted by start: a parent comes before its children
        if s["parent_id"] in under:
            under.add(s["span_id"])
            inside.append(s)
    assert {s["trace_id"] for s in inside} == {dispatch["trace_id"]}
    count = {}
    for s in inside:
        count[s["name"]] = count.get(s["name"], 0) + 1
    for name in PER_SHARD:
        assert count.get(name) == 3, (name, count)
    for name in PER_LAUNCH + ("tpu.compact_stream", "admin.compact_stage",
                              "admin.compact_install"):
        assert count.get(name) == 1, (name, count)
    assert "tpu.stage" not in count and "tpu.kernel" not in count
    # 8-byte values ride the sorts: stacked and read by the leader alone
    assert "tpu.h2d.values" not in count
    assert "tpu.readback.values" not in count
    rows = [s["annotations"]["rows"] for s in inside
            if s["name"] == "tpu.lanes.decode"]
    assert rows == [60, 60, 60]
    # the three-shard stage went over the pool
    assert sum(n.startswith("post-load-compact")
               for n in decode_threads) == 3, decode_threads

    # the riders' wait: from the enqueue (inside the leader's own
    # dispatch) to the start of the dispatch that took them
    start, end = dispatch["start_ms"], \
        dispatch["start_ms"] + dispatch["duration_ms"]
    assert end - start >= 600.0
    waits = [s for s in snap if s["name"] == "admin.compact.wait"]
    assert len(waits) == 4
    riders = [w for w in waits if w["annotations"]["batch"] == 3]
    assert len(riders) == 3
    for w in riders:
        assert abs(w["start_ms"] + w["duration_ms"] - start) < 250.0
    (leader,) = [w for w in waits if w["annotations"]["batch"] == 1]
    assert leader["duration_ms"] < 250.0
    rides = [s for s in snap if s["name"] == "admin.compact.ride"]
    assert len(rides) == 3
    for r in rides:
        assert abs(r["start_ms"] + r["duration_ms"] - end) < 250.0


def test_index_path_values_cross_the_seam_in_spans_of_their_own(tmp_path):
    """Three shards of 64-byte values (the index path) over a pool: each
    shard's values go up in a ``tpu.h2d.values`` and come down in a
    ``tpu.readback.values`` span, on pool threads, beside the codec spans
    (whose sum is ``codec_ms_per_shard``) and never under them; the
    leader's ``tpu.h2d`` and ``tpu.readback`` (``h2d_ms``, ``readback_ms``
    match the names exactly) stay one a group."""
    from concurrent.futures import ThreadPoolExecutor

    from rocksplicator_tpu.storage.records import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter
    from rocksplicator_tpu.tpu import compaction_service as cs

    col = SpanCollector.get()
    col.configure(sample_rate=0.0, capacity=4096)
    dbs = []
    for s in range(3):
        db = DB(str(tmp_path / f"db{s}"))
        for i in range(30):
            db.write(WriteBatch().put(f"k{i:03d}".encode(), b"w" * 64))
        sst = tmp_path / f"in{s}.tsst"
        w = SSTWriter(str(sst))
        for i in range(10, 40):
            w.add(f"k{i:03d}".encode(), 0, OpType.PUT, bytes([s + 1]) * 64)
        w.finish()
        db.ingest_external_file([str(sst)], move_files=True,
                                allow_global_seqno=True)
        dbs.append((f"db{s}", db))
    with ThreadPoolExecutor(3, thread_name_prefix="seam-pool") as pool:
        with start_span("test.caller", always=True) as caller:
            handled, remaining = cs.compact_dbs_batched(dbs, pool=pool)
    assert sorted(handled) == ["db0", "db1", "db2"] and remaining == []
    for s, (_name, db) in enumerate(dbs):
        assert db.get(b"k015") == bytes([s + 1]) * 64
        db.close()

    inside = [s for s in col.snapshot() if s["trace_id"] == caller.trace_id]
    by_id = {s["span_id"]: s for s in inside}
    by_name = {}
    for s in inside:
        by_name.setdefault(s["name"], []).append(s)
    for name in PER_SHARD + ("tpu.h2d.values", "tpu.readback.values"):
        assert len(by_name.get(name, ())) == 3, (name, sorted(by_name))
    for name in PER_LAUNCH + ("tpu.compact_stream",):
        assert len(by_name.get(name, ())) == 1, (name, sorted(by_name))
    # siblings of the codec spans: the stage's / the install's children
    (stage,) = by_name["admin.compact_stage"]
    (install,) = by_name["admin.compact_install"]
    for name, parent, sibling in (
            ("tpu.h2d.values", stage, "tpu.lanes.decode"),
            ("tpu.readback.values", install, "tpu.planar.write")):
        for s in by_name[name]:
            assert by_id[s["parent_id"]] is parent, (name, s)
            assert s["annotations"]["bytes"] == 64 * 64  # bucket x width
        assert {s["parent_id"] for s in by_name[sibling]} == {
            parent["span_id"]}
    # up after its shard's decode ends, down before its shard's file
    # starts: the three pairs interleave, so compare the extremes
    assert (min(s["start_ms"] for s in by_name["tpu.h2d.values"])
            >= min(s["start_ms"] + s["duration_ms"]
                   for s in by_name["tpu.lanes.decode"]))
    assert (max(s["start_ms"] + s["duration_ms"]
                for s in by_name["tpu.readback.values"])
            <= max(s["start_ms"] for s in by_name["tpu.planar.write"]))
    (h2d,) = by_name["tpu.h2d"]
    assert h2d["annotations"]["prestaged"] == 3
    assert h2d["annotations"]["shards"] == 3


def test_device_programs_have_the_names_the_trace_readers_look_for():
    """An XLA module is named after the jitted function: the device
    trace's readers (chipbench/layers) find the programs by these."""
    import numpy as np

    from rocksplicator_tpu.ops import MergeKind, pack_entries
    from rocksplicator_tpu.ops.bloom_tpu import bloom_build_tpu
    from rocksplicator_tpu.storage.records import OpType
    from rocksplicator_tpu.tpu import compaction_service as cs

    batch = pack_entries([(b"k%02d" % i, i + 1, OpType.PUT, b"v" * 8)
                          for i in range(8)])
    fn = cs.TpuCompactionService()._pipeline(MergeKind.NONE, True, 64)
    args = [np.stack([getattr(batch, name)]) for name in cs._GROUP_LANES]
    assert "@jit_" + cs.PIPELINE_PROGRAM in fn.lower(*args).as_text()[:200]
    assert cs.PIPELINE_PROGRAM == "one_shard"
    text = bloom_build_tpu.lower(
        batch.key_words_le, batch.key_len, batch.valid,
        num_words=64).as_text()
    assert "@jit_bloom_build_tpu" in text[:200]


def test_clock_check_pairs_module_events_with_their_launch_spans():
    """tools/chip_clock_check.py on a hand-written recording: the first
    launch keeps order, the second's device event outlasts its readback
    span by 1,000 us, as two clocks that disagree would show."""
    from tools.chip_clock_check import clock_check

    ms = 1e6  # ns
    recording = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_one_shard(1)", 1 * ms, 9 * ms],
            ["jit_bloom_build_tpu(2)", 20 * ms, 0.3 * ms],
            ["jit_one_shard(1)", 500 * ms, 9 * ms]]}]}]}
    spans = [("tpu.compact_stream", 0.0, 30 * ms, "p1", None),
             ("tpu.dispatch", 0.2 * ms, 0.9 * ms, "a", "p1"),
             ("tpu.readback", 5 * ms, 12 * ms, "b", "p1"),
             ("tpu.dispatch", 499 * ms, 500.1 * ms, "c", "p2"),
             ("tpu.readback", 505 * ms, 508 * ms, "d", "p2")]
    assert clock_check(recording, (0.0, 1000 * ms), spans) == {
        "module_events": 2, "launch_pairs": 2,
        "events_ms": [[0.8, 0.7, 9.0, 7.0, 2.0], [1.0, 1.1, 9.0, 3.0, -1.0]],
        "least_lead_us": 800.0,
        "median_lead_us": 1000.0, "least_tail_us": -1000.0,
        "largest_violation_us": 1000.0}
    assert clock_check(recording, (0.0, 1000 * ms), spans[:1]) == {
        "module_events": 2, "launch_pairs": 0}


@pytest.mark.parametrize("straggler", [False, True],
                         ids=["all_join", "bound_runs_out"])
def test_leaders_linger_is_a_span_under_its_wait(straggler):
    """The leader of a group commit waits for the siblings the handler
    announced: admin.compact.linger, a child of the LEADER's
    admin.compact.wait (which therefore contains it), annotated with how
    many were expected when it began, how many joined, and whether the
    bound (the injected dispatch time) ran out first."""
    import threading

    from rocksplicator_tpu.admin.ingest_pipeline import BatchCompactor

    class Stub:
        def compact_range(self):
            pass

    col = SpanCollector.get()
    col.configure(sample_rate=0.0, capacity=4096)
    compactor = BatchCompactor(use_tpu=False, compact_parallelism=2)
    compactor._dispatch_s.append(0.2 if straggler else 60.0)
    tickets = [compactor.expect() for _ in range(3)]

    def submit(s):
        with start_span("test.caller", always=True, shard=s):
            compactor.compact(f"db{s}", Stub(), tickets[s])

    threads = [threading.Thread(target=submit, args=(s,)) for s in range(3)]
    try:
        threads[0].start()
        assert wait_until(lambda: compactor._dispatching, interval=0.005)
        time.sleep(0.02)  # the leader is in its linger, two are expected
        threads[1].start()
        if straggler:
            assert wait_until(lambda: compactor.dispatch_count == 1,
                              interval=0.005)
        threads[2].start()
        for t in threads:
            t.join(30)
            assert not t.is_alive()
    finally:
        compactor.close()
    assert compactor.batch_sizes == ([2, 1] if straggler else [3])

    (linger,) = _spans_by_name("admin.compact.linger")
    assert linger["annotations"] == {
        "expected": 2, "joined": 1 if straggler else 2,
        "timed_out": straggler}
    waits = {w["span_id"]: w for w in _spans_by_name("admin.compact.wait")}
    assert len(waits) == 3
    leader = waits[linger["parent_id"]]
    assert leader["trace_id"] == linger["trace_id"]
    assert leader["annotations"]["batch"] == (2 if straggler else 3)
    assert leader["duration_ms"] >= linger["duration_ms"]
    if straggler:
        assert linger["duration_ms"] >= 200.0 - 20.0  # one dispatch time
    riders = [w for w in waits.values() if w is not leader]
    assert all(w["duration_ms"] <= leader["duration_ms"] for w in riders)
