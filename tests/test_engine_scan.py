"""The engine's bounded, seeking scan (``DB.scan``, ``storage/scan.py``)
against a brute-force dict, over seeded random histories whose data lies
in L1, in overlapping L0 files, in immutable memtables and in the active
memtable: keys of 1 to 48 bytes and differing lengths, PUTs, DELETEs and
uint64-add MERGEs. Then a flush and a compaction installing in the
middle of a scan, the data plane's ``read`` RPC with ``op="scan"``, and
how many blocks a scan of a 20,480-row PLANAR version decodes."""

import os
import random
import struct

import pytest

from rocksplicator_tpu.observability.collector import SpanCollector
from rocksplicator_tpu.storage import DB, DBOptions, WriteBatch
from rocksplicator_tpu.storage.memtable import MemTable
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.scan import ScanCounts
from rocksplicator_tpu.utils.stats import Stats

U64 = struct.Struct("<Q")
MASK = (1 << 64) - 1


class Model:
    """The DB as a dict: PUT sets, DELETE removes, MERGE adds modulo
    2^64 onto the value (0 where there is none)."""

    def __init__(self):
        self.m = {}

    def apply(self, op, key, value):
        if op == "put":
            self.m[key] = value
        elif op == "delete":
            self.m.pop(key, None)
        else:
            base = self.m.get(key)
            total = (U64.unpack(base)[0] if base is not None
                     and len(base) == 8 else 0) + U64.unpack(value)[0]
            self.m[key] = U64.pack(total & MASK)

    def scan(self, start, limit, end=None):
        keys = sorted(k for k in self.m if (start is None or k >= start)
                      and (end is None or k < end))
        if limit is not None:
            keys = keys[:limit]
        return [(k, self.m[k]) for k in keys]


def key_pool(rng, n=160):
    """Keys of 1 to 48 bytes that share prefixes: truncations and
    extensions of each other, so that order by bytes is not order by
    length."""
    pool = set()
    while len(pool) < n:
        base = bytes(rng.choice(b"ab\x00\xfe") for _ in range(
            rng.randint(1, 48)))
        pool.add(base)
        pool.add(base[:rng.randint(1, len(base))])
        if len(base) < 48:
            pool.add(base + b"\x00")
    return sorted(pool)


def history(rng, keys, n, merges):
    ops = []
    for _ in range(n):
        key = rng.choice(keys)
        r = rng.random()
        if r < 0.5:
            value = (U64.pack(rng.getrandbits(64)) if merges
                     else os.urandom(rng.randint(0, 60)))
            ops.append(("put", key, value))
        elif r < 0.7 or not merges:
            ops.append(("delete", key, b""))
        else:
            ops.append(("merge", key, U64.pack(rng.getrandbits(64))))
    return ops


def write(db, model, ops, per_batch=7):
    for lo in range(0, len(ops), per_batch):
        wb = WriteBatch()
        for op, key, value in ops[lo:lo + per_batch]:
            if op == "put":
                wb.put(key, value)
            elif op == "delete":
                wb.delete(key)
            else:
                wb.merge(key, value)
            model.apply(op, key, value)
        db.write(wb)


def freeze_memtable(db):
    """The active memtable into the immutable queue, as the background
    flusher's swap leaves it (in inline mode nothing flushes it)."""
    with db._lock:
        db._imms.append(db._mem)
        db._mem = MemTable()


def layered_db(path, seed, merges):
    """A DB and its model: data in L1, in two overlapping L0 files, in
    two immutable memtables and in the active memtable."""
    rng = random.Random(seed)
    keys = key_pool(rng)
    opts = DBOptions(merge_operator=UInt64AddOperator() if merges else None,
                     disable_auto_compaction=True, block_bytes=256)
    db, model = DB(str(path), opts), Model()
    write(db, model, history(rng, keys, 400, merges))
    db.compact_range()
    for _ in range(2):
        write(db, model, history(rng, keys, 150, merges))
        db.flush()
    for _ in range(2):
        write(db, model, history(rng, keys, 60, merges))
        freeze_memtable(db)
    write(db, model, history(rng, keys, 60, merges))
    assert db._levels[0] and any(db._levels[1:])
    assert len(db._imms) == 2 and len(db._mem)
    return db, model, keys


def starts(model, keys):
    deleted = [k for k in keys if k not in model.m]
    live = sorted(model.m)
    return ([None, b"", b"\x00", live[0], live[len(live) // 2], live[-1],
             b"\xff" * 49] + deleted[:6] + keys[::17])


@pytest.mark.parametrize("merges", [True, False], ids=["uint64add", "puts"])
@pytest.mark.parametrize("seed", range(6))
def test_scan_matches_the_model(tmp_path, seed, merges):
    db, model, keys = layered_db(tmp_path / "db", seed, merges)
    try:
        assert model.m and any(k not in model.m for k in keys)
        for start in starts(model, keys):
            for limit in (1, 2, 7, 100, len(model.m) + 5):
                assert db.scan(start, limit) == model.scan(start, limit), (
                    start, limit)
        live = sorted(model.m)
        for lo, hi in ((None, None), (live[3], live[-3]), (b"a", b"b"),
                       (live[-1], None), (b"\xff", b"\xff\xff")):
            assert list(db.new_iterator(lo, hi)) == model.scan(lo, None, hi)
    finally:
        db.close()


@pytest.mark.parametrize("limit", [1, 40, 10_000])
def test_flush_and_compaction_mid_scan(tmp_path, limit):
    """A view taken, then a flush and a full compaction install and GC
    every file the view holds: the scan reads on from the files it
    pinned, which close when it lets them go, and its answer is the
    view's. A write after the view, of a new key or over a key the
    view holds, is not in it."""
    rng = random.Random(limit)
    keys = key_pool(rng)
    db = DB(str(tmp_path / "db"), DBOptions(
        merge_operator=UInt64AddOperator(), disable_auto_compaction=True,
        block_bytes=256))
    model = Model()
    try:
        for n in (300, 120, 120):
            write(db, model, history(rng, keys, n, True))
            db.flush()
        write(db, model, history(rng, keys, 50, True))
        want = model.scan(b"a", limit)
        view = db._scan_view(b"a", None)
        pinned = list(view.pinned)
        assert len(pinned) == 3
        first = want[0][0]
        late = [("put", b"a\x00new", b"late"), ("put", first, b"late")]
        write(db, Model(), late)
        db.compact_range()  # flushes the memtable, then GCs every file
        assert not any(os.path.exists(r._path) for r in pinned)
        assert all(r._fd >= 0 for r in pinned)
        try:
            assert view.take(b"a", None, limit, True, ScanCounts()) == want
        finally:
            view.release()
        assert all(r._fd < 0 for r in pinned)
        for op in late:
            model.apply(*op)
        assert db.scan(b"a", limit) == model.scan(b"a", limit)
    finally:
        db.close()


def test_scans_under_writes_flushes_and_compactions(tmp_path):
    """More scanning threads than cores, a shortened switch interval,
    and a writer whose every batch sets ALL the keys to one version
    while the background flusher and compactor install and GC files:
    each scan's answer is one version of every key (a point-in-time
    view), and no scan fails on a file closed under it."""
    import sys
    import threading
    import time

    keys = [b"k%03d" % i for i in range(50)]
    db = DB(str(tmp_path / "db"), DBOptions(
        background_compaction=True, memtable_bytes=16 << 10,
        level0_compaction_trigger=2, block_bytes=256))
    stop = threading.Event()
    seen, errors = [], []

    def writer():
        version = 0
        while not stop.is_set():
            version += 1
            wb = WriteBatch()
            for k in keys:
                wb.put(k, U64.pack(version) * 8)
            db.write(wb)

    def scanner():
        try:
            while not stop.is_set():
                got = db.scan(b"k", 100)
                assert [k for k, _v in got] == keys[:len(got)]
                seen.append({v for _k, v in got})
        except Exception as e:  # a failed scan is the finding
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    threads = [threading.Thread(target=writer)] + [
        threading.Thread(target=scanner)
        for _ in range((os.cpu_count() or 1) + 2)]
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
        db.close()
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors[:1]
    assert seen and all(len(versions) <= 1 for versions in seen)
    assert len({v for versions in seen for v in versions}) > 1


def test_close_while_pinned_waits_for_the_last_unpin(tmp_path):
    db = DB(str(tmp_path / "db"), DBOptions(disable_auto_compaction=True))
    db.put(b"k", b"v")
    db.flush()
    view = db._scan_view(None, None)
    (reader,) = view.pinned
    db.close()
    assert reader._fd >= 0
    assert view.take(None, None, 5, True, ScanCounts()) == [(b"k", b"v")]
    view.release()
    assert reader._fd < 0


def test_memtable_sorted_keys_follow_new_keys():
    mem = MemTable()
    rng = random.Random(3)
    seen = set()
    for round_ in range(5):
        for i in range(50):
            key = os.urandom(rng.randint(1, 20))
            seen.add(key)
            mem.apply(key, round_ * 100 + i + 1, 0, b"v")
        held = mem.sorted_keys()
        assert held == sorted(seen)
    assert mem.sorted_keys() is held  # nothing new: the same list
    # a scan's view leaves out what came after its sequence number
    mem.apply(b"\x00late", 10_000, 0, b"late")
    assert all(k != b"\x00late" for k, *_ in mem.seek(held, None, None,
                                                        9_999))


# -- the served path ---------------------------------------------------------

def test_scan_rpc_matches_the_model(tmp_path):
    """``read`` with ``op="scan"`` against the model, on a leader over
    TCP loopback; the scan's ``storage.scan`` span is a child of its
    ``rpc.server.read`` root, and a get's root has none."""
    from rocksplicator_tpu.replication import (ReplicaRole, Replicator,
                                               StorageDbWrapper)
    from rocksplicator_tpu.rpc import IoLoop
    from rocksplicator_tpu.rpc.client_pool import RpcClientPool

    SpanCollector.get().configure(sample_rate=0.0)
    rng = random.Random(7)
    keys = key_pool(rng)
    db = DB(str(tmp_path / "db"), DBOptions(
        merge_operator=UInt64AddOperator(), wal_ttl_seconds=3600.0,
        disable_auto_compaction=True, block_bytes=256))
    model = Model()
    write(db, model, history(rng, keys, 400, True))
    db.compact_range()
    write(db, model, history(rng, keys, 150, True))
    db.flush()
    write(db, model, history(rng, keys, 80, True))
    node = Replicator(port=0)
    node.add_db("seg00000", StorageDbWrapper(db), ReplicaRole.LEADER)
    ioloop, pool = IoLoop.default(), RpcClientPool()

    def read(**kw):
        async def go():
            return await pool.call("127.0.0.1", node.port, "read",
                                   dict(db_name="seg00000", **kw))
        return ioloop.run_sync(go(), timeout=10)

    try:
        for start in starts(model, keys)[1:]:
            for count in (1, 100):
                got = read(op="scan", start=start, count=count)["values"]
                assert [(bytes(k), bytes(v)) for k, v in got] == \
                    model.scan(start, count)
        live = sorted(model.m)
        got = read(op="get", keys=[live[0]])["values"]
        assert bytes(got[0]) == model.m[live[0]]
        spans = SpanCollector.get().snapshot()
        roots = {s["span_id"]: s for s in spans
                 if s["name"] == "rpc.server.read"}
        scans = [s for s in spans if s["name"] == "storage.scan"]
        assert scans and all(s["parent_id"] in roots for s in scans)
        get_root = [s for s in roots.values()
                    if s["span_id"] not in {c["parent_id"] for c in scans}]
        assert len(get_root) == 1
        # its children are its own phases (hop in, exec, hop out, reply)
        assert all(s["name"].startswith("rpc.server.read:") for s in spans
                   if s["parent_id"] == get_root[0]["span_id"])
        ann = scans[-1]["annotations"]
        assert ann["limit"] == 100 and ann["rows"] == len(
            model.scan(starts(model, keys)[-1], 100))
    finally:
        ioloop.run_sync(pool.close(), timeout=5)
        node.stop()
        db.close()


# -- what a scan reads -------------------------------------------------------

VERSION_ROWS = 20_480
VALUE_BYTES = 1_140  # a YCSB record as the RocksDB binding stores it


@pytest.fixture(scope="module")
def planar_version(tmp_path_factory):
    """One DB of 20,480 rows of 1,140-byte values under keys of differing
    length, loaded and compacted to PLANAR files of 64 rows a block."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter

    root = tmp_path_factory.mktemp("planar")
    rng = random.Random(11)
    keys = sorted({b"user%d" % rng.getrandbits(rng.choice((8, 40, 63)))
                   for _ in range(VERSION_ROWS + 200)})[:VERSION_ROWS]
    value = bytes(range(32, 127)) * 12
    src = str(root / "bulk.tsst")
    w = SSTWriter(src)
    for k in keys:
        w.add(k, 0, OpType.PUT, value[:VALUE_BYTES])
    w.finish()
    db = DB(str(root / "db"), DBOptions(disable_auto_compaction=True))
    db.ingest_external_file([src])
    db.compact_range()
    yield db, keys
    db.close()


@pytest.mark.parametrize("seed", range(3))
def test_a_short_scan_reads_three_blocks_a_file(planar_version, seed):
    """Scans of 1 to 100 rows from random keys of a 20,480-row PLANAR
    version decode at most 3 blocks a file, by the ``blocks``
    annotation of ``storage.scan``, and count in ``scan.*``."""
    db, keys = planar_version
    readers = [db._readers[n] for level in db._levels for n in level]
    files = len(readers)
    assert all(r._block_is_planar(0) for r in readers)
    # a scan that read every block of the version would read 200 or more
    assert sum(len(r._index) for r in readers) >= 200
    SpanCollector.get().configure(sample_rate=1.0)
    stats = Stats.get()
    before = {n: stats.get_counter(n)
              for n in ("scan.calls", "scan.rows", "scan.blocks")}
    rng = random.Random(seed)
    rows = 0
    for _ in range(40):
        i = rng.randrange(len(keys))
        n = rng.randint(1, 100)
        got = db.scan(keys[i], n)
        assert [k for k, _v in got] == keys[i:i + n]
        rows += len(got)
    scans = [s["annotations"] for s in SpanCollector.get().snapshot()
             if s["name"] == "storage.scan"][-40:]
    assert len(scans) == 40
    assert all(1 <= a["blocks"] <= 3 * a["files"] for a in scans)
    assert all(a["files"] <= files and a["mem_rows"] == 0 for a in scans)
    assert stats.get_counter("scan.calls") - before["scan.calls"] == 40
    assert stats.get_counter("scan.rows") - before["scan.rows"] == rows
    assert stats.get_counter("scan.blocks") - before["scan.blocks"] == sum(
        a["blocks"] for a in scans)
