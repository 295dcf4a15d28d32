"""1 KB values through the device path and the host layers around it:
the batched post-load compaction against the plain reference
(``resolve_stream`` over a heap merge), the decline of a shard wider
than the device path takes, the served path (``write`` -> load + compact
-> ``get`` / ``multi_get``) against a dict, and the PLANAR codecs at
``vlen`` 1,024. XLA-CPU under conftest.py's explicit JAX_PLATFORMS=cpu.
"""

import heapq
import os
import random

import numpy as np
import pytest

from rocksplicator_tpu.observability.collector import SpanCollector
from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage.compaction import (host_fallback_counts,
                                                  resolve_stream)
from rocksplicator_tpu.storage.errors import Corruption
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
from rocksplicator_tpu.tpu import backend as tb
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.utils.stats import Stats

VLEN = 1024


def key(i: int) -> bytes:
    return b"s000-key%08d" % i


def counters(*names):
    stats = Stats.get()
    return {n: stats.get_counter(n) for n in names}


def make_db(path, rng, rows, live, vlen=VLEN, flush=True):
    """A DB with ``live`` PUTs (some keys twice, one DELETE) under a
    ``rows``-row bulk file ingested above them. Returns (db, the runs it
    holds as entry lists, oldest first)."""
    db = DB(str(path), DBOptions())
    writes = []
    for i in [rng.randrange(rows + 40) for _ in range(live)]:
        writes.append((key(i), OpType.PUT, rng.randbytes(vlen)))
    writes.append((key(rows + 7), OpType.DELETE, b""))
    for lo in range(0, len(writes), 32):
        wb = WriteBatch()
        for k, t, v in writes[lo:lo + 32]:
            wb.put(k, v) if t == OpType.PUT else wb.delete(k)
        db.write(wb)
    if flush:
        db.flush()
    seq = db.latest_sequence_number()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    bulk = [(key(i), rng.randbytes(vlen)) for i in range(rows)]
    for k, v in bulk:
        w.add(k, 0, OpType.PUT, v)
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    live_run = sorted(
        ((k, s + 1, t, v) for s, (k, t, v) in enumerate(writes)),
        key=lambda e: (e[0], -e[1]))
    bulk_run = [(k, seq + 1, OpType.PUT, v) for k, v in bulk]
    return db, [live_run, bulk_run]


def reference(runs, drop=True):
    merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
    return {k: v for k, _s, t, v in resolve_stream(merged, None, drop)
            if t == OpType.PUT}


def test_batched_compaction_of_1kb_records_matches_resolve_stream(tmp_path):
    """3 DBs of 1 KB records, a bulk file above live PUTs: one launch on
    the index path, every output byte as the plain reference has it."""
    SpanCollector.reset_for_test()
    SpanCollector.get().configure(sample_rate=1.0)
    rng = random.Random(29)
    before = counters("compact.value_path.index", "compact.value_path.ride",
                      "codec.python_files")
    fallbacks = host_fallback_counts()
    dbs, want = [], []
    for s in range(3):
        db, runs = make_db(tmp_path / f"db{s}", rng, rows=150, live=60)
        dbs.append((f"db{s}", db))
        want.append(reference(runs))
    handled, remaining = cs.compact_dbs_batched(dbs)
    assert sorted(handled) == ["db0", "db1", "db2"] and remaining == []
    after = counters(*before)
    assert after["compact.value_path.index"] - before[
        "compact.value_path.index"] == 3
    assert after["compact.value_path.ride"] == before[
        "compact.value_path.ride"]
    assert after["codec.python_files"] == before["codec.python_files"]
    assert host_fallback_counts() == fallbacks
    for (_name, db), model in zip(dbs, want):
        assert all(not files for files in db._levels[:-1])  # one run
        assert dict(db.new_iterator()) == model  # every key, every byte
        for k in list(model)[::5] + [key(150 + 7), key(150 + 39)]:
            assert db.get(k) == model.get(k)
        db.close()
    (stream,) = [s for s in SpanCollector.get().snapshot()
                 if s["name"] == "tpu.compact_stream"]
    assert stream["annotations"]["value_path"] == "index"
    assert stream["annotations"]["val_words"] == VLEN // 4
    SpanCollector.reset_for_test()


def test_8_byte_values_without_an_operator_still_ride(tmp_path):
    SpanCollector.reset_for_test()
    SpanCollector.get().configure(sample_rate=1.0)
    rng = random.Random(5)
    db, runs = make_db(tmp_path / "db", rng, rows=64, live=20, vlen=8)
    handled, _ = cs.compact_dbs_batched([("db", db)])
    assert handled == ["db"]
    for k, v in reference(runs).items():
        assert db.get(k) == v
    db.close()
    (stream,) = [s for s in SpanCollector.get().snapshot()
                 if s["name"] == "tpu.compact_stream"]
    assert stream["annotations"]["value_path"] == "ride"
    SpanCollector.reset_for_test()


@pytest.mark.parametrize("case", ["over_the_limit", "uint64add_wide"])
def test_shard_wider_than_the_device_takes_is_declined_without_a_compile(
        case, tmp_path, monkeypatch):
    """Declined before any program is built: counted under its own
    reason, the DB untouched and compactable on the host path."""
    built = []
    monkeypatch.setattr(
        cs.TpuCompactionService, "_pipeline",
        lambda self, *a, **k: built.append(a) or pytest.fail("a program"))
    if case == "over_the_limit":
        options, vlen = DBOptions(), tb.device_value_bytes_max(None) + 4
    else:
        options, vlen = DBOptions(merge_operator=UInt64AddOperator()), 16
    assert vlen > tb.device_value_bytes_max(options.merge_operator)
    db = DB(str(tmp_path / "db"), options)
    for i in range(20):
        db.write(WriteBatch().put(key(i), bytes([65 + i]) * vlen))
    db.flush()
    for i in range(10, 30):
        db.write(WriteBatch().put(key(i), bytes([97 + i]) * vlen))
    db.flush()
    was = host_fallback_counts().get("value_width", 0)
    handled, remaining = cs.compact_dbs_batched([("db", db)])
    assert handled == [] and [n for n, _ in remaining] == ["db"]
    assert host_fallback_counts()["value_width"] == was + 1
    assert not built
    db.compact_range()  # the plan's mutex was handed back
    assert db.get(key(5)) == bytes([65 + 5]) * vlen
    assert db.get(key(15)) == bytes([97 + 15]) * vlen
    db.close()


def test_what_the_device_path_takes_is_one_function():
    assert cs.device_value_bytes_max is tb.device_value_bytes_max
    assert tb.device_value_bytes_max(None) >= VLEN
    assert tb.device_value_bytes_max(UInt64AddOperator()) == 8

    class Custom(UInt64AddOperator.__mro__[1]):
        pass

    assert tb.device_value_bytes_max(Custom()) == 0


def test_served_path_1kb_write_load_compact_get(tmp_path):
    """``write`` -> ``add_s3_sst_files_to_db(compact_db_after_load)`` ->
    ``get`` / ``multi_get`` of every key against a dict, over the wire,
    through ``AdminHandler(tpu_compaction=True)``: no host fallback, no
    file through a Python codec."""
    from chipbench import cluster as cl
    from rocksplicator_tpu.rpc import IoLoop, RpcClientPool
    from rocksplicator_tpu.utils.objectstore import LocalObjectStore
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    rng = random.Random(41)
    rows, model = 256, {}
    fallbacks = host_fallback_counts()
    before = counters("codec.python_files", "compact.value_path.index")
    cluster = cl.Cluster(str(tmp_path), {
        "merge_operator": None, "wal_ttl_seconds": 3600, "bits_per_key": 10,
        "background_compaction": True}, in_flight=2)
    pool, loop = RpcClientPool(), IoLoop.default()

    def call(port, method, **args):
        return loop.run_sync(pool.call("127.0.0.1", port, method, args,
                                       timeout=120), timeout=150)

    try:
        db = segment_to_db_name("seg-v0", 0)
        call(cluster.server.port, "add_db", db_name=db, role="LEADER")
        live = [key(rng.randrange(rows)) for _ in range(90)]
        live += [b"s000-liv%08d" % i for i in range(8)] * 3
        rng.shuffle(live)
        for lo in range(0, len(live), 32):
            wb = WriteBatch()
            for k in live[lo:lo + 32]:
                model[k] = rng.randbytes(VLEN)
                wb.put(k, model[k])
            call(cluster.replicator.port, "write", db_name=db,
                 raw_batch=wb.encode())
        sst = str(tmp_path / "bulk.tsst")
        w = SSTWriter(sst)
        for i in range(rows):
            model[key(i)] = rng.randbytes(VLEN)  # lands above the live PUTs
            w.add(key(i), 0, OpType.PUT, model[key(i)])
        w.finish()
        bucket = str(tmp_path / "bucket")
        LocalObjectStore(bucket).put_object(sst, "sst/bulk.tsst")
        res = call(cluster.server.port, "add_s3_sst_files_to_db", db_name=db,
                   s3_bucket=bucket, s3_path="sst",
                   compact_db_after_load=True)
        assert res.get("ingested_files") == 1
        keys = sorted(model) + [b"s000-nil%08d" % i for i in range(4)]
        for k in keys[::7]:
            (got,) = call(cluster.replicator.port, "read", db_name=db,
                          op="get", keys=[k])["values"]
            assert (None if got is None else bytes(got)) == model.get(k)
        for lo in range(0, len(keys), 64):
            chunk = keys[lo:lo + 64]
            got = call(cluster.replicator.port, "read", db_name=db,
                       op="multi_get", keys=chunk)["values"]
            assert [None if v is None else bytes(v) for v in got] == [
                model.get(k) for k in chunk]
        assert cluster.launches() == 1
    finally:
        loop.run_sync(pool.close(), timeout=30)
        cluster.close()
    assert host_fallback_counts() == fallbacks
    after = counters(*before)
    assert after["codec.python_files"] == before["codec.python_files"]
    assert after["compact.value_path.index"] == before[
        "compact.value_path.index"] + 1


@pytest.mark.parametrize("compression", [0, 1])
def test_planar_codecs_round_trip_at_vlen_1024(compression, tmp_path):
    """The PLANAR sink and the lane source at 1 KB values (31 rows a
    32 KB block, a tail block): lanes in, the same lanes out, one native
    call each way; a flipped byte raises Corruption."""
    from rocksplicator_tpu.storage.native.binding import get_file_codecs
    from rocksplicator_tpu.tpu.format import (read_sst_arrays,
                                              write_sst_from_arrays)

    assert get_file_codecs() is not None
    n, rng = 200, np.random.default_rng(3)
    kb = np.zeros((n, 24), np.uint8)
    for i in range(n):
        kb[i, :16] = np.frombuffer(key(i), np.uint8)
    arrays = {
        "key_words_be": kb.view(">u4").astype(np.uint32),
        "key_words_le": kb.view("<u4").astype(np.uint32),
        "key_len": np.full(n, 16, np.uint32),
        "seq_hi": np.zeros(n, np.uint32),
        "seq_lo": np.arange(1, n + 1, dtype=np.uint32),
        "vtype": np.ones(n, np.uint32),
        "val_words": rng.integers(0, 1 << 32, (n, VLEN // 4),
                                  dtype=np.uint32),
        "val_len": np.full(n, VLEN, np.uint32),
    }
    arrays["vtype"][5], arrays["val_len"][5] = 2, 0  # a kept tombstone
    arrays["val_words"][5] = 0
    path = str(tmp_path / "wide.tsst")
    before = counters("codec.native_files", "codec.python_files")
    props = write_sst_from_arrays(arrays, n, path, block_entries=31,
                                  compression=compression, planar=True)
    assert props is not None
    reader = SSTReader(path)
    assert reader.props["planar"][:2] == [16, VLEN]
    assert len(reader._index) == -(-n // 31)
    lanes = read_sst_arrays(reader)
    after = counters(*before)
    assert after["codec.native_files"] == before["codec.native_files"] + 2
    assert after["codec.python_files"] == before["codec.python_files"]
    for name in ("key_words_be", "key_len", "seq_lo", "vtype", "val_len",
                 "val_words"):
        np.testing.assert_array_equal(lanes[name], arrays[name], err_msg=name)
    assert reader.get(key(7))[2] == arrays["val_words"][7].tobytes()
    reader.close()

    raw = bytearray(open(path, "rb").read())
    raw[os.path.getsize(path) // 3] ^= 0x40  # inside a data block
    bad = str(tmp_path / "bad.tsst")
    open(bad, "wb").write(bytes(raw))
    reader = SSTReader(bad)
    with pytest.raises(Corruption):
        read_sst_arrays(reader)
    reader.close()
