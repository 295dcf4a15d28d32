"""The index path's values across the host-device seam (PR 32): a shard
of the served door (``compact_dbs_batched``) is decoded straight into a
buffer padded to its capacity bucket, which the thread that decoded it
puts on the device; its resolved block stays on the device until the
thread that writes it reads it back. Against the same shards with the
hand-over bypassed (the leader pads, puts and reads every block, as
before PR 32): equal files, byte for byte. XLA-CPU under conftest.py's
explicit JAX_PLATFORMS=cpu.
"""

import os
import random

import numpy as np
import pytest

from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage import native_compaction as nc
from rocksplicator_tpu.storage.compaction import host_fallback_counts
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.utils.stats import Stats

VLEN = 64  # 16 value words: past RIDE_MAX_VAL_WORDS, so the index path
SEAM = ("seam.values.prestaged", "seam.values.restaged")


def key(i: int) -> bytes:
    return b"s000-key%08d" % i


def seam_counters():
    return [int(Stats.get().get_counter(n)) for n in SEAM]


def make_db(path, seed, rows, live=40, vlen=VLEN, keep_tombstones=False):
    """A DB of ``vlen``-byte records: ``live`` flushed PUTs and DELETEs
    under a ``rows``-row bulk file. The same ``seed`` gives the same
    DB, whatever the path."""
    rng = random.Random(seed)
    db = DB(str(path), DBOptions(allow_ingest_behind=keep_tombstones))
    wb = WriteBatch()
    for n in range(live):
        i = rng.randrange(rows + 20)
        wb.delete(key(i)) if n % 7 == 3 else wb.put(key(i),
                                                    rng.randbytes(vlen))
    db.write(wb)
    db.flush()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    for i in range(rows):
        w.add(key(i), 0, OpType.PUT, rng.randbytes(vlen))
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    return db


def sst_files(db) -> list:
    """Every SST of ``db``'s directory: (path, bytes), in the order the
    DB named them (a name is a per-DB random tag and a file number)."""
    names = sorted(n for n in os.listdir(db.path) if n.endswith(".tsst"))
    return [(os.path.join(db.path, n),
             open(os.path.join(db.path, n), "rb").read()) for n in names]


def bypass_the_hand_over(monkeypatch):
    """The launch gets the host lanes alone, as before PR 32: the
    leader's thread pads and puts each block and reads each back."""
    real = cs._LaneBatch
    monkeypatch.setattr(cs, "_LaneBatch",
                        lambda lanes, val_words_dev=None: real(lanes))


def compact_twins(tmp_path, monkeypatch, shard_rows, **db_kw):
    """The same shards compacted twice, in one group each: handed over
    per shard, then bypassed. Returns both sides' DBs and what the seam
    counters gained on each side."""
    sides, gained = [], []
    for side in ("handed", "bypassed"):
        if side == "bypassed":
            bypass_the_hand_over(monkeypatch)
        dbs = [(f"db{s}", make_db(tmp_path / side / f"db{s}", 100 + s,
                                  rows, **db_kw))
               for s, rows in enumerate(shard_rows)]
        before = seam_counters()
        handled, remaining = cs.compact_dbs_batched(dbs)
        assert sorted(handled) == [n for n, _ in dbs] and remaining == []
        gained.append([a - b for a, b in zip(seam_counters(), before)])
        sides.append([db for _n, db in dbs])
    return sides, gained


def assert_equal_files(sides):
    handed, bypassed = sides
    for a, b in zip(handed, bypassed):
        files = [data for _path, data in sst_files(a)]
        # every byte, the blooms among them
        assert files and files == [data for _path, data in sst_files(b)]
        assert dict(a.new_iterator()) == dict(b.new_iterator())
        a.close()
        b.close()


@pytest.mark.parametrize("shards, keep_tombstones", [
    (1, False), (3, False), (8, False), (3, True)],
    ids=["1", "3", "8", "3-tombstones-kept"])
def test_handed_over_values_give_the_files_of_the_leaders_copies(
        shards, keep_tombstones, tmp_path, monkeypatch):
    """Every plan but the last drops the tombstones its shard holds."""
    fallbacks = host_fallback_counts()
    sides, gained = compact_twins(
        tmp_path, monkeypatch, [150] * shards,
        keep_tombstones=keep_tombstones)
    assert gained == [[shards, 0], [0, 0]]
    assert host_fallback_counts() == fallbacks
    kept = sum(t == OpType.DELETE for db in sides[0]
               for path, _ in sst_files(db)
               for _k, _s, t, _v in SSTReader(path).iterate())
    assert (kept > 0) == keep_tombstones
    assert_equal_files(sides)


def test_a_shard_of_a_smaller_bucket_goes_up_again(tmp_path, monkeypatch):
    """Shards of 256-, 1,024- and 1,024-row buckets in one group of
    capacity 1,024: the small one's buffer is of another shape than the
    program takes, so the leader pads and puts its host values, and it
    is counted."""
    sides, gained = compact_twins(tmp_path, monkeypatch, [150, 600, 700])
    assert gained == [[2, 1], [0, 0]]
    assert_equal_files(sides)


def test_reader_without_the_argument_returns_the_parents_arrays(tmp_path):
    """``read_runs_as_lanes`` as its other callers use it (the engine
    door, the host array path): arrays that own their rows, ``total`` of
    them. With ``value_rows``: the same values as the first rows of a
    zero-tailed buffer of the rows asked for, every other lane as it
    was."""
    db = make_db(tmp_path / "db", 7, rows=150)
    plan = db.plan_full_compaction()
    try:
        _parts, lanes, total, vw = nc.read_runs_as_lanes(plan["runs"], None)
        asked = []
        _parts, padded, total2, vw2 = nc.read_runs_as_lanes(
            plan["runs"], None,
            value_rows=lambda t, w: asked.append((t, w)) or 256)
    finally:
        db.abort_full_compaction(plan)
        db.close()
    assert (total2, vw2) == (total, vw) == (190, VLEN // 4)
    assert asked == [(total, vw)]
    assert list(lanes) == list(padded) == [
        "key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
        "val_words", "val_len"]
    for name, arr in lanes.items():
        assert arr.shape[0] == total and arr.base is None, name
        assert arr.dtype == padded[name].dtype
        assert np.array_equal(arr, padded[name]), name
        if name != "val_words":
            assert padded[name].base is None
    assert lanes["val_words"].shape == (total, vw)
    buf = padded["val_words"].base
    assert buf.shape == (256, vw) and buf.dtype == np.uint32
    assert buf.flags["C_CONTIGUOUS"] and not buf[total:].any()
    assert np.shares_memory(buf[:total], padded["val_words"])


@pytest.mark.parametrize("fault", ["launch", "readback"])
def test_a_fault_after_the_uploads_hands_every_mutex_back(
        fault, tmp_path, monkeypatch):
    """The launch raises with every shard's values on the device already,
    or one shard's block does not come back: the shards concerned are
    handed to the per-db path with their plan's mutex released, and that
    path compacts them."""
    lost = []

    class Lost:
        nbytes = 0

        def __array__(self, *a, **kw):
            lost.append(1)
            raise RuntimeError("injected: the block did not come back")

    if fault == "launch":
        def boom(self, *a, **kw):
            raise RuntimeError("injected: launch failed")

        monkeypatch.setattr(
            cs.TpuCompactionService, "compact_shard_stream", boom)
    else:
        real = cs._shard_result

        def shard_result(host, s, count, return_arrays):
            res = real(host, s, count, return_arrays)
            if s == 1:
                assert not isinstance(res["arrays"]["val_words"], np.ndarray)
                res["arrays"]["val_words"] = Lost()
            return res

        monkeypatch.setattr(cs, "_shard_result", shard_result)
    dbs = [(f"db{s}", make_db(tmp_path / f"db{s}", 100 + s, 150))
           for s in range(3)]
    models = [dict(db.new_iterator()) for _n, db in dbs]
    before, fallbacks = seam_counters(), host_fallback_counts()
    handled, remaining = cs.compact_dbs_batched(dbs)
    if fault == "launch":
        assert handled == [] and len(remaining) == 3
        assert host_fallback_counts() == dict(
            fallbacks, batched_launch=fallbacks.get("batched_launch", 0) + 1)
    else:
        assert sorted(handled) == ["db0", "db2"] and lost == [1]
        assert [n for n, _ in remaining] == ["db1"]
        assert seam_counters()[0] - before[0] == 3
    for (_n, db), model in zip(dbs, models):
        db.compact_range()  # the mutex came back: this cannot hang
        assert all(not files for files in db._levels[:-1])
        assert dict(db.new_iterator()) == model
        db.close()
