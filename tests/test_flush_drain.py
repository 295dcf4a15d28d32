"""Round-9 array-native flush / compaction parity matrix.

The vectorized paths (MemTable.drain_lanes → lexsort → planar sink,
CpuCompactionBackend's direct array merge-resolve, the decoded-block
cache, batched multi_get, fence-bisect file lookup) must be
*entry-exact* with the per-entry paths they replace — these tests pin
that, including the shapes the lane representation can't express (which
must fall back, not corrupt):

- mixed PUT/DELETE/MERGE stacks, seq32 on/off, the exact u16 vlen
  boundary, non-uniform-width fallbacks;
- `wal.append` / `sst.fsync` failpoint trips behaving identically
  through the drain path;
- one MERGE-operand fold implementation (storage/merge) cross-checked
  between the scalar resolve and the array segment fold, including
  uint64 wraparound.
"""

import os
import struct

import numpy as np
import pytest

from rocksplicator_tpu.storage import (
    DB,
    DBOptions,
    OpType,
    UInt64AddOperator,
)
from rocksplicator_tpu.storage.bloom import BloomFilter
from rocksplicator_tpu.storage.compaction import (
    CpuCompactionBackend,
    resolve_stream,
)
from rocksplicator_tpu.storage.engine import _MergedMemView
from rocksplicator_tpu.storage.memtable import MemTable
from rocksplicator_tpu.storage.merge import (
    resolve_entry_group,
    uint64_wrap,
    uint64add_segment_sums,
)
from rocksplicator_tpu.storage.planar import PLANAR_MAX_VLEN
from rocksplicator_tpu.storage.sst import BlockCache, SSTReader, SSTWriter
from rocksplicator_tpu.testing import failpoints as fp
from rocksplicator_tpu.utils.stats import Stats

pack64 = struct.Struct("<q").pack


@pytest.fixture(autouse=True)
def _clean_process_state():
    fp.reset_for_test()
    BlockCache.reset_for_test()
    yield
    fp.reset_for_test()
    BlockCache.reset_for_test()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _entry_sink(path: str, mem) -> None:
    """The per-entry reference sink: exactly what _write_mem_sst falls
    back to (sorted tuple stream through SSTWriter.add)."""
    writer = SSTWriter(path)
    try:
        for key, seq, vtype, value in mem.entries():
            writer.add(key, seq, vtype, value)
        writer.finish()
    except BaseException:
        writer.abandon()
        raise


def _flush_both(tmp_path, mem, expect_planar):
    """Flush one memtable through the engine sink AND the per-entry
    reference sink; assert which path engaged and return both files'
    full entry streams."""
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        path_a = str(tmp_path / "a.tsst")
        db._write_mem_sst(path_a, mem)
    finally:
        db.close()
    path_b = str(tmp_path / "b.tsst")
    _entry_sink(path_b, mem)
    ra, rb = SSTReader(path_a), SSTReader(path_b)
    try:
        assert ("planar" in ra.props) == expect_planar, (
            f"expected planar={expect_planar}, props={list(ra.props)}")
        return list(ra.iterate()), list(rb.iterate())
    finally:
        ra.close()
        rb.close()


def _mixed_mem(n=400, big_seq=False, vlen=8):
    """Uniform-width mixed-op memtable with multi-entry stacks per key
    (PUT, MERGE and DELETE at distinct seqs on the same keys), applied
    in a non-sorted key order so the lexsort has real work."""
    mem = MemTable()
    base = (1 << 40) if big_seq else 0
    seq = 0
    for i in range(n):
        k = f"key{(i * 37) % n:08d}".encode()
        seq += 1
        mem.apply(k, base + seq, OpType.PUT, pack64(i).ljust(vlen, b"\0")[:vlen])
        if i % 3 == 0:
            seq += 1
            mem.apply(k, base + seq, OpType.MERGE,
                      pack64(1).ljust(vlen, b"\0")[:vlen])
        if i % 7 == 0:
            seq += 1
            mem.apply(k, base + seq, OpType.DELETE, b"")
    return mem


# ---------------------------------------------------------------------------
# flush parity matrix: drain→lexsort→planar vs per-entry sink
# ---------------------------------------------------------------------------


def test_flush_parity_mixed_ops(tmp_path):
    got_a, got_b = _flush_both(tmp_path, _mixed_mem(), expect_planar=True)
    assert got_a == got_b
    assert len(got_a) > 400  # stacks survived (no accidental resolve)


def test_flush_parity_seq_above_32bit(tmp_path):
    """seqs >= 2^32 force the wide (non-seq32) planar layout AND the
    lexsort's seq-desc tiebreak to use the full 64-bit seq."""
    got_a, got_b = _flush_both(
        tmp_path, _mixed_mem(big_seq=True), expect_planar=True)
    assert got_a == got_b
    ra = SSTReader(str(tmp_path / "a.tsst"))
    try:
        assert ra.props["planar"][2] == 0  # [klen, vlen, seq32]
    finally:
        ra.close()


def test_flush_parity_exact_u16_vlen_boundary(tmp_path):
    """vlen == 0xFFFF is the widest value planar can express (the
    round-2 overflow class) — must take the array path, exactly."""
    mem = MemTable()
    for i in range(6):
        mem.apply(f"key{i:08d}".encode(), i + 1, OpType.PUT,
                  bytes([i]) * PLANAR_MAX_VLEN)
    got_a, got_b = _flush_both(tmp_path, mem, expect_planar=True)
    assert got_a == got_b
    assert all(len(v) == PLANAR_MAX_VLEN for _k, _s, _t, v in got_a)


def test_flush_fallback_vlen_over_u16(tmp_path):
    """One byte past the u16 field: the drain must DECLINE (not
    truncate) and the per-entry sink must produce identical bytes."""
    mem = MemTable()
    for i in range(4):
        mem.apply(f"key{i:08d}".encode(), i + 1, OpType.PUT,
                  bytes([i]) * (PLANAR_MAX_VLEN + 1))
    assert mem.drain_lanes() is None
    got_a, got_b = _flush_both(tmp_path, mem, expect_planar=False)
    assert got_a == got_b


def test_flush_fallback_non_uniform_widths(tmp_path):
    """A value of another width falls back to the per-entry sink; a key
    of another LENGTH does not any more (tests/test_mixed_key_widths.py):
    the array flush takes it, and the file is the per-entry sink's
    entries all the same."""
    for mutate in ("klen", "vlen"):
        mem = _mixed_mem(64)
        if mutate == "klen":
            mem.apply(b"short", 10_000, OpType.PUT, pack64(1))
        else:
            mem.apply(b"key%05d" % 1, 10_000, OpType.PUT, b"wide-value-16b!!")
        assert (mem.drain_lanes() is None) == (mutate == "vlen")
        sub = tmp_path / mutate
        sub.mkdir()
        got_a, got_b = _flush_both(sub, mem, expect_planar=mutate == "klen")
        assert got_a == got_b


def test_drain_lanes_rejects_inexpressible_shapes():
    assert MemTable().drain_lanes() is None  # empty
    m = MemTable()
    m.apply(b"k" * 8, 1, OpType.DELETE, b"oops")  # DELETE carrying a value
    assert m.drain_lanes() is None
    m = MemTable()
    m.apply(b"k" * 25, 1, OpType.PUT, pack64(0))  # klen > PLANAR_MAX_KLEN
    assert m.drain_lanes() is None


def test_drain_lanes_sorts_nothing_but_expresses_order(tmp_path):
    """drain_lanes returns UNSORTED lanes; the flush lexsort must
    restore exact (key asc, seq desc) order from adversarial apply
    order."""
    mem = MemTable()
    rng = np.random.RandomState(7)
    for seq, i in enumerate(rng.permutation(500), start=1):
        # seq ascends (the engine invariant) but KEYS arrive shuffled,
        # so append order is nowhere near lane order
        mem.apply(f"key{int(i) % 50:08d}".encode(), seq, OpType.PUT,
                  pack64(seq))
    got_a, got_b = _flush_both(tmp_path, mem, expect_planar=True)
    assert got_a == got_b
    order = [(k, -s) for k, s, _t, _v in got_a]
    assert order == sorted(order)


def test_merged_memview_drain_parity(tmp_path):
    """Multi-memtable flush (the background burst path) drains each
    memtable's lanes and concatenates; one lexsort restores the global
    order. Parity against the merged per-entry stream."""
    mems = []
    seq = 0
    for part in range(3):
        m = MemTable()
        for i in range(100):
            seq += 1
            m.apply(f"key{(i * 13) % 80:08d}".encode(), seq,
                    OpType.PUT if i % 5 else OpType.DELETE,
                    pack64(seq) if i % 5 else b"")
        mems.append(m)
    view = _MergedMemView(mems)
    assert view.drain_lanes() is not None
    got_a, got_b = _flush_both(tmp_path, view, expect_planar=True)
    assert got_a == got_b
    # a VALUE width mismatch in ANY memtable declines the whole view
    # (checked per-part BEFORE any pad/concat, so the bail is O(parts)
    # not O(entries))
    odd_k = MemTable()  # a key of another length is lanes like any other
    odd_k.apply(b"odd-width-key", 9999, OpType.PUT, pack64(1))
    lanes, key_mat = _MergedMemView(mems + [odd_k]).drain_lanes()
    assert lanes["key_len"].tolist() == [11] * 300 + [13]
    assert key_mat.shape == (301, 13) and bytes(key_mat[0]) == (
        b"key00000000\0\0")
    bad_v = MemTable()
    bad_v.apply(b"key00000000", 9999, OpType.PUT, b"sixteen-byte-val")
    assert _MergedMemView(mems + [bad_v]).drain_lanes() is None
    # ...and an all-DELETE memtable constrains neither width
    all_del = MemTable()
    all_del.apply(b"key00000000", 10_000, OpType.DELETE, b"")
    assert _MergedMemView(mems + [all_del]).drain_lanes() is not None


# ---------------------------------------------------------------------------
# failpoints through the drain path
# ---------------------------------------------------------------------------


def _uniform_fill(db, n=300):
    for i in range(n):
        db.put(f"key{i:08d}".encode(), pack64(i))


def test_sst_fsync_failpoint_trips_through_drain(tmp_path):
    """The array sink finalizes through SSTWriter.finish, so an
    sst.fsync trip must fail the flush identically to the per-entry
    path: the flush raises, nothing is installed, a retry succeeds and
    the file that lands is the planar drain output."""
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        _uniform_fill(db)
        fp.activate("sst.fsync", "fail_nth:1")
        with pytest.raises(OSError):
            db.flush()
        assert db._levels[0] == []  # nothing half-installed
        fp.deactivate("sst.fsync")
        db.flush()
        assert db.get(b"key00000007") == pack64(7)
        name = db._levels[0][0]
        assert "planar" in db._readers[name].props  # drain path engaged
    finally:
        db.close()


def test_wal_torn_append_then_drain_flush_recovers(tmp_path):
    """A healed torn WAL append followed by a drain-path flush: the
    flushed planar SST and post-reopen state must reflect exactly the
    committed writes (chaos-smoke's hole-free-prefix invariant, pinned
    here at the unit level for the new flush path)."""
    db = DB(str(tmp_path / "db"), DBOptions(disable_auto_compaction=True))
    try:
        _uniform_fill(db, 50)
        fp.activate("wal.append", "torn:1.0,one_shot")
        with pytest.raises(OSError):
            db.put(b"key-torn-off", b"x" * 64)
        _uniform_fill(db, 60)  # overwrite + extend after the heal
        db.flush()
        name = db._levels[0][0]
        assert "planar" in db._readers[name].props
        assert db.get(b"key-torn-off") is None
        assert db.get(b"key00000059") == pack64(59)
    finally:
        db.close()
    db = DB(str(tmp_path / "db"), DBOptions(disable_auto_compaction=True))
    try:
        assert db.get(b"key-torn-off") is None
        assert db.get(b"key00000059") == pack64(59)
    finally:
        db.close()


# ---------------------------------------------------------------------------
# MERGE fold: one implementation, two faces
# ---------------------------------------------------------------------------


def test_uint64_wrap_matches_operator_overflow():
    op = UInt64AddOperator()
    near_max = (1 << 63) - 3
    got = op.merge(b"k", pack64(near_max), [pack64(10)])
    assert got == pack64(uint64_wrap(near_max + 10))
    # and the vectorized segment fold wraps identically (int64 overflow)
    vals = np.array([near_max, 10, 5, -7], dtype=np.int64)
    contrib = np.array([True, True, True, True])
    bounds = np.array([0, 2])  # segments [0:2], [2:4]
    sums = uint64add_segment_sums(vals, contrib, bounds)
    assert int(sums[0]) == uint64_wrap(near_max + 10)
    assert int(sums[1]) == uint64_wrap(5 - 7)


def test_resolve_stream_delegates_to_shared_fold():
    """storage/compaction._resolve_group IS storage/merge's
    resolve_entry_group — same output on a stacked group, including the
    keep-the-chain case with no operator."""
    group = [
        (b"k", 30, OpType.MERGE, pack64(5)),
        (b"k", 20, OpType.MERGE, pack64(7)),
        (b"k", 10, OpType.PUT, pack64(100)),
    ]
    op = UInt64AddOperator()
    assert resolve_entry_group(group, op, False) == [
        (b"k", 30, OpType.PUT, pack64(112))]
    assert list(resolve_stream(iter(group), op, False)) == [
        (b"k", 30, OpType.PUT, pack64(112))]
    # no operator: an all-MERGE chain survives intact (RocksDB stacking)
    chain = group[:2]
    assert resolve_entry_group(chain, None, False) == chain
    assert list(resolve_stream(iter(chain), None, False)) == chain


def test_array_vs_tuple_compaction_crosscheck(tmp_path):
    """Full-compaction A/B: the direct array sink vs the seed's
    heap-merge + per-entry stream, same writes (PUT/MERGE/DELETE with
    values crossing int64 overflow), byte-identical iteration — the
    single-source-of-truth cross-check the merge.py docstring names."""

    def build(path, backend):
        opts = DBOptions(memtable_bytes=1 << 30,
                         compaction_backend=backend,
                         merge_operator=UInt64AddOperator(),
                         disable_auto_compaction=True)
        db = DB(str(path), opts)
        for r in range(3):
            for i in range(120):
                k = f"key{(i * 11 + r) % 90:08d}".encode()
                m = (i + r) % 4
                if m == 0:
                    db.merge(k, pack64((1 << 62) + i))  # overflow fodder
                elif m == 1:
                    db.delete(k)
                else:
                    db.put(k, pack64(i))
            db.flush()
        db.compact_range()
        out = list(db.new_iterator())
        bottom = max(i for i, files in enumerate(db._levels) if files)
        props = [db._readers[n].props for n in db._levels[bottom]]
        db.close()
        return out, props

    out_a, props_a = build(tmp_path / "arr", CpuCompactionBackend())
    seed_backend = CpuCompactionBackend()
    seed_backend.merge_runs_to_files = None  # the engine's tuple path
    out_b, _props_b = build(tmp_path / "tup", seed_backend)
    assert out_a == out_b and out_a
    assert any("planar" in p for p in props_a)  # array sink engaged


def test_install_full_compaction_arrays_matches_entries(tmp_path):
    """The external-merger array install sink (install_full_compaction
    with ``arrays=``): resolved lanes install byte-identically to the
    same rows installed as ``entries=`` tuples, through planar files
    with the crash-safe manifest-then-GC order."""
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    def seeded_db(tag):
        db = DB(str(tmp_path / tag),
                DBOptions(memtable_bytes=1 << 30,
                          disable_auto_compaction=True))
        for i in range(500):
            db.put(f"key{i:08d}".encode(), pack64(i))
        db.flush()
        return db

    db_a = seeded_db("arrays")
    plan = db_a.plan_full_compaction()
    lanes = read_sst_arrays(db_a._readers[plan["inputs"][0]])
    count = int(lanes["key_len"].shape[0])
    db_a.install_full_compaction(plan, arrays=(lanes, count))
    out_a = list(db_a.new_iterator())
    bottom = plan["bottom"]
    assert db_a._levels[bottom] and all(
        "planar" in db_a._readers[n].props for n in db_a._levels[bottom])
    db_a.compact_range()  # mutex was released — a follow-up plan works
    db_a.close()

    db_b = seeded_db("entries")
    plan_b = db_b.plan_full_compaction()
    entries = list(db_b._readers[plan_b["inputs"][0]].iterate())
    db_b.install_full_compaction(plan_b, entries=entries)
    out_b = list(db_b.new_iterator())
    db_b.close()
    assert out_a == out_b and len(out_a) == 500


def test_install_full_compaction_arrays_empty_and_invalid(tmp_path):
    """count=0 installs an empty output set (fully-compacted-away); a
    lane dict planar can't express raises InvalidArgument, releases the
    plan mutex, and leaves the DB intact."""
    from rocksplicator_tpu.storage.errors import InvalidArgument
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        for i in range(100):
            db.put(f"key{i:08d}".encode(), pack64(i))
        db.flush()
        plan = db.plan_full_compaction()
        lanes = read_sst_arrays(db._readers[plan["inputs"][0]])
        lanes["key_len"] = lanes["key_len"].copy()
        lanes["key_len"][0] = 25  # over the lanes' 24 bytes: no planar row
        with pytest.raises(InvalidArgument):
            db.install_full_compaction(
                plan, arrays=(lanes, int(lanes["key_len"].shape[0])))
        assert db.get(b"key00000042") == pack64(42)  # untouched
        # mutex released on the raise: a fresh plan can proceed, and an
        # empty-arrays install compacts everything away
        plan2 = db.plan_full_compaction()
        db.install_full_compaction(plan2, arrays=({}, 0))
        assert all(not files for files in db._levels)
        assert db.get(b"key00000042") is None
    finally:
        db.close()


# ---------------------------------------------------------------------------
# multi_get: one lock pass, batch blooms, per-block grouping
# ---------------------------------------------------------------------------


def _layered_db(tmp_path):
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30,
                      merge_operator=UInt64AddOperator(),
                      disable_auto_compaction=True,
                      target_file_bytes=4 * 1024))
    # L1: compacted base
    for i in range(200):
        db.put(f"key{i:08d}".encode(), pack64(i))
    db.flush()
    db.compact_range()
    # L0: overwrites, deletes, merge operands
    for i in range(0, 200, 3):
        db.merge(f"key{i:08d}".encode(), pack64(1000))
    for i in range(0, 200, 7):
        db.delete(f"key{i:08d}".encode())
    db.flush()
    # memtable: freshest layer
    for i in range(0, 200, 5):
        db.put(f"key{i:08d}".encode(), pack64(i + 5))
    for i in range(0, 200, 11):
        db.merge(f"key{i:08d}".encode(), pack64(2000))
    return db


def test_multi_get_parity_with_get(tmp_path):
    db = _layered_db(tmp_path)
    try:
        keys = [f"key{i:08d}".encode() for i in range(0, 210)]
        keys += [b"missing-key", keys[3], keys[3]]  # absent + duplicates
        want = [db.get(k) for k in keys]
        got = db.multi_get(keys)
        assert got == want
    finally:
        db.close()


def test_multi_get_empty_and_order(tmp_path):
    db = _layered_db(tmp_path)
    try:
        assert db.multi_get([]) == []
        ks = [b"key00000199", b"nope", b"key00000000"]
        assert db.multi_get(ks) == [db.get(k) for k in ks]
    finally:
        db.close()


def test_bloom_may_contain_many_bit_exact():
    from rocksplicator_tpu.storage.bloom import hash_many

    keys = [f"k{i}".encode() * (1 + i % 5) for i in range(64)]
    bloom = BloomFilter.build(keys, bits_per_key=10)
    probes = keys + [f"absent{i}".encode() for i in range(64)]
    got = bloom.may_contain_many(probes)
    assert got.tolist() == [bloom.may_contain(k) for k in probes]
    assert got[: len(keys)].all()  # no false negatives
    # the hash-once-probe-many split (multi_get's multi-SST path) is
    # bit-exact with the one-shot probe against a DIFFERENT filter too
    h1, mask = hash_many(probes)
    assert bloom.may_contain_hashed(h1, mask).tolist() == got.tolist()
    other = BloomFilter.build(keys[:7], bits_per_key=14)
    assert other.may_contain_hashed(h1, mask).tolist() == [
        other.may_contain(k) for k in probes]


# ---------------------------------------------------------------------------
# fence-bisect file lookup
# ---------------------------------------------------------------------------


def test_fence_bisect_covers_file_boundaries(tmp_path):
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30,
                      disable_auto_compaction=True,
                      target_file_bytes=2 * 1024))
    try:
        # the array sink floors file splits at 1024 entries — 2500 keys
        # guarantee multiple bottom-level files to fence
        for i in range(2500):
            db.put(f"key{i:08d}".encode(), pack64(i))
        db.flush()
        db.compact_range()  # full compaction lands at the bottom level
        bottom = max(i for i, files in enumerate(db._levels) if files)
        assert bottom >= 1 and len(db._levels[bottom]) > 1
        # every key resolves through the bisect, including each file's
        # exact min/max fence keys
        for name in db._levels[bottom]:
            r = db._readers[name]
            for k in (r.min_key(), r.max_key()):
                i = int(k[3:])
                assert db.get(k) == pack64(i)
        assert db.get(b"key-off-the-end") is None
        assert bottom in db._fences  # fences were built
        # a new compaction generation invalidates them
        db.put(b"key00000001", pack64(1))
        db.flush()
        db.compact_range()
        assert bottom not in db._fences
        assert db.get(b"key00000001") == pack64(1)
    finally:
        db.close()


# ---------------------------------------------------------------------------
# decoded-block cache
# ---------------------------------------------------------------------------


def test_block_cache_hit_miss_counters(tmp_path):
    BlockCache.reset_for_test(capacity=8 << 20)
    Stats.reset_for_test()
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        _uniform_fill(db, 200)
        db.flush()
        db.get(b"key00000007")
        stats = Stats.get()
        misses0 = stats.get_counter("storage.block_cache.miss")
        assert misses0 >= 1
        hits0 = stats.get_counter("storage.block_cache.hit")
        db.get(b"key00000007")  # same block again
        assert stats.get_counter("storage.block_cache.hit") > hits0
        assert stats.get_counter("storage.block_cache.miss") == misses0
    finally:
        db.close()


def test_block_cache_budget_evicts(tmp_path):
    cap = 4096
    BlockCache.reset_for_test(capacity=cap)
    path = str(tmp_path / "f.tsst")
    w = SSTWriter(path, block_bytes=1024, compression=0)
    for i in range(400):
        w.add(f"key{i:08d}".encode(), i + 1, OpType.PUT, pack64(i) * 16)
    w.finish()
    r = SSTReader(path)
    try:
        for i in range(0, 400, 5):
            r.get(f"key{i:08d}".encode())
        cache = BlockCache.get_instance()
        st = cache.stats()
        assert 0 < st["bytes"] <= cap
    finally:
        r.close()


def test_block_cache_invalidated_on_close_and_gc(tmp_path):
    BlockCache.reset_for_test(capacity=8 << 20)
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        _uniform_fill(db, 200)
        db.flush()
        db.get(b"key00000003")
        cache = BlockCache.get_instance()
        assert cache.stats()["blocks"] > 0
        # compact_range GCs the L0 input file → its reader closes → its
        # cached blocks must die with it (a recycled name can never
        # serve stale bytes)
        db.compact_range()
        db.get(b"key00000003")
    finally:
        db.close()
    assert BlockCache.get_instance().stats()["blocks"] == 0


def test_block_cache_disabled_by_zero_capacity(tmp_path):
    BlockCache.reset_for_test(capacity=0)
    assert BlockCache.get_instance() is None
    db = DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30, disable_auto_compaction=True))
    try:
        _uniform_fill(db, 50)
        db.flush()
        assert db.get(b"key00000017") == pack64(17)  # reads still work
    finally:
        db.close()


# ---------------------------------------------------------------------------
# the memtable as columns (PR 33): a key's stack is a chain of rows; the
# same answers as a list of entry tuples a key, and nothing on the heap
# for the collector to walk
# ---------------------------------------------------------------------------


def _stack_ops(seed: int, keys: int, n: int):
    rng = np.random.default_rng(seed)
    kinds = (OpType.PUT, OpType.MERGE, OpType.DELETE)
    ops = []
    for i in range(n):
        kind = kinds[rng.integers(3)]
        ops.append((b"k%03d" % rng.integers(keys), i + 1, kind,
                    b"" if kind == OpType.DELETE
                    else pack64(int(rng.integers(100)))))
    return ops


def _model_get(stack, key, merge_op):
    """``MemTable.get`` over a plain newest-first list of entries."""
    operands = []
    for _seq, vtype, value in stack:
        if vtype in (OpType.PUT, OpType.DELETE):
            base = value if vtype == OpType.PUT else None
            if operands and merge_op:
                return True, merge_op.merge(key, base, operands[::-1]), []
            return True, base, []
        operands.append(value)
    return False, None, operands[::-1]


@pytest.mark.parametrize("keys,n", [(400, 300), (40, 300), (1, 50)],
                         ids=["mostly_single", "stacks", "one_key"])
@pytest.mark.parametrize("absorbed", [False, True])
def test_memtable_columns_answer_as_a_list_of_entries_a_key(keys, n,
                                                            absorbed):
    ops = _stack_ops(33, keys, n)
    model = {}
    for key, seq, vtype, value in ops:
        model.setdefault(key, []).insert(0, (seq, int(vtype), value))
    if absorbed:
        # the flush-failure path: the older half folded beneath the newer
        older, mem = MemTable(), MemTable()
        for op in ops[:n // 2]:
            older.apply(*op)
        for op in ops[n // 2:]:
            mem.apply(*op)
        mem.absorb_older(older)
        arrival = ops[n // 2:] + ops[:n // 2]
    else:
        mem = MemTable()
        for op in ops:
            mem.apply(*op)
        arrival = ops
    assert len(mem) == len(model)
    assert (mem.min_seq, mem.max_seq) == (1, n)
    assert list(mem.entries()) == [
        (k, *e) for k in sorted(model) for e in model[k]]
    for op in (UInt64AddOperator(), None):
        for key, stack in model.items():
            assert mem.get(key, op) == _model_get(stack, key, op)
        assert mem.get(b"absent", op) == (False, None, [])
    # the drain reads the columns: every op, in arrival order
    lanes, key_mat = mem.drain_lanes()
    assert lanes["vtype"].tolist() == [int(v) for _k, _s, v, _x in arrival]
    assert lanes["seq_lo"].tolist() == [s for _k, s, _v, _x in arrival]
    assert [bytes(r) for r in key_mat] == [k for k, _s, _v, _x in arrival]
    assert [bytes(r[:8]) if v != OpType.DELETE else b""
            for r, (_k, _s, v, _x) in zip(lanes["val_words"].view(np.uint8),
                                          arrival)] \
        == [x for _k, _s, _v, x in arrival]
    mem.apply(b"k000", n + 1, OpType.PUT, pack64(7))  # not pinned by a drain
    assert mem.get(b"k000", None) == (True, pack64(7), [])


def test_memtable_adds_no_container_a_key_or_an_entry():
    """Eight memtables fill before every bulk load: what they hold must
    not lengthen a full collection (PERF.md section 6, PR 33)."""
    import gc

    gc.collect()
    before = len(gc.get_objects())
    mem = MemTable()
    for i in range(2000):
        mem.apply(b"k%05d" % (i % 1800), i + 1, OpType.MERGE, pack64(i))
    gc.collect()
    assert len(mem) == 1800 and not gc.is_tracked(mem._newest)
    assert len(gc.get_objects()) - before < 50
