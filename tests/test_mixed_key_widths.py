"""Keys of DIFFERING length in one shard (PR 35): 1 to 24 bytes, mixed in
any proportion, through every array path, against a dict model and the
tuple path.

- the PLANAR codecs, Python and native, block for block: same bytes,
  same lanes, a key-length plane exactly where a block's rows differ;
  the native row-format lane source against ``pack_entries``;
- the one order (zero-padded big-endian words, then length = bytewise):
  ``counter-1`` < ``counter-10`` < ``counter-2``, a key that is another's
  prefix, keys that differ in trailing NUL bytes only;
- the array flush against the per-entry sink;
- the served door (``compact_dbs_batched``) riding, on the index path and
  cut by key range; the engine-seam door; the host array path, whole and
  in key-range slices; point reads and scans after each;
- a key over the lanes' 48 bytes: ``key_width``, and both doors decline
  it to the host (keys of 25 to 48 bytes: tests/test_mixgraph_records.py);
- files of ONE key length are byte for byte what the parent commit wrote
  (and so files written before this PR read back); an old-layout block
  reads back;
- keys all of one length are the control of every case.

XLA-CPU under conftest.py's explicit JAX_PLATFORMS=cpu.
"""

import hashlib
import heapq
import os
import random
import struct

import numpy as np
import pytest

import rocksplicator_tpu.storage.native_compaction as nc
import rocksplicator_tpu.storage.stream_merge as sm
from rocksplicator_tpu.ops.kv_format import pack_entries
from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage.compaction import (host_fallback_counts,
                                                  resolve_stream)
from rocksplicator_tpu.storage.memtable import MemTable
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.native.binding import get_file_codecs
from rocksplicator_tpu.storage.planar import (PLANAR_FLAG_KLENS,
                                              PLANAR_FLAG_SEQ32,
                                              decode_planar_block,
                                              encode_planar_block,
                                              iter_planar_block,
                                              pack_planar_header,
                                              unpack_planar_header)
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
from rocksplicator_tpu.tpu import backend as tb
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.tpu import format as fmt
from rocksplicator_tpu.utils.stats import Stats

P, D, M = 1, 2, 3
pack64 = struct.Struct("<Q").pack
MASK64 = (1 << 64) - 1
LANES = ("key_words_be", "key_words_le", "key_len", "seq_hi", "seq_lo",
         "vtype", "val_words", "val_len")
KEYS = ("mixed", "names", "uniform")  # uniform: the control


def keys_of(kind: str, n: int, seed: int = 35):
    """``n`` distinct keys, in bytewise order. ``mixed``: 1 to 24 bytes,
    prefixes of one another, keys that differ in trailing NULs only;
    ``names``: the counter service's ``counter-<n>``; ``uniform``: all of
    one length."""
    rng = random.Random(seed)
    if kind == "uniform":
        return [b"k%015d" % i for i in sorted(rng.sample(range(10 ** 6), n))]
    if kind == "names":
        return sorted(b"counter-%d" % i
                      for i in rng.sample(range(2 * 10 ** 6), n))
    stems = [rng.randbytes(rng.randrange(1, 10)) for _ in range(max(2, n // 8))]
    keys = set()
    while len(keys) < n:
        stem = rng.choice(stems)
        how = rng.randrange(4)
        if how == 1:  # the stem with NUL bytes behind it, and nothing else
            stem += b"\0" * rng.randrange(1, 4)
        elif how == 2:  # the stem as a prefix
            stem += rng.randbytes(rng.randrange(1, 15))
        elif how == 3:
            stem = bytes(rng.choices(b"\0\1ab", k=rng.randrange(1, 25)))
        keys.add(stem[:24])
    return sorted(keys)


def lanes_of(entries, val_bytes=8):
    b = pack_entries(entries, val_bytes=val_bytes)
    return {f: getattr(b, f) for f in LANES}


def file_entries(paths):
    out = []
    for p in paths:
        r = SSTReader(p)
        out.extend((k, int(t), bytes(v)) for k, _s, t, v in r.iterate())
        r.close()
    return out


def db_files(db):
    return [os.path.join(db.path, n) for level in db._levels for n in level]


def python_codecs(monkeypatch):
    monkeypatch.setattr(fmt, "get_file_codecs", lambda: None)


# ---------------------------------------------------------------------------
# the codecs, block for block
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("compression", [0, 1, 4], ids=["none", "zlib", "rlz"])
@pytest.mark.parametrize("kind", KEYS)
def test_planar_codecs_agree_block_for_block(kind, compression, tmp_path,
                                             monkeypatch):
    keys = keys_of(kind, 700)
    entries = [(k, (7 * i) % 1000 + 1, P if i % 5 else D,
                pack64(i) if i % 5 else b"") for i, k in enumerate(keys)]
    arrays = lanes_of(entries)
    n, be = len(entries), 64
    assert fmt.planar_widths(arrays, n) == (
        max(map(len, keys)), 8, kind != "uniform", False)
    assert get_file_codecs() is not None
    native, python = str(tmp_path / "n.tsst"), str(tmp_path / "p.tsst")
    was = Stats.get().get_counter("codec.python_files")
    props = fmt.write_sst_from_arrays(
        arrays, n, native, block_entries=be, compression=compression,
        planar=True)
    assert Stats.get().get_counter("codec.python_files") == was
    with monkeypatch.context() as m:
        python_codecs(m)
        assert fmt.write_sst_from_arrays(
            arrays, n, python, block_entries=be, compression=compression,
            planar=True) == props
        lanes_py = fmt.read_sst_arrays(SSTReader(python))
    assert open(native, "rb").read() == open(python, "rb").read()
    assert props["planar"] == [max(map(len, keys)), 8, 1] + [1] * (
        kind != "uniform")

    reader = SSTReader(native)
    lanes = fmt.read_sst_arrays(reader)  # the native source
    assert Stats.get().get_counter("codec.python_files") == was + 2
    for f in LANES:
        assert np.array_equal(lanes[f], lanes_py[f]), f
        assert np.array_equal(lanes[f], arrays[f][:n]), f
    flagged = 0
    for bi, start in enumerate(range(0, n, be)):
        raw = reader._read_block(bi, fill_cache=False)
        rows = entries[start:start + be]
        lens = {len(k) for k, *_ in rows}
        count, klen, vlen, flags = unpack_planar_header(raw)
        # the key-length plane exactly where the block's rows differ
        assert bool(flags & PLANAR_FLAG_KLENS) == (len(lens) > 1)
        assert (count, klen, vlen) == (len(rows), max(lens), 8)
        flagged += bool(flags & PLANAR_FLAG_KLENS)
        assert raw == encode_planar_block(
            arrays, start, start + len(rows), klen, 8, True,
            mixed=kind != "uniform")
        block = decode_planar_block(raw)
        for f in LANES:
            assert np.array_equal(block[f],
                                  lanes[f][start:start + len(rows)]), f
        assert [(k, s, t, v) for k, s, t, v in iter_planar_block(raw)] == [
            (k, s, t, v) for k, s, t, v in rows]
    assert (flagged > 0) == (kind != "uniform")
    # point reads through the native block search, and keys never written
    for k, _s, t, v in entries[::3]:
        got = reader.get(k)
        assert got is not None and (got[1], got[2]) == (t, v)
    have = set(keys)
    for k in keys[::5]:
        for miss in (k + b"\0", k[:-1], k + b"\xff"):
            if miss and len(miss) <= 24 and miss not in have:
                assert reader.get(miss) is None
    reader.close()


@pytest.mark.parametrize("kind", KEYS)
def test_row_format_file_decodes_to_lanes_natively(kind, tmp_path):
    """A bulk file as a batch job writes it (the plain row writer): the
    native source walks its entries, whatever each key's length."""
    keys = keys_of(kind, 900)
    entries = [(k, 0, P, pack64(i * 0x01010101)) for i, k in enumerate(keys)]
    path = str(tmp_path / "bulk.tsst")
    w = SSTWriter(path)
    for e in entries:
        w.add(*e)
    w.finish()
    was = Stats.get().get_counter("codec.python_files")
    lanes = fmt.read_sst_arrays(SSTReader(path))
    assert Stats.get().get_counter("codec.python_files") == was
    want = lanes_of(entries)
    for f in LANES:
        assert np.array_equal(lanes[f], want[f][:len(entries)]), f


def test_native_row_source_declines_what_the_lanes_cannot_hold(tmp_path):
    """A key over 48 bytes (the lanes held 24 until keys of up to 48 were
    taken), or a value wider than the planar layout's u16 width (a value
    of another width is lanes since values of differing widths are):
    not lanes."""
    for name, odd in (("key", (b"k" * 49, 0, P, pack64(1))),
                      ("value", (b"zz", 0, P, b"v" * (1 << 16)))):
        path = str(tmp_path / f"{name}.tsst")
        w = SSTWriter(path)
        for e in [(b"a%02d" % i, 0, P, pack64(i)) for i in range(50)] + [odd]:
            w.add(*e)
        w.finish()
        assert fmt.read_sst_arrays(SSTReader(path)) is None


def test_old_layout_block_reads_back():
    """A block as every file had it before: no flag, one key length."""
    n, klen = 5, 10
    keys = [b"old-key-%02d" % i for i in range(n)]
    kw = np.zeros((n, 24), np.uint8)
    for i, k in enumerate(keys):
        kw[i, :klen] = np.frombuffer(k, np.uint8)
    words = np.concatenate([
        kw.view(">u4").astype("<u4").reshape(n, 6)[:, :3].T.reshape(-1),
        np.arange(1, n + 1, dtype="<u4"),                     # seq_lo
        np.frombuffer(bytes([P] * n + [0] * 3), "<u4"),       # vtype
        np.arange(n, dtype="<u4"), np.zeros(n, "<u4")])       # 8 B values
    raw = pack_planar_header(n, klen, 8, PLANAR_FLAG_SEQ32) + words.tobytes()
    assert [(k, s, t, v) for k, s, t, v in iter_planar_block(raw)] == [
        (k, i + 1, P, pack64(i)) for i, k in enumerate(keys)]
    assert decode_planar_block(raw)["key_len"].tolist() == [klen] * n


# sha256 of the PLANAR sink's file for seeded rows of ONE key length, as
# the PARENT commit (7b99616) wrote it, native and Python alike: the
# three cells' shapes (resolved counters; 1 KB records; a flush's stacks,
# tombstones and seqs over 2^32), uncompressed so that no zlib build
# speaks. A file of one key length is byte for byte the parent's.
PARENT_FILES = {
    "counter":
        "260f5c8957119be102999c258e4934e13f70bdb4cf49fd1760e4caa3a0b62ba5",
    "record":
        "d2ebc5ef3b123fd276601d8696f4bea6a249815cf679d330f8d9626d3a2b6e9f",
    "flush":
        "6d4911dbbba618e518c11b619a1b5370f9fe1152eafd09df5c1bde48024c37ee",
}


def parent_rows(shape):
    rng = np.random.default_rng(35)
    if shape == "counter":
        return [(b"s007-key%08d" % i, 0, OpType.PUT, pack64(int(v)))
                for i, v in enumerate(rng.integers(0, 1 << 40, 20250))]
    if shape == "record":
        return [(b"s003-key%08d" % i, 0, OpType.PUT,
                 rng.integers(32, 127, 1024, dtype=np.uint8).tobytes())
                for i in range(1536)]
    out, seq = [], 1 << 33
    for i in range(6000):
        seq -= 1
        kind = (OpType.MERGE, OpType.PUT, OpType.DELETE)[(i * 7) % 11 % 3]
        out.append((b"s001-liv%08d" % (i // 3), seq, kind,
                    b"" if kind == OpType.DELETE
                    else pack64(int(rng.integers(1, 1 << 63)))))
    return out


@pytest.mark.parametrize("codec", ["native", "python"])
@pytest.mark.parametrize("shape", sorted(PARENT_FILES))
def test_uniform_file_is_byte_for_byte_the_parents(shape, codec, tmp_path,
                                                   monkeypatch):
    entries = parent_rows(shape)
    vb = max(8, max(len(e[3]) for e in entries))
    arrays = lanes_of(entries, val_bytes=vb)
    if codec == "python":
        python_codecs(monkeypatch)
    path = str(tmp_path / "f.tsst")
    props = fmt.write_sst_from_arrays(
        arrays, len(entries), path,
        block_entries=max(64, 4096 // (16 + vb + 9)), compression=0,
        planar=True)
    assert len(props["planar"]) == 3  # no fourth member: one key length
    assert hashlib.sha256(open(path, "rb").read()).hexdigest() == \
        PARENT_FILES[shape]
    # and what the parent wrote reads back
    assert [(k, s, int(t), v) for k, s, t, v in SSTReader(path).iterate()] \
        == [(k, s, int(t), v) for k, s, t, v in entries]


# ---------------------------------------------------------------------------
# the one order
# ---------------------------------------------------------------------------

ORDERED = [b"a", b"a\0", b"a\0\0", b"a\0\1", b"ab", b"counter-1",
           b"counter-10", b"counter-100", b"counter-11", b"counter-2",
           b"counter-2\0", b"x" * 23, b"x" * 24]


def test_order_is_bytewise_in_flush_compaction_and_point_reads(tmp_path):
    assert ORDERED == sorted(ORDERED)
    rng = random.Random(1)
    db = DB(str(tmp_path / "db"), DBOptions(
        merge_operator=UInt64AddOperator(), disable_auto_compaction=True))
    model = {}
    for run in range(2):
        order = ORDERED[:]
        rng.shuffle(order)
        for k in order:
            if k == b"counter-1" and run == 0:
                continue
            db.merge(k, pack64(run + len(k)))
            model[k] = model.get(k, 0) + run + len(k)
        db.flush()
    for path in db_files(db):  # each flushed run ascends bytewise
        got = [k for k, *_ in file_entries([path])]
        assert got == sorted(got) and "planar" in SSTReader(path).props
    handled, remaining = cs.compact_dbs_batched([("db", db)])
    assert handled == ["db"] and not remaining
    assert [k for k, *_ in file_entries(db_files(db))] == ORDERED
    for k in ORDERED:
        assert db.get(k) == pack64(model[k])
    # absent beside present: a prefix, the key with a NUL, the next name
    for miss in (b"counter-", b"counter-1\0", b"counter-12", b"a\0\0\0",
                 b"x" * 22, b"b"):
        assert db.get(miss) is None
    assert db.multi_get([b"counter-1", b"counter-1\0", b"counter-10"]) == [
        pack64(model[b"counter-1"]), None, pack64(model[b"counter-10"])]
    assert [k for k, _v in db.new_iterator(b"counter-1", b"counter-2")] == [
        b"counter-1", b"counter-10", b"counter-100", b"counter-11"]
    db.close()


@pytest.mark.parametrize("kind", KEYS)
def test_planner_cuts_at_keys_of_any_length(kind):
    keys = keys_of(kind, 600)
    seq = iter(range(1, 1 << 20))
    runs = [sorted([(k, next(seq), M, pack64(1)) for k in keys[r::2]]
                   + [(k, next(seq), M, pack64(2)) for k in keys[r::7]],
                   key=lambda e: (e[0], -e[1])) for r in (0, 1)]
    parts = [nc.NativeCompactionBackend._arrays_from_entries(r, pack_entries)
             for r in runs]
    total = sum(len(r) for r in runs)
    lanes = nc.concat_lanes(parts, total)
    klen = nc.shard_klen(lanes)
    assert klen == (16 if kind == "uniform" else 0)
    bounds = nc.plan_subcompactions(parts, total, 1, klen,
                                    max_slice_rows=128)
    assert bounds == sorted(bounds) and set(bounds) <= set(keys)
    places = nc.slice_lanes(parts, bounds, klen)
    rows = [p["key_len"].shape[0] for p in places]
    assert max(rows) <= 128 and sum(rows) == total
    assert len(places) == len(bounds) + 1 == -(-total // 128)
    last = b""
    for place, n in zip(places, rows):
        got = [nc._part_key(place, r, klen) for r in range(n)]
        assert min(got) > last  # key-disjoint, in bytewise order
        last = max(got)
    # the parallelism rule's sampled boundaries are keys too
    nc_bounds = nc.choose_slice_boundaries(parts, 4, klen)
    assert nc_bounds and set(nc_bounds) <= set(keys)


# ---------------------------------------------------------------------------
# the array flush
# ---------------------------------------------------------------------------


def stacked_mem(keys, seed=3):
    """PUT / MERGE / DELETE stacks on shuffled keys, seqs ascending."""
    rng = random.Random(seed)
    mem, model, seq = MemTable(), {}, 0
    for _ in range(3):
        order = keys[:]
        rng.shuffle(order)
        for i, k in enumerate(order):
            seq += 1
            how = rng.randrange(7)
            if how == 0:
                mem.apply(k, seq, OpType.DELETE, b"")
                model.pop(k, None)
            elif how < 3:
                v = rng.randrange(1 << 64)
                mem.apply(k, seq, OpType.PUT, pack64(v))
                model[k] = v
            else:
                v = rng.randrange(1 << 64)
                mem.apply(k, seq, OpType.MERGE, pack64(v))
                model[k] = (model.get(k, 0) + v) & MASK64
    return mem, model


@pytest.mark.parametrize("kind", KEYS)
def test_array_flush_writes_what_the_per_entry_sink_writes(kind, tmp_path):
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.observability.span import start_span

    keys = keys_of(kind, 400)
    mem, model = stacked_mem(keys)
    drained = mem.drain_lanes()
    assert drained is not None
    lanes, key_mat = drained  # rows in arrival order, keys zero-padded
    assert lanes["key_len"].tolist() == list(mem._klens)
    assert key_mat.shape == (len(mem._seqs), max(map(len, keys)))
    assert b"".join(bytes(r[:n]) for r, n in zip(key_mat, mem._klens)) == \
        bytes(mem._key_buf)
    stats = Stats.get()
    was = {c: stats.get_counter(c) for c in (
        "flush.key_widths.mixed", "codec.python_files")}
    db = DB(str(tmp_path / "db"), DBOptions(
        merge_operator=UInt64AddOperator(), memtable_bytes=1 << 30,
        disable_auto_compaction=True))
    path = str(tmp_path / "a.tsst")
    with start_span("test.flush", always=True) as caller:
        db._write_mem_sst(path, mem)
    (encode,) = [s for s in SpanCollector.get().snapshot()
                 if s["trace_id"] == caller.trace_id
                 and s["name"] == "flush.encode"]
    mixed = kind != "uniform"
    assert encode["annotations"]["key_widths"] == (
        "mixed" if mixed else "uniform")
    assert encode["annotations"]["key_bytes_max"] == max(map(len, keys))
    assert encode["annotations"]["native"] == 1
    assert stats.get_counter("flush.key_widths.mixed") - was[
        "flush.key_widths.mixed"] == int(mixed)
    assert stats.get_counter("codec.python_files") == was["codec.python_files"]
    reader = SSTReader(path)
    assert "planar" in reader.props
    got = [(k, s, int(t), bytes(v)) for k, s, t, v in reader.iterate()]
    assert got == [(k, s, int(t), bytes(v)) for k, s, t, v in mem.entries()]
    assert all(reader.may_contain(k) for k in keys)
    reader.close()
    db.close()
    # the same memtable through a DB: every key reads as the model says
    db = DB(str(tmp_path / "db2"), DBOptions(
        merge_operator=UInt64AddOperator(), disable_auto_compaction=True))
    for k, s, t, v in sorted(mem.entries(), key=lambda e: e[1]):
        wb = WriteBatch()
        {P: wb.put, M: wb.merge}[int(t)](k, v) if t != D else wb.delete(k)
        db.write(wb)
    db.flush()
    for k in keys:
        want = model.get(k)
        assert db.get(k) == (None if want is None else pack64(want))
    assert [k for k, _v in db.new_iterator()] == sorted(model)
    db.close()


def test_merged_memtables_of_differing_key_lengths_flush_as_one(tmp_path):
    from rocksplicator_tpu.storage.engine import _MergedMemView

    mems = [stacked_mem(keys_of(kind, 120), seed)[0]
            for seed, kind in enumerate(KEYS)]
    # distinct memtables never share a seq
    for shift, mem in enumerate(mems):
        for i in range(len(mem._seqs)):
            mem._seqs[i] += shift << 20
    view = _MergedMemView(mems)
    db = DB(str(tmp_path / "db"), DBOptions(
        memtable_bytes=1 << 30, disable_auto_compaction=True))
    path = str(tmp_path / "a.tsst")
    db._write_mem_sst(path, view)
    reader = SSTReader(path)
    assert "planar" in reader.props
    assert [(k, s, int(t), bytes(v)) for k, s, t, v in reader.iterate()] == [
        (k, s, int(t), bytes(v)) for k, s, t, v in view.entries()]
    db.close()


# ---------------------------------------------------------------------------
# the doors
# ---------------------------------------------------------------------------


def make_db(path, keys, seed, counters: bool, width=64):
    """A shard as a refresh unit leaves it before its compaction: live
    writes (for counters MERGE increments, base PUTs and DELETEs; else
    ``width``-byte PUTs and DELETEs) flushed under a bulk file of every
    second key, and a dict of what each key reads as afterwards."""
    rng = random.Random(seed)
    db = DB(str(path), DBOptions(
        merge_operator=UInt64AddOperator() if counters else None,
        bits_per_key=10, disable_auto_compaction=True))
    model = {}

    def value():
        return (pack64(rng.randrange(1 << 64)) if counters
                else rng.randbytes(width))

    wb = WriteBatch()
    for n in range(len(keys)):
        k = rng.choice(keys)
        v = value()
        if n % 9 == 4:
            wb.delete(k)
            model.pop(k, None)
        elif counters and n % 3:
            wb.merge(k, v)
            model[k] = pack64((struct.unpack("<Q", model.get(
                k, pack64(0)))[0] + struct.unpack("<Q", v)[0]) & MASK64)
        else:
            wb.put(k, v)
            model[k] = v
    db.write(wb)
    db.flush()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    for k in keys[::2]:
        v = value()
        w.add(k, 0, OpType.PUT, v)
        model[k] = v
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    return db, model


def reference(db, drop=True):
    """What the tuple path's resolve keeps, in order."""
    runs = [list(db._readers[n].iterate())
            for level in db._levels for n in level]
    merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
    return [(k, int(t), bytes(v)) for k, _s, t, v in resolve_stream(
        merged, db.options.merge_operator, drop)]


def check_db(db, keys, model, want):
    """Files, point reads, batch reads, scans, filters: the tuple path's
    entries and the dict's answers."""
    assert file_entries(db_files(db)) == want  # bytewise order too
    assert [(k, v) for k, _t, v in want] == sorted(model.items())
    for k in keys:
        assert db.get(k) == model.get(k)
    assert db.multi_get(keys[::3]) == [model.get(k) for k in keys[::3]]
    lo, hi = keys[len(keys) // 4], keys[3 * len(keys) // 4]
    assert list(db.new_iterator(lo, hi)) == sorted(
        (k, v) for k, v in model.items() if lo <= k < hi)
    have = set(keys)
    for k in keys[::4]:
        for miss in (k + b"\0", k[:-1]):
            if miss and len(miss) <= 24 and miss not in have:
                assert db.get(miss) is None
    for path in db_files(db):
        r = SSTReader(path)
        assert all(r.may_contain(k) for k, *_ in r.iterate())
        r.close()


def compact_traced(dbs):
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.observability.span import start_span

    with start_span("test.caller", always=True) as caller:
        verdict = cs.compact_dbs_batched(dbs)
    by_name = {}
    for s in SpanCollector.get().snapshot():
        if s["trace_id"] == caller.trace_id:
            by_name.setdefault(s["name"], []).append(s["annotations"])
    return verdict, by_name


@pytest.mark.parametrize("kind", ["mixed", "uniform"])
@pytest.mark.parametrize("path", ["ride", "index", "ride_cut", "index_cut"])
def test_served_door_compacts_as_the_tuple_path_and_the_dict(
        path, kind, tmp_path, monkeypatch):
    counters, cut = path.startswith("ride"), path.endswith("cut")
    if cut:
        monkeypatch.setattr(cs, "PLACE_ROWS_MAX", 128)
    stats = Stats.get()
    names = ("compact.key_widths.mixed", "compact.key_widths.uniform",
             "compact.range_cut.shards", "codec.python_files")
    was = {c: stats.get_counter(c) for c in names}
    fell = dict(host_fallback_counts())
    keys = keys_of(kind, 220)
    made = [make_db(tmp_path / f"db{n}", keys, 50 + n, counters)
            for n in range(2)]
    want = [reference(db) for db, _m in made]
    (handled, remaining), spans = compact_traced(
        [(f"db{n}", db) for n, (db, _m) in enumerate(made)])
    assert sorted(handled) == ["db0", "db1"] and not remaining
    assert host_fallback_counts() == fell
    mixed = kind != "uniform"
    shape = {"key_widths": "mixed" if mixed else "uniform",
             "key_bytes_max": max(map(len, keys))}
    (launch,) = spans["tpu.compact_stream"]
    assert launch["value_path"] == ("ride" if counters else "index")
    assert launch["dbs"] == 2 and {k: launch[k] for k in shape} == shape
    assert (launch["shards"] > 2) == cut
    for name in ("tpu.lanes.decode", "tpu.planar.write"):
        assert len(spans[name]) >= 2
        for said in spans[name]:
            assert said["key_widths"] == shape["key_widths"], name
            assert said["key_bytes_max"] <= shape["key_bytes_max"]
            assert said.get("native", 1) == 1
    gained = {c: stats.get_counter(c) - was[c] for c in names}
    assert gained == {
        "compact.key_widths.mixed": 2 * mixed,
        "compact.key_widths.uniform": 2 * (not mixed),
        "compact.range_cut.shards": 2 * cut, "codec.python_files": 0}
    for (db, model), entries in zip(made, want):
        check_db(db, keys, model, entries)
        assert (len(db_files(db)) > 1) == cut
        db.close()


@pytest.mark.parametrize("kind", ["names", "uniform"])
def test_engine_seam_door_takes_keys_of_differing_length(kind, tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(sm, "STREAM_MODE_OVERRIDE", None)
    keys = keys_of(kind, 220)
    db, model = make_db(tmp_path / "db", keys, 9, counters=True)
    db._backend = db.options.compaction_backend = tb.TpuCompactionBackend()
    want = reference(db)
    made = []
    sink = db._backend.merge_runs_to_files
    monkeypatch.setattr(db._backend, "merge_runs_to_files",
                        lambda *a, **k: made.append(sink(*a, **k)) or made[-1])
    db.compact_range()
    assert len(made) == 1 and made[0]  # the door's own files, no fallback
    check_db(db, keys, model, want)
    db.close()


@pytest.mark.parametrize("sliced", [False, True], ids=["whole", "sliced"])
@pytest.mark.parametrize("kind", KEYS)
def test_host_array_path_takes_keys_of_differing_length(kind, sliced,
                                                        tmp_path,
                                                        monkeypatch):
    monkeypatch.setattr(sm, "STREAM_MODE_OVERRIDE", None)
    monkeypatch.setattr(nc, "MIN_SLICE_ENTRIES", 64)
    keys = keys_of(kind, 400)
    db, model = make_db(tmp_path / "db", keys, 12, counters=True)
    db.options.max_subcompactions = 4 if sliced else 1
    want = reference(db)
    made = []
    sink = db._backend.merge_runs_to_files
    monkeypatch.setattr(db._backend, "merge_runs_to_files",
                        lambda *a, **k: made.append(sink(*a, **k)) or made[-1])
    was = Stats.get().get_counter("compaction.subcompactions")
    db.compact_range()
    assert len(made) == 1 and made[0]  # the array sink's files
    assert (Stats.get().get_counter("compaction.subcompactions") > was) \
        == sliced
    check_db(db, keys, model, want)
    db.close()


def test_a_file_of_differing_key_lengths_is_not_streamed(tmp_path):
    """The chunked merge cuts its windows at keys of one width: it
    leaves such a file to the in-RAM array path."""
    for kind, streams in (("mixed", False), ("uniform", True)):
        entries = [(k, 1, P, pack64(i))
                   for i, k in enumerate(keys_of(kind, 300))]
        path = str(tmp_path / f"{kind}.tsst")
        assert fmt.write_sst_from_arrays(
            lanes_of(entries), len(entries), path, planar=True)
        probe = fmt.SstBlockLaneSource.probe(SSTReader(path))
        assert (probe is not None) == streams


# ---------------------------------------------------------------------------
# what the lanes cannot hold, and what the door says it takes
# ---------------------------------------------------------------------------


def test_the_rule_keeps_key_width_for_a_key_the_lanes_cannot_hold():
    lanes = {"key_len": np.array([9, 24, 1, 14], np.uint32),
             "vtype": np.array([P, D, M, P], np.uint32),
             "val_len": np.array([8, 0, 8, 8], np.uint32)}
    assert nc.lanes_decline_reason(lanes, UInt64AddOperator()) is None
    assert tb.device_decline_reason(lanes, UInt64AddOperator()) is None
    for bad in (49, 0):  # 25 until keys of up to 48 bytes were taken
        lanes["key_len"][1] = bad
        op = UInt64AddOperator()
        assert nc.lanes_decline_reason(lanes, op) == "key_width"
        assert tb.device_decline_reason(lanes, op) == "key_width"
        assert fmt.planar_widths(lanes, 4) is None


@pytest.mark.parametrize("door", ["engine_seam", "batched"])
def test_a_key_of_25_bytes_goes_to_the_host_path(door, tmp_path,
                                                 monkeypatch):
    """Named when the lanes held 24 bytes: the key is now one byte over
    the 48 they hold (keys of 25 to 48 bytes take twelve key words)."""
    monkeypatch.setattr(sm, "STREAM_MODE_OVERRIDE", None)
    keys = sorted(keys_of("names", 80) + [b"counter-" + b"9" * 41])
    assert max(map(len, keys)) == 49
    db, model = make_db(tmp_path / "db", keys, 4, counters=True)
    if door == "engine_seam":
        db._backend = tb.TpuCompactionBackend()
    want = reference(db)
    monkeypatch.setattr(
        cs.TpuCompactionService, "_pipeline",
        lambda self, *a, **k: pytest.fail("a program was built"))
    if door == "batched":
        handled, remaining = cs.compact_dbs_batched([("db", db)])
        assert handled == [] and [n for n, _ in remaining] == ["db"]
    db.compact_range()  # what the caller does with ``remaining``
    check_db(db, keys, model, want)
    db.close()


def test_the_door_says_how_long_a_key_of_a_mixed_shard_may_be():
    assert cs.device_mixed_key_bytes_max(UInt64AddOperator()) == 48
    assert cs.device_mixed_key_bytes_max(None) == 48

    from rocksplicator_tpu.storage.merge import MergeOperator

    class Concat(MergeOperator):
        def merge(self, key, existing, operands):
            return (existing or b"") + b"".join(operands)

        def partial_merge(self, key, operands):
            return None

    assert cs.device_mixed_key_bytes_max(Concat()) == 0
