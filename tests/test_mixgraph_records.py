"""db_bench MixGraph's records (ZippyDB, FAST '20) on every array path:
keys of 48 bytes (an 8-byte big-endian number and ASCII ``'0'`` up to 48)
and values of 10 to 1,024 bytes of DIFFERING width in one shard, against
a plain reference written here (``sorted()`` by key bytes and newest
seq, newest PUT / DELETE wins, tombstones dropped at the bottom) and
never the engine's own compaction code.

- the PLANAR codecs, Python and native, block for block: same bytes,
  same lanes, a value-length plane exactly where a block's live rows
  differ in width, key lanes of twelve words;
- files and blocks of one value width read as they always did;
- the memtable drain and the array flush at 48-byte keys and mixed
  widths;
- the served door (``compact_dbs_batched``) on a MixGraph-shaped shard
  and on adversarial ones: keys that share their first 24 bytes, a
  24-byte key beside its 25-byte extension, trailing NULs, a value of
  10 bytes (or of none) beside one of 1,024 under the same key; point
  reads and batch reads of every key through the files it wrote, and
  absent keys that share a present key's first 24 bytes;
- the engine-seam door and the host array path on the same shard;
- what the rule still declines: a key over 48 bytes, values of
  differing widths under the uint64-add operator.

XLA-CPU under conftest.py's explicit JAX_PLATFORMS=cpu.
"""

import os
import random
import struct

import numpy as np
import pytest

import rocksplicator_tpu.storage.native_compaction as nc
import rocksplicator_tpu.storage.stream_merge as sm
from rocksplicator_tpu.storage import DB, DBOptions, OpType
from rocksplicator_tpu.storage.compaction import host_fallback_counts
from rocksplicator_tpu.storage.memtable import MemTable
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.native.binding import get_file_codecs
from rocksplicator_tpu.storage.planar import (PLANAR_FLAG_KLENS,
                                              PLANAR_FLAG_SEQ32,
                                              PLANAR_FLAG_VLENS,
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

P, D = 1, 2
pack64 = struct.Struct("<Q").pack
LANES = ("key_words_be", "key_words_le", "key_len", "seq_hi", "seq_lo",
         "vtype", "val_words", "val_len")
KEY_BYTES, VALUE_MAX = 48, 1024


def mix_key(n: int) -> bytes:
    """db_bench's key: the number big-endian in 8 bytes, then '0's."""
    return struct.pack(">Q", n) + b"0" * (KEY_BYTES - 8)


def mix_value_len(rng: random.Random) -> int:
    """db_bench mixgraph's value size: a generalized Pareto draw (theta
    0, k 0.2615, sigma 25.45), 10 at least, wrapped at 1,024."""
    u = rng.random() or 0.5
    size = int(np.ceil(25.45 * (u ** -0.2615 - 1) / 0.2615))
    return 10 if size < 10 else (size % VALUE_MAX if size > VALUE_MAX
                                 else size)


def mix_value(rng: random.Random, n: int = None) -> bytes:
    n = mix_value_len(rng) if n is None else n
    return bytes(rng.randrange(32, 127) for _ in range(n))


def lanes_of(entries):
    """Kernel lanes of (key, seq, vtype, value) rows of any key width up
    to 48 and any value widths: the lanes' widths follow the rows'."""
    n = len(entries)
    kmax = max(len(k) for k, *_ in entries)
    kc = 6 if kmax <= 24 else 12
    vw = max(2, (max(len(v) for *_, v in entries) + 3) // 4)
    kb = np.zeros((n, 4 * kc), np.uint8)
    vb = np.zeros((n, 4 * vw), np.uint8)
    for i, (k, _s, _t, v) in enumerate(entries):
        kb[i, :len(k)] = np.frombuffer(k, np.uint8)
        vb[i, :len(v)] = np.frombuffer(v, np.uint8)
    seq = np.array([s for _k, s, _t, _v in entries], np.uint64)
    return {
        "key_words_be": kb.view(">u4").astype(np.uint32).reshape(n, kc),
        "key_words_le": kb.view("<u4").reshape(n, kc).copy(),
        "key_len": np.array([len(k) for k, *_ in entries], np.uint32),
        "seq_hi": (seq >> np.uint64(32)).astype(np.uint32),
        "seq_lo": (seq & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        "vtype": np.array([int(t) for _k, _s, t, _v in entries], np.uint32),
        "val_words": vb.view("<u4").reshape(n, vw).copy(),
        "val_len": np.array([len(v) for *_, v in entries], np.uint32),
    }


def plain_reference(entries, drop=True):
    """The plain reference: every entry sorted by (key bytes, newest
    first); a key's newest PUT or DELETE wins; a DELETE that wins is
    dropped at the bottom level. Returns [(key, vtype, value)]."""
    out, last = [], None
    for key, _seq, vtype, value in sorted(entries,
                                          key=lambda e: (e[0], -e[1])):
        if key == last:
            continue
        last = key
        if vtype == D and drop:
            continue
        out.append((key, int(vtype), bytes(value)))
    return out


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


def mixgraph_rows(n: int, seed: int, deletes: bool = True):
    """``n`` MixGraph rows in key order (key numbers of one shard of
    2,500, as the deployment holds them), values of seeded widths, a
    DELETE every seventh row."""
    rng = random.Random(seed)
    keys = [mix_key(s * 2500 + 17) for s in sorted(rng.sample(range(20000), n))]
    return [(k, i + 1, D if deletes and i % 7 == 3 else P,
             b"" if deletes and i % 7 == 3 else mix_value(rng))
            for i, k in enumerate(keys)]


# ---------------------------------------------------------------------------
# the codecs, block for block
# ---------------------------------------------------------------------------

SHAPES = ("mixgraph", "wide_keys_one_width", "control")


def shape_rows(shape):
    if shape == "mixgraph":
        return mixgraph_rows(600, 41)
    rows = mixgraph_rows(600, 41, deletes=False)
    if shape == "wide_keys_one_width":
        return [(k, s, t, pack64(s)) for k, s, t, _v in rows]
    return [(k[:8] + b"key%05d" % i, s, t, pack64(s))  # 16 B keys
            for i, (k, s, t, _v) in enumerate(rows)]


@pytest.mark.parametrize("compression", [0, 1, 4], ids=["none", "zlib", "rlz"])
@pytest.mark.parametrize("shape", SHAPES)
def test_value_length_plane_python_and_native_block_for_block(
        shape, compression, tmp_path, monkeypatch):
    entries = shape_rows(shape)
    arrays = lanes_of(entries)
    n, be = len(entries), 64
    live = [len(v) for _k, _s, t, v in entries if t != D]
    vmixed = len(set(live)) > 1
    klen = max(len(k) for k, *_ in entries)
    assert fmt.planar_widths(arrays, n) == (klen, max(live), False, vmixed)
    assert vmixed == (shape == "mixgraph")
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
    seq32 = 1
    assert props["planar"] == [klen, max(live), seq32] + (
        [0, 1] if vmixed else [])

    reader = SSTReader(native)
    lanes = fmt.read_sst_arrays(reader)  # the native source
    assert Stats.get().get_counter("codec.python_files") == was + 2
    assert lanes["key_words_be"].shape[1] == (12 if klen > 24 else 6)
    for f in LANES:
        assert np.array_equal(lanes[f], lanes_py[f]), f
        assert np.array_equal(lanes[f], arrays[f][:n]), f
    flagged = 0
    for bi, start in enumerate(range(0, n, be)):
        raw = reader._read_block(bi, fill_cache=False)
        rows = entries[start:start + be]
        widths = {len(v) for _k, _s, t, v in rows if t != D}
        count, bklen, bvlen, flags = unpack_planar_header(raw)
        # the value-length plane exactly where the block's live rows differ
        assert bool(flags & PLANAR_FLAG_VLENS) == (len(widths) > 1)
        assert not flags & PLANAR_FLAG_KLENS
        assert (count, bklen, bvlen) == (len(rows), klen, max(widths))
        flagged += bool(flags & PLANAR_FLAG_VLENS)
        assert raw == encode_planar_block(
            arrays, start, start + len(rows), klen, max(live), True,
            vmixed=vmixed)
        block = decode_planar_block(raw, lanes["key_words_be"].shape[1],
                                    lanes["val_words"].shape[1])
        for f in LANES:
            assert np.array_equal(block[f],
                                  lanes[f][start:start + len(rows)]), f
        assert list(iter_planar_block(raw)) == rows
    assert (flagged > 0) == vmixed
    # point reads through the native block search, every key; keys that
    # share a present key's first 24 bytes (or all 48 but one) are absent
    for k, _s, t, v in entries:
        got = reader.get(k)
        assert got is not None and (got[1], got[2]) == (t, v)
    have = {k for k, *_ in entries}
    for k, *_ in entries[::5]:
        for miss in (k[:24] + b"1" * (len(k) - 24), k[:-1] + b"1", k + b"\0",
                     k[:-1]):
            if miss not in have:
                assert reader.get(miss) is None
    reader.close()


def test_old_layout_blocks_and_files_read_unchanged(tmp_path):
    """A block of one value width is the block every file had (no VLENS
    flag, no plane), and reads as it always did; so does a file of one
    key length and one value width: its ``planar`` prop has no fifth
    member. A block with the key-length plane and no value-length plane
    (a file of counter names) reads too."""
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
    assert list(iter_planar_block(raw)) == [
        (k, i + 1, P, pack64(i)) for i, k in enumerate(keys)]
    lanes = decode_planar_block(raw)
    assert lanes["key_words_be"].shape == (n, 6)
    assert lanes["val_len"].tolist() == [8] * n
    native = get_file_codecs()
    got, past = native.planar_get_entries(raw, keys[3])
    assert got == [(4, P, pack64(3))] and past
    for rows in ([(b"k%04d" % i, i + 1, P, pack64(i)) for i in range(300)],
                 sorted((b"c-%d" % i, i + 1, P, pack64(i))
                        for i in range(300))):
        path = str(tmp_path / "f.tsst")
        props = fmt.write_sst_from_arrays(
            lanes_of(rows), len(rows), path, block_entries=64, planar=True)
        assert len(props["planar"]) <= 4
        r = SSTReader(path)
        assert [e for e in r.iterate()] == sorted(rows)
        for bi in range(len(r._index)):
            flags = unpack_planar_header(r._read_block(bi))[3]
            assert not flags & PLANAR_FLAG_VLENS
        r.close()


def test_a_value_over_a_block_header_width_is_corrupt():
    """A value-length plane that says more than the block's header
    width: Corruption on the Python path, the slow path on the native
    one (which then raises the descriptive error)."""
    from rocksplicator_tpu.storage.errors import Corruption

    rows = [(mix_key(i), i + 1, P, b"x" * (10 + 7 * i)) for i in range(4)]
    arrays = lanes_of(rows)
    raw = bytearray(encode_planar_block(arrays, 0, 4, 48, 31, True,
                                        vmixed=True))
    assert raw[6] & PLANAR_FLAG_VLENS
    # the vlen plane lies behind 12 key-word planes, seq_lo and vtype
    at = 16 + 4 * (12 * 4 + 4 + 1)
    raw[at:at + 2] = struct.pack("<H", 200)
    with pytest.raises(Corruption):
        decode_planar_block(bytes(raw))
    assert get_file_codecs().planar_get_entries(bytes(raw), rows[0][0]) \
        is None


# ---------------------------------------------------------------------------
# the memtable drain and the array flush
# ---------------------------------------------------------------------------


def test_drain_lanes_and_flush_at_48_byte_keys_and_mixed_widths(tmp_path):
    rows = mixgraph_rows(400, 7)
    mem = MemTable()
    for k, s, t, v in random.Random(3).sample(rows, len(rows)):
        mem.apply(k, s, t, v)
    lanes, key_mat = mem.drain_lanes()
    assert lanes["key_words_be"].shape[1] == 12 and key_mat.shape[1] == 48
    order = np.lexsort((lanes["seq_lo"],) + tuple(
        lanes["key_words_be"][:, w] for w in range(11, -1, -1)))
    want = lanes_of(rows)
    for f in ("key_words_be", "key_len", "vtype", "val_len"):
        assert np.array_equal(lanes[f][order], want[f]), f
    w = want["val_words"].shape[1]
    assert np.array_equal(lanes["val_words"][order][:, :w], want["val_words"])

    was = Stats.get().get_counter("flush.value_widths.mixed")
    db = DB(str(tmp_path / "db"), DBOptions(
        bits_per_key=10, disable_auto_compaction=True,
        memtable_bytes=1 << 30))
    for k, s, t, v in rows:
        wb = WriteBatch()
        wb.delete(k) if t == D else wb.put(k, v)
        db.write(wb)
    db.flush()
    assert Stats.get().get_counter("flush.value_widths.mixed") == was + 1
    (path,) = db_files(db)
    r = SSTReader(path)
    assert r.props["planar"][:2] == [48, max(len(v) for *_, v in rows)]
    assert r.props["planar"][4] == 1
    assert file_entries([path]) == plain_reference(
        [(k, s, t, v) for k, s, t, v in rows], drop=False)
    r.close()
    for k, _s, t, v in rows:
        assert db.get(k) == (None if t == D else v)
    db.close()


# ---------------------------------------------------------------------------
# the doors
# ---------------------------------------------------------------------------


def adversarial_keys(case: str):
    """(keys, fixed values or None): each case's keys in bytewise order."""
    if case == "shared_24_prefix":  # the first 24 bytes alike, then not
        stem = b"p" * 24
        return sorted(stem + bytes([c]) * 24 for c in range(40, 100)) + \
            sorted(stem + b"q" * n for n in range(1, 25)), None
    if case == "prefix_extension_24_25":  # a 24 B key beside its 25 B one
        keys = []
        for i in range(30):
            k24 = b"%024d" % i
            keys += [k24, k24 + b"\0", k24 + b"x", k24[:23]]
        return sorted(set(keys)), None
    if case == "trailing_nuls":
        keys = set()
        for i in range(20):
            k = mix_key(i)[:30]
            keys |= {k, k + b"\0", k + b"\0\0", k + b"\0" * 18}
        return sorted(keys), None
    # a value of 10 B (or none: MixGraph's size wraps to 0 at 2,048) beside
    # one of 1,024 B under the same key
    keys = sorted(mix_key(i) for i in range(80))
    return keys, (0 if case == "empty_beside_large" else 10, VALUE_MAX)


def make_db(path, keys, seed, sizes=None):
    """A shard as a MixGraph refresh unit leaves it before its
    compaction: live PUTs (and DELETEs) flushed under a bulk file of
    every second key, values of MixGraph widths (or alternately
    ``sizes``). Returns the DB and every entry written, for the plain
    reference."""
    rng = random.Random(seed)
    db = DB(str(path), DBOptions(bits_per_key=10,
                                 disable_auto_compaction=True))
    entries, seq = [], 0
    wb = WriteBatch()
    for n in range(len(keys)):
        k = rng.choice(keys)
        seq += 1
        if n % 9 == 4:
            wb.delete(k)
            entries.append((k, seq, D, b""))
        else:
            v = mix_value(rng, sizes[n % 2] if sizes else None)
            wb.put(k, v)
            entries.append((k, seq, P, v))
    db.write(wb)
    db.flush()
    sst = str(path) + ".bulk.tsst"
    w = SSTWriter(sst)
    bulk = []
    for i, k in enumerate(keys[::2]):
        v = mix_value(rng, sizes[(i + 1) % 2] if sizes else None)
        w.add(k, 0, OpType.PUT, v)
        bulk.append((k, v))
    w.finish()
    db.ingest_external_file([sst], move_files=True, allow_global_seqno=True)
    # the load lands above every live write
    entries += [(k, seq + 1, P, v) for k, v in bulk]
    return db, entries


def check_db(db, keys, entries, drop=True):
    """Files, point reads, batch reads, scans, filters: the plain
    reference's entries and answers."""
    want = plain_reference(entries, drop)
    assert file_entries(db_files(db)) == want  # bytewise order too
    model = {k: v for k, t, v in want if t == P}
    for k in keys:
        assert db.get(k) == model.get(k)
    assert db.multi_get(keys[::3]) == [model.get(k) for k in keys[::3]]
    lo, hi = keys[len(keys) // 4], keys[3 * len(keys) // 4]
    assert list(db.new_iterator(lo, hi)) == sorted(
        (k, v) for k, v in model.items() if lo <= k < hi)
    have = set(keys)
    for k in keys[::4]:
        for miss in (k + b"\0", k[:-1], k[:24] + b"\xff" * (len(k) - 24),
                     k[:-1] + b"\x01"):
            if miss and miss not in have:
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


CASES = ("mixgraph", "mixgraph_cut", "shared_24_prefix",
         "prefix_extension_24_25", "trailing_nuls", "small_beside_large",
         "empty_beside_large")


@pytest.mark.parametrize("case", CASES)
def test_served_door_compacts_as_the_plain_reference(case, tmp_path,
                                                     monkeypatch):
    cut = case.endswith("_cut")
    if cut:
        monkeypatch.setattr(cs, "PLACE_ROWS_MAX", 128)
    stats = Stats.get()
    names = ("compact.key_lanes.wide", "compact.value_widths.mixed",
             "compact.value_widths.uniform", "codec.python_files",
             "compact.value_path.index")
    was = {c: stats.get_counter(c) for c in names}
    fell = dict(host_fallback_counts())
    if case.startswith("mixgraph"):
        keys, sizes = [k for k, *_ in mixgraph_rows(220, 5, False)], None
    else:
        keys, sizes = adversarial_keys(case)
    made = [make_db(tmp_path / f"db{n}", keys, 50 + n, sizes)
            for n in range(2)]
    (handled, remaining), spans = compact_traced(
        [(f"db{n}", db) for n, (db, _e) in enumerate(made)])
    assert sorted(handled) == ["db0", "db1"] and not remaining
    assert host_fallback_counts() == fell
    wide = max(map(len, keys)) > 24
    (launch,) = spans["tpu.compact_stream"]
    assert launch["value_path"] == "index" and launch["dbs"] == 2
    assert launch["key_lanes"] == (12 if wide else 6)
    assert launch["key_words"] == (max(map(len, keys)) + 3) // 4
    assert launch["value_widths"] == "mixed"
    widest = max(len(v) for _db, entries in made for *_, v in entries)
    # the power-of-two bucket of the widest value's words
    assert launch["val_words"] == cs.value_bucket((widest + 3) // 4)
    assert 0 < launch["value_bytes"] < launch["value_bytes_padded"]
    assert (launch["shards"] > 2) == cut
    for name in ("tpu.lanes.decode", "tpu.planar.write"):
        assert len(spans[name]) >= 2
        for said in spans[name]:
            assert said["value_bytes_max"] <= VALUE_MAX, name
            assert said.get("native", 1) == 1
    assert all(s["value_widths"] == "mixed" for s in spans["tpu.lanes.decode"])
    gained = {c: stats.get_counter(c) - was[c] for c in names}
    assert gained["compact.key_lanes.wide"] == 2 * wide
    assert gained["compact.value_widths.mixed"] == 2
    assert gained["compact.value_widths.uniform"] == 0
    assert gained["codec.python_files"] == 0
    for (db, entries) in made:
        check_db(db, keys, entries)
        assert (len(db_files(db)) > 1) == cut
        db.close()


def test_engine_seam_door_and_host_array_path_take_mixgraph_shards(
        tmp_path, monkeypatch):
    monkeypatch.setattr(sm, "STREAM_MODE_OVERRIDE", None)
    keys = [k for k, *_ in mixgraph_rows(220, 9, False)]
    for door in ("engine_seam", "host"):
        db, entries = make_db(tmp_path / door, keys, 9)
        if door == "engine_seam":
            db._backend = db.options.compaction_backend = \
                tb.TpuCompactionBackend()
        made = []
        sink = db._backend.merge_runs_to_files
        monkeypatch.setattr(
            db._backend, "merge_runs_to_files",
            lambda *a, **k: made.append(sink(*a, **k)) or made[-1])
        db.compact_range()
        assert len(made) == 1 and made[0]  # the array sink's own files
        check_db(db, keys, entries)
        db.close()


def test_the_rule_declines_what_the_lanes_cannot_hold():
    lanes = {"key_len": np.array([48, 48, 1, 30], np.uint32),
             "vtype": np.array([P, D, P, P], np.uint32),
             "val_len": np.array([10, 0, 1024, 37], np.uint32)}
    assert nc.lanes_decline_reason(lanes, None) is None
    assert tb.device_decline_reason(lanes, None) is None
    lanes["key_len"][0] = 49
    assert nc.lanes_decline_reason(lanes, None) == "key_width"
    assert tb.device_decline_reason(lanes, None) == "key_width"
    assert fmt.planar_widths(dict(lanes, seq_hi=None), 4) is None
    # values of differing widths under the uint64-add fold: declined
    lanes = {"key_len": np.array([48, 48, 16], np.uint32),
             "vtype": np.array([P, P, P], np.uint32),
             "val_len": np.array([8, 4, 8], np.uint32)}
    op = UInt64AddOperator()
    assert nc.lanes_decline_reason(lanes, op) == "value_width_mixed"
    assert tb.device_decline_reason(lanes, op) == "value_width_mixed"
    lanes["val_len"][1] = 8
    assert nc.lanes_decline_reason(lanes, op) is None


def test_the_door_says_it_takes_48_byte_keys_and_mixed_widths():
    assert cs.device_mixed_key_bytes_max(None) == 48
    assert cs.device_mixed_value_bytes_max(None) == tb.DEVICE_VALUE_BYTES_MAX
    assert cs.device_mixed_value_bytes_max(UInt64AddOperator()) == 0
    assert [cs.value_bucket(w) for w in (3, 64, 250, 256)] == [
        4, 64, 256, 256]


def test_a_key_over_48_bytes_goes_to_the_host_path(tmp_path, monkeypatch):
    keys = [k for k, *_ in mixgraph_rows(60, 4, False)] + [b"z" * 49]
    db, entries = make_db(tmp_path / "db", keys, 4)
    monkeypatch.setattr(
        cs.TpuCompactionService, "_pipeline",
        lambda self, *a, **k: pytest.fail("a program was built"))
    handled, remaining = cs.compact_dbs_batched([("db", db)])
    assert handled == [] and [n for n, _ in remaining] == ["db"]
    db.compact_range()  # what the caller does with ``remaining``
    check_db(db, keys, entries)
    db.close()
