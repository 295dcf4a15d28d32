"""PLANAR block format tests: codec round-trip, container integration,
host/device encode parity, checksum verification, reader dispatch.

Format-compat discipline per SURVEY §4 (sst_load_compatibility_test):
entry-stream (v1) files must stay readable alongside planar output —
tests/test_golden_formats.py pins the old format; these pin the new.
"""

import struct

import numpy as np
import pytest

from rocksplicator_tpu.ops.kv_format import pack_entries
from rocksplicator_tpu.storage.errors import Corruption
from rocksplicator_tpu.storage.planar import (
    decode_planar_block, encode_planar_block, iter_planar_block,
    plane_words, PLANAR_HEADER)
from rocksplicator_tpu.storage.records import OpType
from rocksplicator_tpu.storage.sst import SSTReader
from rocksplicator_tpu.tpu.format import (
    planar_widths, read_sst_arrays, write_sst_from_arrays)

pack64 = struct.Struct("<q").pack


def _arrays(entries):
    b = pack_entries(entries)
    n = b.num_valid()
    return {
        "key_words_be": b.key_words_be[:n],
        "key_words_le": b.key_words_le[:n],
        "key_len": b.key_len[:n],
        "seq_hi": b.seq_hi[:n],
        "seq_lo": b.seq_lo[:n],
        "vtype": b.vtype[:n],
        "val_words": b.val_words[:n],
        "val_len": b.val_len[:n],
    }, n


def _entries(n, klen=16, with_deletes=False, big_seq=False):
    out = []
    for i in range(n):
        key = f"key{i:08d}".encode().ljust(klen, b"x")[:klen]
        seq = (1 << 40) + i if big_seq else 1000 + i
        if with_deletes and i % 7 == 3:
            out.append((key, seq, OpType.DELETE, b""))
        else:
            out.append((key, seq, OpType.PUT, pack64(i * 3)))
    return out


@pytest.mark.parametrize("seq32", [True, False])
@pytest.mark.parametrize("with_deletes", [False, True])
def test_planar_block_roundtrip(seq32, with_deletes):
    entries = _entries(37, with_deletes=with_deletes, big_seq=not seq32)
    arrays, n = _arrays(entries)
    raw = encode_planar_block(arrays, 0, n, 16, 8, seq32)
    assert len(raw) == PLANAR_HEADER.size + 4 * plane_words(n, 16, 8, seq32)
    got = list(iter_planar_block(raw))
    want = [(k, s, int(vt), v) for k, s, vt, v in entries]
    assert [(k, s, vt, v) for k, s, vt, v in got] == want
    lanes = decode_planar_block(raw)
    assert (lanes["key_len"] == 16).all()
    assert (lanes["val_len"] == arrays["val_len"]).all()


def test_planar_block_rejects_truncation():
    arrays, n = _arrays(_entries(8))
    raw = encode_planar_block(arrays, 0, n, 16, 8, True)
    with pytest.raises(Corruption):
        decode_planar_block(raw[:-4])


def test_planar_sst_roundtrip_and_reader_dispatch(tmp_path):
    entries = _entries(1000, with_deletes=True)
    arrays, n = _arrays(entries)
    path = str(tmp_path / "planar.tsst")
    props = write_sst_from_arrays(
        arrays, n, path, block_entries=256, planar=True)
    assert props is not None
    r = SSTReader(path)
    assert r.props["planar"] == [16, 8, 1]
    # generic tuple iteration (reader dispatch on the codec nibble)
    got = list(r.iterate())
    assert got == entries
    # point lookups hit the planar decode path too
    k, s, vt, v = entries[500]
    assert r.get_entries(k) == [(s, int(vt), v)]
    assert r.get_entries(b"absent-key-000000") == []
    # array source path: lanes come back without per-entry work
    lanes = read_sst_arrays(r)
    assert lanes is not None and len(lanes["seq_lo"]) == n
    assert (lanes["vtype"] == arrays["vtype"]).all()
    assert (lanes["seq_lo"] == arrays["seq_lo"]).all()
    r.close()


def test_planar_sst_smaller_than_rows(tmp_path):
    import os

    entries = _entries(4096)
    arrays, n = _arrays(entries)
    p_rows = str(tmp_path / "rows.tsst")
    p_planar = str(tmp_path / "planar.tsst")
    # compression off isolates the encoding-size difference
    assert write_sst_from_arrays(
        arrays, n, p_rows, block_entries=1024, compression=0) is not None
    assert write_sst_from_arrays(
        arrays, n, p_planar, block_entries=1024, compression=0,
        planar=True) is not None
    rows_sz = os.path.getsize(p_rows)
    planar_sz = os.path.getsize(p_planar)
    # 41 B/entry -> 29 B (16B key + 4B seq_lo + 1B vtype + 8B val): ~29%
    assert planar_sz < rows_sz * 0.78, (planar_sz, rows_sz)


def test_planar_checksum_detects_corruption(tmp_path):
    entries = _entries(512)
    arrays, n = _arrays(entries)
    path = str(tmp_path / "planar.tsst")
    # compression=0 keeps on-disk bytes == block bytes so a flipped file
    # byte lands in a plane word
    props = write_sst_from_arrays(
        arrays, n, path, block_entries=256, compression=0, planar=True)
    assert props["block_chk"]["algo"] == "poly1w"
    with open(path, "r+b") as f:
        f.seek(PLANAR_HEADER.size + 64)  # inside block 0's planes
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0x40]))
    r = SSTReader(path)
    with pytest.raises(Corruption):
        list(r.iterate())
    r.close()


def test_planar_widths_allows_tombstones_rejects_mixed():
    arrays, n = _arrays(_entries(50, with_deletes=True))
    assert planar_widths(arrays, n) == (16, 8, False, False)
    # mixed non-delete value widths are planar-expressible (the widest,
    # and a value-length plane where a block's live rows differ) ...
    mixed, m = _arrays([
        (b"k" * 16, 2, OpType.PUT, b"12345678"),
        (b"m" * 16, 1, OpType.PUT, b"1234"),
    ])
    assert planar_widths(mixed, m) == (16, 8, False, True)
    # ... a DELETE that carries a value is not
    mixed["vtype"][1] = int(OpType.DELETE)
    assert planar_widths(mixed, m) is None


def test_planar_global_seqno_override(tmp_path):
    entries = _entries(10)
    arrays, n = _arrays(entries)
    path = str(tmp_path / "planar.tsst")
    assert write_sst_from_arrays(
        arrays, n, path, block_entries=8, planar=True) is not None
    # simulate ingestion stamping (reference global-seqno semantics)
    from rocksplicator_tpu.storage import sst as sst_mod

    r = SSTReader(path)
    r.global_seqno = 777
    lanes = read_sst_arrays(r)
    assert (lanes["seq_lo"] == 777).all() and (lanes["seq_hi"] == 0).all()
    for k, s, vt, v in r.iterate():
        assert s == 777
    r.close()


def test_device_planar_encode_matches_host():
    import jax.numpy as jnp

    from rocksplicator_tpu.ops.block_encode import (
        encode_planar_words_tpu, planar_checksums_tpu)
    from rocksplicator_tpu.storage.planar import PLANAR_FLAG_SEQ32
    from rocksplicator_tpu.utils.checksum import poly_checksum_words

    entries = _entries(512, with_deletes=True)
    arrays, n = _arrays(entries)
    be = 128  # block_entries; n == 4 full blocks
    for seq32 in (True, False):
        dev = np.asarray(encode_planar_words_tpu(
            jnp.asarray(arrays["key_words_be"]),
            jnp.asarray(arrays["seq_hi"]), jnp.asarray(arrays["seq_lo"]),
            jnp.asarray(arrays["vtype"]), jnp.asarray(arrays["val_words"]),
            klen=16, vlen=8, seq32=seq32, block_entries=be,
        ))
        chk = np.asarray(planar_checksums_tpu(jnp.asarray(dev)))
        for bi in range(n // be):
            host = encode_planar_block(
                arrays, bi * be, (bi + 1) * be, 16, 8, seq32)
            host_words = np.frombuffer(
                host, dtype="<u4", offset=PLANAR_HEADER.size)
            assert (dev[bi] == host_words).all(), (seq32, bi)
            assert int(chk[bi]) == poly_checksum_words(
                host_words, plane_words(be, 16, 8, seq32))


def test_planar_sink_device_words_path(tmp_path):
    import jax.numpy as jnp

    from rocksplicator_tpu.ops.block_encode import (
        encode_planar_words_tpu, planar_checksums_tpu)

    entries = _entries(600)  # 2 full blocks of 256 + tail of 88
    arrays, n = _arrays(entries)
    cap = 1024
    padded = {
        k: np.pad(v, [(0, cap - n)] + [(0, 0)] * (v.ndim - 1))
        for k, v in arrays.items()
    }
    words = np.asarray(encode_planar_words_tpu(
        jnp.asarray(padded["key_words_be"]),
        jnp.asarray(padded["seq_hi"]), jnp.asarray(padded["seq_lo"]),
        jnp.asarray(padded["vtype"]), jnp.asarray(padded["val_words"]),
        klen=16, vlen=8, seq32=True, block_entries=256,
    ))
    chks = np.asarray(planar_checksums_tpu(jnp.asarray(words)))
    path = str(tmp_path / "dev.tsst")
    props = write_sst_from_arrays(
        arrays, n, path, block_entries=256, planar=True,
        device_words=words, device_checksums=chks)
    assert props is not None
    r = SSTReader(path)
    assert list(r.iterate()) == entries  # tail host-packed, checksums ok
    r.close()


def test_read_sst_arrays_infers_uniform_flush_files(tmp_path):
    """Flush-written files carry no sink props; the array source must
    infer the uniform stride and decode them array-to-array, and must
    REJECT non-uniform files (tuple path handles those)."""
    from rocksplicator_tpu.storage.sst import SSTWriter
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    uni = str(tmp_path / "uniform.tsst")
    w = SSTWriter(uni, compression=0)
    entries = _entries(500)
    for e in entries:
        w.add(*e)
    w.finish()
    r = SSTReader(uni)
    lanes = read_sst_arrays(r)
    assert lanes is not None
    assert len(lanes["seq_lo"]) == 500
    assert (lanes["key_len"] == 16).all() and (lanes["val_len"] == 8).all()
    r.close()

    mixed = str(tmp_path / "mixed.tsst")
    w = SSTWriter(mixed, compression=0)
    w.add(b"a" * 16, 2, 1, b"12345678")
    w.add(b"b" * 16, 1, 1, b"123")  # different value width
    w.finish()
    r = SSTReader(mixed)
    # a file that says its widest value (``widths_max``) decodes with each
    # row's own value width; one written before that prop existed (no
    # width prop at all) is not lanes
    lanes = read_sst_arrays(r)
    assert lanes["val_len"].tolist() == [8, 3]
    assert bytes(lanes["val_words"][1].view(np.uint8)) == b"123" + bytes(5)
    r.close()
    w = SSTWriter(mixed, compression=0)
    w.add(b"a" * 16, 2, 1, b"12345678")
    w.add(b"b" * 16, 1, 1, b"123")
    w.finish(extra_props={"widths_max": None})
    r = SSTReader(mixed)
    assert read_sst_arrays(r) is None
    r.close()

    # value widths 8, 4, 12: encoded sizes 41+37+45 = 123 = 3x41, so the
    # block-0 divisibility probe PASSES with the mis-inferred stride 41
    # and only the per-row klens/vlens checks can reject the misaligned
    # decode — the guard against silent garbage
    tricky = str(tmp_path / "tricky.tsst")
    w = SSTWriter(tricky, compression=0)
    w.add(b"a" * 16, 3, 1, b"12345678")
    w.add(b"b" * 16, 2, 1, b"1234")
    w.add(b"c" * 16, 1, 1, b"123456789012")
    w.finish(extra_props={"widths_max": None})  # no width prop: inferred
    r = SSTReader(tricky)
    assert read_sst_arrays(r) is None
    r.close()


def test_engine_flush_writes_planar_files(tmp_path):
    """Fixed-width memtable flushes take the PLANAR sink, so L0 files —
    tombstones included — decode array-to-array for first-level
    compactions; variable-width workloads fall back to entry-stream."""
    from rocksplicator_tpu.storage.engine import DB, DBOptions
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    db = DB(str(tmp_path / "db"), DBOptions(compression=0))
    for i in range(100):
        db.put(f"k{i:015d}".encode(), pack64(i))
    db.delete(b"k" + b"0" * 14 + b"7")
    db.flush()
    names = list(db._levels[0])
    assert len(names) == 1
    r = db._readers[names[0]]
    assert r.props.get("planar"), r.props
    lanes = read_sst_arrays(r)
    assert lanes is not None and len(lanes["seq_lo"]) == 101
    assert (lanes["vtype"] == 2).sum() == 1  # the tombstone rode along
    assert db.get(b"k" + b"0" * 14 + b"7") is None
    assert db.get(b"k" + b"0" * 14 + b"3") == pack64(3)
    db.close()

    # variable widths: planar too (the widest, a fifth prop member), and
    # fully readable
    db2 = DB(str(tmp_path / "db2"), DBOptions(compression=0))
    db2.put(b"a" * 16, b"12345678")
    db2.put(b"b" * 16, b"123")
    db2.flush()
    names = list(db2._levels[0])
    r2 = db2._readers[names[0]]
    assert r2.props.get("planar") == [16, 8, 1, 0, 1]
    assert db2.get(b"b" * 16) == b"123"
    assert db2.get(b"a" * 16) == b"12345678"
    db2.close()


def test_planar_wide_values_roundtrip():
    """vlen is a u16 in the header (byte 7 carries the high byte — the
    round-2 crash was values >= 256 B overflowing a u8 field). Pin the
    codec at 300 B and at the 65535-B boundary."""
    for vlen in (300, 65535):
        vb = (vlen + 3) // 4 * 4
        entries = [
            (f"k{i:07d}".encode(), 10 + i, int(OpType.PUT),
             bytes([i + 1]) * vlen)
            for i in range(3)
        ]
        arrays, n = _arrays_val_bytes(entries, vb)
        raw = encode_planar_block(arrays, 0, n, 8, vlen, seq32=False)
        got = list(iter_planar_block(raw))
        assert [g[0] for g in got] == [e[0] for e in entries]
        assert [g[3] for g in got] == [e[3] for e in entries]


def _arrays_val_bytes(entries, val_bytes):
    b = pack_entries(entries, val_bytes=val_bytes)
    n = b.num_valid()
    return {
        "key_words_be": b.key_words_be[:n],
        "key_words_le": b.key_words_le[:n],
        "key_len": b.key_len[:n],
        "seq_hi": b.seq_hi[:n],
        "seq_lo": b.seq_lo[:n],
        "vtype": b.vtype[:n],
        "val_words": b.val_words[:n],
        "val_len": b.val_len[:n],
    }, n


def test_planar_widths_bounds_vlen():
    """Values wider than the u16 header field must refuse the planar sink
    (entry-stream handles them), never crash the header packer."""
    from rocksplicator_tpu.storage.planar import (PLANAR_MAX_VLEN,
                                                  pack_planar_header)

    entries = [(b"k" * 8, 1, int(OpType.PUT), b"v" * (PLANAR_MAX_VLEN + 1))]
    arrays, n = _arrays_val_bytes(entries, PLANAR_MAX_VLEN + 5)
    assert planar_widths(arrays, n) is None
    with pytest.raises(ValueError):
        pack_planar_header(1, 8, PLANAR_MAX_VLEN + 1, 0)
    with pytest.raises(ValueError):
        pack_planar_header(1, 49, 8, 0)  # klen beyond the TPU key lanes


def test_decode_planar_block_bad_klen_raises_corruption():
    """A length-self-consistent block with klen > 48 (the key lanes'
    width; 24 before keys of up to 48 bytes were taken) must raise
    Corruption (not a numpy broadcast error) on the generic reader path."""
    n, klen, vlen = 4, 49, 8
    words = plane_words(n, klen, vlen, seq32=False)
    raw = PLANAR_HEADER.pack(n, klen, vlen, 0, 0, 0) + b"\x00" * (4 * words)
    with pytest.raises(Corruption):
        decode_planar_block(raw)
    with pytest.raises(Corruption):
        list(iter_planar_block(raw))


def test_engine_flush_512b_values_planar(tmp_path):
    """The round-2 repro: 200 puts of 512-byte uniform values crashed
    every flush. Now they take the planar sink and read back, including
    across reopen."""
    from rocksplicator_tpu.storage.engine import DB, DBOptions

    path = str(tmp_path / "db")
    db = DB(path, DBOptions(memtable_bytes=64 * 1024, compression=0))
    for i in range(200):
        db.put(b"key%08d" % i, bytes([i % 251]) * 512)
    db.flush()
    assert any(
        db._readers[nm].props.get("planar")
        for files in db._levels for nm in files
    )
    db.close()
    db = DB(path)
    for i in range(200):
        assert db.get(b"key%08d" % i) == bytes([i % 251]) * 512
    db.close()


def test_engine_flush_64kb_values_fallback(tmp_path):
    """Values beyond the u16 planar bound fall back to the entry-stream
    writer — flush still succeeds and data reads back."""
    from rocksplicator_tpu.storage.engine import DB, DBOptions

    path = str(tmp_path / "db")
    db = DB(path, DBOptions(compression=0))
    big = 64 * 1024  # 65536 > PLANAR_MAX_VLEN
    for i in range(4):
        db.put(b"wide%04d" % i, bytes([i + 1]) * big)
    db.flush()
    for files in db._levels:
        for nm in files:
            assert not db._readers[nm].props.get("planar")
    db.close()
    db = DB(path)
    for i in range(4):
        assert db.get(b"wide%04d" % i) == bytes([i + 1]) * big
    db.close()


# ---------------------------------------------------------------------------
# the native whole-file source over PLANAR files (one call per file)
# ---------------------------------------------------------------------------

def _native_source_only(monkeypatch):
    """Skip without the library; with it, take the Python planar source
    away so that what answers is the native one."""
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.tpu import format as fmt

    lib = get_native()
    if lib is None or not lib.has_file_codecs:
        pytest.skip("native lib not built")
    monkeypatch.setattr(fmt, "_read_planar_arrays", None)
    return fmt


@pytest.mark.parametrize("compression", [0, 1, 4])
def test_native_source_flipped_byte_raises_corruption(
        compression, tmp_path, monkeypatch):
    """A flipped byte inside a block: the native source reports it as
    Corruption (the block does not inflate, does not fit its layout, or
    fails its block_chk value) — never as lanes."""
    fmt = _native_source_only(monkeypatch)
    arrays, n = _arrays(_entries(600, with_deletes=True))
    path = str(tmp_path / "planar.tsst")
    props = write_sst_from_arrays(
        arrays, n, path, block_entries=256, compression=compression,
        planar=True)
    assert props["block_chk"]["algo"] == "poly1w"
    r = SSTReader(path)
    assert len(fmt.read_sst_arrays(r)["key_len"]) == n
    off1, size1 = r._index[1][1], r._index[1][2]
    r.close()
    for at in (off1 + size1 // 2, off1 + size1 - 3):
        with open(path, "r+b") as f:
            f.seek(at)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x10]))
        r = SSTReader(path)
        with pytest.raises(Corruption):
            fmt.read_sst_arrays(r)
        r.close()
        with open(path, "r+b") as f:  # put it back for the next offset
            f.seek(at)
            f.write(b)


def test_native_source_corrupt_header_and_short_file(tmp_path, monkeypatch):
    """A block whose header no longer fits its bytes, and a file cut
    short inside a block, are Corruption too."""
    fmt = _native_source_only(monkeypatch)
    arrays, n = _arrays(_entries(300))
    path = str(tmp_path / "planar.tsst")
    assert write_sst_from_arrays(
        arrays, n, path, block_entries=100, compression=0,
        planar=True) is not None
    r = SSTReader(path)
    off2 = r._index[2][1]
    r.close()
    with open(path, "r+b") as f:
        f.seek(off2)  # block 2's entry count
        f.write((99).to_bytes(4, "little"))
    r = SSTReader(path)
    with pytest.raises(Corruption, match="block 2 is corrupt"):
        fmt.read_sst_arrays(r)
    # the index points past the end of the file
    r._index[2] = r._index[2][:1] + (1 << 30,) + r._index[2][2:]
    with pytest.raises(Corruption, match="block 2 could not be read"):
        fmt.read_sst_arrays(r)
    r.close()


def test_native_source_global_seqno_override(tmp_path, monkeypatch):
    fmt = _native_source_only(monkeypatch)
    arrays, n = _arrays(_entries(300, big_seq=True))
    path = str(tmp_path / "planar.tsst")
    assert write_sst_from_arrays(
        arrays, n, path, block_entries=64, planar=True) is not None
    r = SSTReader(path)
    plain = fmt.read_sst_arrays(r)
    assert (plain["seq_hi"] == 1 << 8).all()
    assert np.array_equal(plain["seq_lo"], arrays["seq_lo"])
    r.global_seqno = (7 << 32) + 5
    lanes = fmt.read_sst_arrays(r)
    assert (lanes["seq_lo"] == 5).all() and (lanes["seq_hi"] == 7).all()
    for f in ("key_words_be", "vtype", "val_words", "val_len"):
        assert np.array_equal(lanes[f], plain[f])
    r.close()


def test_native_source_width_drift_and_foreign_props(tmp_path, monkeypatch):
    """Blocks whose widths are not the file's yield None (the tuple
    path's file); a ``planar`` prop the native source cannot read is
    left to the Python source, which says None as it always did."""
    from rocksplicator_tpu.tpu import format as fmt

    arrays, n = _arrays(_entries(200))
    path = str(tmp_path / "planar.tsst")
    assert write_sst_from_arrays(
        arrays, n, path, block_entries=64, planar=True) is not None
    r = SSTReader(path)
    r.props["planar"] = [12, 8, 1]  # the blocks say 16
    with monkeypatch.context() as m:
        _native_source_only(m)
        assert fmt.read_sst_arrays(r) is None
    for foreign in (True, [0, 8, 1], ["x", 8, 1], {"klen": 16}):
        r.props["planar"] = foreign
        assert fmt._read_lanes_native(r, True) is fmt._NOT_TAKEN
    r.props["planar"] = True
    r.props["block_chk"] = {"algo": "poly1", "block_bytes": 64,
                            "values": [1, 2, 3, 4]}
    assert fmt._read_lanes_native(r, True) is fmt._NOT_TAKEN
    r.close()
