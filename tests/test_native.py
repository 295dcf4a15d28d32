"""Native-library parity tests: the C++ hot paths must be byte-identical
to the Python implementations (and the whole storage suite runs against
whichever is active)."""

import os
import struct
import zlib

import numpy as np
import pytest

from rocksplicator_tpu.storage.native.binding import NATIVE, native_available

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native lib not built"
)


def test_native_lib_builds_and_loads():
    assert NATIVE is not None


def test_crc32_matches_zlib():
    for data in (b"", b"x", b"hello world" * 100, os.urandom(4096)):
        assert NATIVE.crc32(data) == (zlib.crc32(data) & 0xFFFFFFFF)


def test_block_codec_roundtrip_and_python_parity():
    from rocksplicator_tpu.storage.sst import _encode_entry

    entries = [
        (b"alpha", 1, 1, b"value-1"),
        (b"beta", 2, 3, b""),
        (b"gamma" * 4, 3, 2, os.urandom(100)),
        (b"", 4, 1, b"empty-key"),
    ]
    native_bytes = NATIVE.encode_block(
        [e[0] for e in entries], [e[1] for e in entries],
        [e[2] for e in entries], [e[3] for e in entries],
    )
    python_bytes = b"".join(_encode_entry(*e) for e in entries)
    assert native_bytes == python_bytes  # byte-identical format
    decoded = NATIVE.decode_block(native_bytes)
    assert decoded == entries


def test_decode_rejects_corruption():
    from rocksplicator_tpu.storage.errors import Corruption

    good = NATIVE.encode_block([b"k"], [1], [1], [b"v"])
    with pytest.raises(Corruption):
        NATIVE.decode_block(good[:-1])


def test_wal_scan_matches_python(tmp_path):
    from rocksplicator_tpu.storage import wal as wal_mod
    from rocksplicator_tpu.storage.records import WriteBatch

    wal_dir = str(tmp_path / "wal")
    w = wal_mod.WalWriter(wal_dir)
    bodies = []
    for i in range(5):
        b = WriteBatch().put(f"k{i}".encode(), os.urandom(20)).encode()
        w.append(i * 3 + 1, b)
        bodies.append((i * 3 + 1, b))
    w.close()
    seg = os.path.join(wal_dir, sorted(os.listdir(wal_dir))[0])
    raw = open(seg, "rb").read()
    records, bad = NATIVE.wal_scan(raw)
    assert bad == -1
    assert [(s, raw[o:o + l]) for s, o, l in records] == bodies
    # corrupt a middle record: scan stops there and reports the offset
    mutated = bytearray(raw)
    mutated[40] ^= 0xFF
    records2, bad2 = NATIVE.wal_scan(bytes(mutated))
    assert bad2 >= 0


def test_native_bloom_matches_python():
    from rocksplicator_tpu.storage.bloom import (
        BloomFilter, num_words_for, word_mask,
    )

    keys = [os.urandom(np.random.randint(1, 30)) for _ in range(500)]
    nw = num_words_for(len(keys))
    # python-only build (bypasses the native fast path)
    py = BloomFilter(nw)
    for k in keys:
        idx, mask = word_mask(k, nw)
        py.words[idx] |= np.uint32(mask)
    nat = BloomFilter(nw)
    NATIVE.bloom_add_many(nat.words, keys)
    assert np.array_equal(py.words, nat.words)
    for k in keys:
        assert NATIVE.bloom_may_contain(nat.words, k)


def test_storage_engine_runs_on_native_paths(tmp_path):
    """End-to-end: DB ops exercise native decode/scan/bloom underneath."""
    from rocksplicator_tpu.storage import DB, DBOptions, UInt64AddOperator

    pack = struct.Struct("<q").pack
    with DB(str(tmp_path / "db"),
            DBOptions(merge_operator=UInt64AddOperator())) as db:
        for i in range(300):
            db.put(f"key{i:04d}".encode(), f"val{i}".encode())
            db.merge(b"ctr", pack(1))
        db.flush()
        db.compact_range()
        assert db.get(b"key0123") == b"val123"
        assert db.get(b"ctr") == pack(300)
        assert len(list(db.new_iterator())) == 301
    # recovery path (native wal_scan)
    db2 = DB(str(tmp_path / "db"))
    assert db2.latest_sequence_number() == 600
    db2.close()


def test_native_point_lookup_matches_and_early_exits():
    entries = [
        (b"a", 9, 1, b"va"),
        (b"k", 5, 3, b"m5"),
        (b"k", 3, 3, b"m3"),
        (b"k", 1, 1, b"base"),
        (b"z", 2, 1, b"vz"),
    ]
    raw = NATIVE.encode_block(
        [e[0] for e in entries], [e[1] for e in entries],
        [e[2] for e in entries], [e[3] for e in entries],
    )
    matches, past_end = NATIVE.get_entries(raw, b"k")
    assert matches == [(5, 3, b"m5"), (3, 3, b"m3"), (1, 1, b"base")]
    assert past_end  # saw b"z" > b"k"
    matches2, past2 = NATIVE.get_entries(raw, b"zz")
    assert matches2 == [] and not past2  # ran off the end, no proof
    matches3, past3 = NATIVE.get_entries(raw, b"b")
    assert matches3 == [] and past3


def test_native_point_lookup_deep_merge_stack_retry():
    # >64 entries for one key: must retry internally, not fall back
    n = 200
    keys = [b"hot"] * n + [b"z"]
    seqs = list(range(n, 0, -1)) + [500]
    vtypes = [3] * n + [1]
    vals = [struct.pack("<q", i) for i in range(n)] + [b"zz"]
    raw = NATIVE.encode_block(keys, seqs, vtypes, vals)
    res = NATIVE.get_entries(raw, b"hot")
    assert res is not None
    matches, past_end = res
    assert len(matches) == n and past_end


def test_native_planar_get_entries_parity():
    """Native planar point lookup vs the Python planar codec."""
    import struct

    from rocksplicator_tpu.ops.kv_format import pack_entries
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.storage.planar import (
        encode_planar_block, iter_planar_block)
    from rocksplicator_tpu.storage.records import OpType

    native = get_native()
    if native is None or not native._has_planar:
        import pytest

        pytest.skip("native lib unavailable")
    pk = struct.Struct("<q").pack
    entries = []
    for i in range(40):
        key = f"key{i:05d}".encode().ljust(12, b"p")
        if i == 17:  # a MERGE stack: several entries for one key
            for s in (9, 7, 5):
                entries.append((key, 100 + s, OpType.MERGE, pk(s)))
        entries.append((key, 50 + i, OpType.PUT, pk(i))
                       if i % 5 else (key, 50 + i, OpType.DELETE, b""))
    entries.sort(key=lambda e: (e[0], -e[1]))
    b = pack_entries(entries)
    n = b.num_valid()
    arrays = {f: getattr(b, f)[:n] for f in (
        "key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
        "val_words", "val_len")}
    for seq32 in (True, False):
        raw = encode_planar_block(arrays, 0, n, 12, 8, seq32)
        ref = list(iter_planar_block(raw))
        for probe_key in {e[0] for e in entries} | {b"absent", b"key00017"}:
            want = [(s, vt, v) for k, s, vt, v in ref if k == probe_key]
            got = native.planar_get_entries(raw, probe_key, max_matches=2)
            assert got is not None
            matches, past_end = got
            assert matches == want, (probe_key, seq32)
            if want and probe_key != ref[-1][0]:
                assert past_end  # stopped at a greater key
        # absent key smaller than everything: past_end must be set
        m, pe = native.planar_get_entries(raw, b"aaa")
        assert m == [] and pe
        # absent key greater than everything: later blocks may match
        m, pe = native.planar_get_entries(raw, b"zzz")
        assert m == [] and not pe


def test_native_planar_get_entries_wide_values():
    """vlen >= 256 must stay on the native fast path (the u16 header high
    byte lives at byte 7; the binding must pass the full cap, not just
    the low byte — regression for the round-3 truncated-cap bug)."""
    from rocksplicator_tpu.ops.kv_format import pack_entries
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.storage.planar import (
        encode_planar_block, iter_planar_block)
    from rocksplicator_tpu.storage.records import OpType

    native = get_native()
    if native is None or not native._has_planar:
        import pytest

        pytest.skip("native lib unavailable")
    vlen = 300
    vb = (vlen + 3) // 4 * 4
    entries = [
        (f"wk{i:06d}".encode(), 10 + i, int(OpType.PUT),
         bytes([i + 1]) * vlen)
        for i in range(8)
    ]
    b = pack_entries(entries, val_bytes=vb)
    n = b.num_valid()
    arrays = {f: getattr(b, f)[:n] for f in (
        "key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
        "val_words", "val_len")}
    raw = encode_planar_block(arrays, 0, n, 8, vlen, seq32=False)
    ref = list(iter_planar_block(raw))
    for k, s, vt, v in ref:
        got = native.planar_get_entries(raw, k)
        assert got is not None, "wide values fell off the native fast path"
        matches, _ = got
        assert matches == [(s, vt, v)]
        assert len(matches[0][2]) == vlen


def test_native_merge_resolve_parity_fuzz():
    """cpu_merge_resolve (packed-record sort + linear segment resolve)
    must be element-exact with numpy_merge_resolve across workloads,
    both flag combinations, and degenerate shapes."""
    import numpy as np

    from rocksplicator_tpu.models.compaction_model import synth_counter_batch
    from rocksplicator_tpu.ops.kv_format import KVBatch
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.tpu.backend import (cpu_merge_resolve,
                                               numpy_merge_resolve)

    lib = get_native()
    if lib is None or not lib.has_merge_resolve:
        pytest.skip("native merge-resolve unavailable")

    def batch_of(n, seed, **kw):
        d = synth_counter_batch(n, key_space=max(1, n // 8), seed=seed,
                                key_bytes=16, **kw)
        return KVBatch(
            key_words_be=d["key_words_be"], key_words_le=d["key_words_le"],
            key_len=d["key_len"], seq_hi=d["seq_hi"], seq_lo=d["seq_lo"],
            vtype=d["vtype"], val_words=d["val_words"],
            val_len=d["val_len"], valid=d["valid"], val_bytes=8)

    cases = [batch_of(n, seed)
             for n in (1, 2, 64, 4096) for seed in (0, 7)]
    cases += [batch_of(2048, 3, merge_frac=1.0),      # pure operands
              batch_of(2048, 4, merge_frac=0.0, delete_frac=1.0),
              batch_of(2048, 5, delete_frac=0.0)]
    for b in cases:
        for uint64_add in (True, False):
            for drop in (True, False):
                a1, c1 = numpy_merge_resolve(b, uint64_add, drop)
                a2, c2 = cpu_merge_resolve(b, uint64_add, drop)
                assert c1 == c2, (len(b.key_len), uint64_add, drop)
                for x, y in zip(a1, a2):
                    assert np.array_equal(x, y), (uint64_add, drop)


def test_bloom_build_from_arrays_parity():
    """The array-path bulk build must produce the same words as the
    per-key path (same format as every other implementation)."""
    import numpy as np

    from rocksplicator_tpu.storage.bloom import BloomFilter

    rng = np.random.default_rng(11)
    keys = [bytes(rng.integers(0, 256, size=int(l), dtype=np.uint8))
            for l in rng.integers(1, 24, size=500)]
    ref = BloomFilter.build(keys)
    maxlen = max(len(k) for k in keys)
    mat = np.zeros((len(keys), maxlen), dtype=np.uint8)
    lens = np.zeros(len(keys), dtype=np.uint32)
    for i, k in enumerate(keys):
        mat[i, :len(k)] = np.frombuffer(k, dtype=np.uint8)
        lens[i] = len(k)
    got = BloomFilter.build_from_arrays(mat, lens)
    assert np.array_equal(ref.words, got.words)
    for k in keys[:50]:
        assert got.may_contain(k)


def test_native_compaction_backend_engine_parity(tmp_path):
    """The engine's default backend (NativeCompactionBackend direct
    array sink) must produce byte-identical post-compaction content to
    the streaming heap-merge across a mixed put/merge/delete workload —
    and actually take the direct sink for uniform inputs."""
    from rocksplicator_tpu.storage import DB, DBOptions
    from rocksplicator_tpu.storage.compaction import CpuCompactionBackend
    from rocksplicator_tpu.storage.merge import UInt64AddOperator
    from rocksplicator_tpu.storage.native_compaction import (
        NativeCompactionBackend,
    )

    def run(backend, name):
        opts = DBOptions(memtable_bytes=1 << 16,
                         compaction_backend=backend,
                         merge_operator=UInt64AddOperator(),
                         disable_auto_compaction=True)
        db = DB(str(tmp_path / name), opts)
        val = b"\x02\x00\x00\x00\x00\x00\x00\x00"
        for r in range(4):
            for i in range(1500):
                k = f"key{(i * 13 + r) % 3000:012d}+".encode()
                m = (i + r) % 5
                if m == 0:
                    db.merge(k, val)
                elif m == 1:
                    db.delete(k)
                else:
                    db.put(k, f"v{r}{i % 97}".encode().ljust(8, b"."))
            db.flush()
        db.compact_range()
        out = list(db.new_iterator())
        db.close()
        return out

    heap = run(CpuCompactionBackend(), "heap")
    native = run(NativeCompactionBackend(), "native")
    assert heap == native and len(heap) > 0

    # the direct sink path really engages (returns outputs, not None)
    called = {}
    backend = NativeCompactionBackend()
    orig = NativeCompactionBackend.merge_runs_to_files

    def spy(self, *a, **kw):
        out = orig(self, *a, **kw)
        called["result"] = out is not None
        return out

    NativeCompactionBackend.merge_runs_to_files = spy
    try:
        run(backend, "spied")
    finally:
        NativeCompactionBackend.merge_runs_to_files = orig
    assert called.get("result") is True, "direct array sink never engaged"


def test_uint64add_non8byte_puts_survive_compaction(tmp_path):
    """Regression (round-5 review): uint64-add fold semantics assume
    8-byte values — a lone 4-byte PUT under UInt64AddOperator must stay
    verbatim through compaction (the array sink would rewrite it to the
    parsed-as-zero operand sum); such shapes must route to the stream
    path on EVERY backend."""
    from rocksplicator_tpu.storage import DB, DBOptions
    from rocksplicator_tpu.storage.merge import UInt64AddOperator
    from rocksplicator_tpu.storage.native_compaction import (
        NativeCompactionBackend,
    )
    from rocksplicator_tpu.tpu.backend import NumpyCompactionBackend

    opts = DBOptions(memtable_bytes=1 << 14,
                     merge_operator=UInt64AddOperator(),
                     disable_auto_compaction=True)
    db = DB(str(tmp_path / "db"), opts)
    for r in range(3):
        for i in range(500):
            db.put(f"k{i:06d}".encode(), b"abcd")  # 4-byte values
        db.flush()
    db.compact_range()
    assert db.get(b"k000007") == b"abcd"
    assert db.get(b"k000499") == b"abcd"
    db.close()

    # the tuple-path backend too
    entries = [(b"kx", 3, 1, b"abcd"), (b"ky", 2, 1, b"abcd")]
    out = list(NumpyCompactionBackend().merge_runs(
        [entries], UInt64AddOperator(), True))
    assert out == [(b"kx", 3, 1, b"abcd"), (b"ky", 2, 1, b"abcd")]

    # and 8-byte counter workloads still take the direct sink
    called = {}
    orig = NativeCompactionBackend.merge_runs_to_files

    def spy(self, *a, **kw):
        res = orig(self, *a, **kw)
        called["engaged"] = res is not None
        return res

    NativeCompactionBackend.merge_runs_to_files = spy
    try:
        db2 = DB(str(tmp_path / "db2"), DBOptions(
            memtable_bytes=1 << 14, merge_operator=UInt64AddOperator(),
            disable_auto_compaction=True))
        one = (1).to_bytes(8, "little")
        for r in range(3):
            for i in range(500):
                db2.merge(f"c{i:06d}".encode(), one)
            db2.flush()
        db2.compact_range()
        assert db2.get(b"c000007") == (3).to_bytes(8, "little")
        db2.close()
    finally:
        NativeCompactionBackend.merge_runs_to_files = orig
    assert called.get("engaged") is True


def test_native_kway_runs_merge_parity():
    """cpu_merge_resolve_runs (k-way merge over pre-sorted runs) must be
    element-exact with the full-sort resolve over the same concatenated
    lanes — runs in the engine's own comparator order."""
    import numpy as np

    from rocksplicator_tpu.models.compaction_model import synth_counter_batch
    from rocksplicator_tpu.ops.kv_format import KVBatch
    from rocksplicator_tpu.storage.native.binding import get_native
    from rocksplicator_tpu.storage.native_compaction import (
        NativeCompactionBackend,
    )
    from rocksplicator_tpu.tpu.backend import cpu_merge_resolve

    lib = get_native()
    if lib is None or not getattr(lib, "has_merge_resolve_runs", False):
        pytest.skip("native k-way merge unavailable")
    runs = []
    for r in range(5):
        d = synth_counter_batch(2048, key_space=512, seed=100 + r,
                                key_bytes=16)
        cols = NativeCompactionBackend._sort_cols(d)
        order = np.lexsort(tuple(reversed(cols)))
        run = {k: v[order] for k, v in d.items()}
        assert NativeCompactionBackend._run_is_sorted(run)
        runs.append(run)
    fields = ("key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
              "val_words", "val_len")
    lanes = {f: np.concatenate([p[f] for p in runs]) for f in fields}
    total = len(lanes["key_len"])
    offsets = np.zeros(len(runs) + 1, dtype=np.uint64)
    np.cumsum([2048] * len(runs), out=offsets[1:])
    seq = (lanes["seq_hi"].astype(np.uint64) << np.uint64(32)) \
        | lanes["seq_lo"].astype(np.uint64)
    batch = KVBatch(
        key_words_be=lanes["key_words_be"],
        key_words_le=lanes["key_words_be"], key_len=lanes["key_len"],
        seq_hi=lanes["seq_hi"], seq_lo=lanes["seq_lo"],
        vtype=lanes["vtype"], val_words=lanes["val_words"],
        val_len=lanes["val_len"], valid=np.ones(total, bool), val_bytes=8)
    for ua in (True, False):
        for drop in (True, False):
            kway = lib.merge_resolve_runs(
                lanes["key_words_be"], lanes["key_len"], seq,
                lanes["vtype"], lanes["val_words"], lanes["val_len"],
                offsets, ua, drop)
            full, count = cpu_merge_resolve(batch, ua, drop)
            n = kway[6]
            assert n == count, (ua, drop, n, count)
            assert np.array_equal(kway[0][:n], full[0])
            assert np.array_equal(kway[1][:n], full[1])
            assert np.array_equal(
                (kway[2][:n] >> np.uint64(32)).astype(np.uint32), full[2])
            assert np.array_equal(
                (kway[2][:n] & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                full[3])
            assert np.array_equal(kway[3][:n].astype(full[4].dtype),
                                  full[4])
            assert np.array_equal(kway[4][:n], full[5])
            assert np.array_equal(kway[5][:n], full[6])

    # an UNSORTED run must fail the sortedness gate (the wrapper's
    # contract: callers verify before dispatching to the k-way path)
    shuffled = {k: v[::-1] for k, v in runs[0].items()}
    assert not NativeCompactionBackend._run_is_sorted(shuffled)


def test_direct_sink_midloop_failure_cleans_outputs(tmp_path):
    """A failure while writing output file N must remove files 1..N-1:
    the engine falls back to the tuple path and nothing would ever
    reference or GC the orphans."""

    from rocksplicator_tpu.storage.merge import UInt64AddOperator
    from rocksplicator_tpu.storage.native_compaction import (
        NativeCompactionBackend,
    )

    entries = [(f"k{i:08d}".encode(), i + 1, 1,
                (i).to_bytes(8, "little")) for i in range(5000)]
    backend = NativeCompactionBackend()
    made = []

    def path_factory():
        if len(made) == 1:
            raise OSError("disk full (simulated)")
        p = str(tmp_path / f"out{len(made)}.tsst")
        made.append(p)
        return p

    with pytest.raises(OSError):
        backend.merge_runs_to_files(
            [entries], UInt64AddOperator(), True, path_factory,
            block_bytes=4096, compression=0, bits_per_key=10,
            target_file_bytes=16_000,  # forces multiple output files
        )
    assert made and not os.path.exists(made[0]), (
        "orphaned output file left on disk after mid-loop failure")


# ---------------------------------------------------------------------------
# whole-file codecs: one native call per file (tpu/format.py sink + source)
# ---------------------------------------------------------------------------

_LANE_FIELDS = ("key_words_be", "key_words_le", "key_len", "seq_hi",
                "seq_lo", "vtype", "val_words", "val_len")

# name: rows, block_entries, compression, klen, vlen, seq past 32 bits,
# kept tombstones. The first two are counter_64x20k.refresh's own files.
_FILE_CASES = {
    "cell_compaction_output": (20250, 124, 1, 16, 8, False, False),
    "cell_flush_file": (5875, 124, 1, 16, 8, False, True),
    "tail_block": (300, 124, 1, 16, 8, False, False),
    "one_entry": (1, 124, 1, 16, 8, False, False),
    "seq_past_32_bits": (700, 64, 1, 16, 8, True, False),
    "kept_tombstones": (700, 64, 1, 16, 8, False, True),
    "codec_none": (700, 64, 0, 16, 8, False, True),
    "codec_rlz": (700, 64, 4, 16, 8, True, True),
    "klen_10_vlen_20": (700, 64, 1, 10, 20, False, True),
    "no_value": (700, 64, 1, 7, 0, False, False),
}


def _file_lanes(rows, klen, vlen, big_seq, deletes, seed=0):
    rng = np.random.default_rng(seed + rows)
    kb = np.zeros((rows, 24), dtype=np.uint8)
    kb[:, :klen] = rng.integers(0, 256, (rows, klen), dtype=np.uint8)
    kb = kb[np.lexsort(kb[:, ::-1].T)]  # rows ascending as byte strings
    vtype = np.ones(rows, dtype=np.uint32)
    val_len = np.full(rows, vlen, dtype=np.uint32)
    vb = np.zeros((rows, max(2, (vlen + 3) // 4) * 4), dtype=np.uint8)
    vb[:, :vlen] = rng.integers(0, 256, (rows, vlen), dtype=np.uint8)
    if deletes:
        dead = rng.random(rows) < 0.15
        vtype[dead], val_len[dead], vb[dead] = 2, 0, 0
    return {
        "key_words_be": kb.view(">u4").astype(np.uint32).reshape(rows, 6),
        "key_words_le": kb.view("<u4").reshape(rows, 6).copy(),
        "key_len": np.full(rows, klen, dtype=np.uint32),
        "seq_hi": np.full(rows, 3 if big_seq else 0, dtype=np.uint32),
        "seq_lo": rng.integers(1, 2 ** 32, rows, dtype=np.uint64
                               ).astype(np.uint32),
        "vtype": vtype,
        "val_words": vb.view("<u4").reshape(rows, -1).copy(),
        "val_len": val_len,
    }


def _python_codecs(monkeypatch):
    """Hide the whole-file codecs only (the Python block loops still use
    the library's rlz, as they do in a process that has it)."""
    monkeypatch.setattr(NATIVE, "has_file_codecs", False)


def _assert_same_lanes(got, want):
    assert got is not None and want is not None
    for f in _LANE_FIELDS:
        assert got[f].dtype == want[f].dtype, f
        assert got[f].shape == want[f].shape, f
        assert np.array_equal(got[f], want[f]), f


def _write_case(case, path, bloom=True):
    from rocksplicator_tpu.tpu.format import write_sst_from_arrays

    rows, block_entries, compression, klen, vlen, big_seq, deletes = \
        _FILE_CASES[case]
    lanes = _file_lanes(rows, klen, vlen, big_seq, deletes)
    props = write_sst_from_arrays(
        lanes, rows, path, block_entries=block_entries,
        compression=compression, planar=True,
        bloom_words=np.arange(64, dtype=np.uint32) if bloom else None)
    assert props is not None
    return lanes, props


@pytest.mark.parametrize("case", sorted(_FILE_CASES))
def test_native_planar_sink_writes_the_python_sinks_file(
        case, tmp_path, monkeypatch):
    """Same props, same index, same uncompressed bytes in every block,
    same lanes back: the native sink's file IS the Python sink's."""
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu.format import read_sst_arrays
    from rocksplicator_tpu.utils.stats import Stats

    assert NATIVE.has_file_codecs
    before = Stats.get().get_counter("codec.native_files")
    p_native = str(tmp_path / "native.tsst")
    lanes, props_native = _write_case(case, p_native)
    assert Stats.get().get_counter("codec.native_files") == before + 1
    _python_codecs(monkeypatch)
    p_python = str(tmp_path / "python.tsst")
    _lanes, props_python = _write_case(case, p_python)
    for key in ("planar", "block_chk", "num_keys", "num_entries",
                "min_key", "max_key", "min_seq", "max_seq"):
        assert props_native[key] == props_python[key], key
    rn, rp = SSTReader(p_native), SSTReader(p_python)
    try:
        assert len(rn._index) == len(rp._index) == len(
            props_native["block_chk"]["values"])
        assert [e[0] for e in rn._index] == [e[0] for e in rp._index]
        for i in range(len(rn._index)):
            assert (rn._read_block(i, fill_cache=False)
                    == rp._read_block(i, fill_cache=False)), i
        want = read_sst_arrays(rp)  # Python sink, Python source
        _assert_same_lanes(read_sst_arrays(rn), want)
        for f in _LANE_FIELDS:
            assert np.array_equal(want[f], lanes[f]), f
        assert list(rn.iterate()) == list(rp.iterate())
    finally:
        rn.close()
        rp.close()


def test_native_planar_sink_same_libz_same_file(tmp_path, monkeypatch):
    """Python's zlib and the library's are one libz here, so even the
    compressed bytes are the Python sink's: the files are identical."""
    p_native = str(tmp_path / "native.tsst")
    p_python = str(tmp_path / "python.tsst")
    _write_case("cell_compaction_output", p_native)
    _python_codecs(monkeypatch)
    _write_case("cell_compaction_output", p_python)
    with open(p_native, "rb") as a, open(p_python, "rb") as b:
        assert a.read() == b.read()


def test_native_planar_sink_builds_bloom_from_keys(tmp_path):
    """Without a prebuilt bloom the sink builds one from the keys."""
    from rocksplicator_tpu.storage.sst import SSTReader

    path = str(tmp_path / "f.tsst")
    lanes, _props = _write_case("tail_block", path, bloom=False)
    r = SSTReader(path)
    keys = np.ascontiguousarray(
        lanes["key_words_be"].astype(">u4")).view(np.uint8)[:, :16]
    assert all(r.get(keys[i].tobytes()) is not None for i in (0, 150, 299))
    r.close()


@pytest.mark.parametrize("sink", ["native_sink", "python_sink"])
@pytest.mark.parametrize("case", sorted(_FILE_CASES))
def test_each_sinks_file_reads_the_same_by_each_source(
        case, sink, tmp_path, monkeypatch):
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu import format as fmt

    path = str(tmp_path / "f.tsst")
    if sink == "python_sink":
        with monkeypatch.context() as m:
            _python_codecs(m)
            lanes, _props = _write_case(case, path)
    else:
        lanes, _props = _write_case(case, path)
    r = SSTReader(path)
    try:
        with monkeypatch.context() as m:
            # the native source must not lean on the Python one
            m.setattr(fmt, "_read_planar_arrays", None)
            from_native = fmt.read_sst_arrays(r)
        _python_codecs(monkeypatch)
        from_python = fmt.read_sst_arrays(r)
        _assert_same_lanes(from_native, from_python)
        for f in _LANE_FIELDS:
            assert np.array_equal(from_native[f], lanes[f]), f
    finally:
        r.close()


def _write_rows(path, rows, compression, klen=16, vlen=8, seed=0,
                global_seqno=None, extra_props=None):
    """A row-format file as a bulk loader writes it (SSTWriter.add)."""
    from rocksplicator_tpu.storage.sst import SSTWriter

    lanes = _file_lanes(rows, klen, vlen, False, False, seed)
    kb = np.ascontiguousarray(
        lanes["key_words_be"].astype(">u4")).view(np.uint8)
    vb = lanes["val_words"].view(np.uint8)
    w = SSTWriter(path, compression=compression)
    for i in range(rows):
        w.add(kb[i, :klen].tobytes(), 1000 - i % 7, 1, vb[i, :vlen].tobytes())
    w.finish(global_seqno=global_seqno, extra_props=extra_props)


@pytest.mark.parametrize("case", [
    # rows, compression, klen, vlen, global_seqno, the sink's prop
    pytest.param((20000, 1, 16, 8, 7, False), id="cell_bulk_file"),
    pytest.param((900, 0, 16, 8, None, False), id="codec_none"),
    pytest.param((900, 4, 16, 8, (5 << 32) + 9, False), id="codec_rlz"),
    pytest.param((900, 1, 16, 8, None, True), id="uniform_prop"),
    pytest.param((900, 1, 9, 23, 11, False), id="klen_9_vlen_23"),
    pytest.param((900, 1, 9, 23, None, True), id="klen_9_vlen_23_prop"),
    pytest.param((1, 1, 24, 0, None, False), id="one_entry_no_value"),
])
def test_native_row_source_matches_python(case, tmp_path, monkeypatch):
    """Row-format blocks (a bulk loader's file): widths inferred from
    block 0 or taken from the sink's prop, global_seqno stamped."""
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu import format as fmt

    rows, compression, klen, vlen, seqno, prop = case
    path = str(tmp_path / "rows.tsst")
    _write_rows(path, rows, compression, klen, vlen, global_seqno=seqno,
                extra_props={"uniform": [klen, vlen]} if prop else None)
    r = SSTReader(path)
    try:
        with monkeypatch.context() as m:
            m.setattr(fmt, "_read_uniform_arrays", None)
            from_native = fmt.read_sst_arrays(r)
        _python_codecs(monkeypatch)
        from_python = fmt.read_sst_arrays(r)
        _assert_same_lanes(from_native, from_python)
        assert len(from_native["key_len"]) == rows
        if seqno is not None:
            assert (from_native["seq_lo"] == seqno & 0xFFFFFFFF).all()
            assert (from_native["seq_hi"] == seqno >> 32).all()
    finally:
        r.close()


def test_native_row_source_verifies_poly1_block_chk(tmp_path, monkeypatch):
    """A row-format file with byte-domain checksums (the device block
    encoder's): the native source computes each and the caller holds it
    against the prop — intact reads, a flipped byte raises Corruption."""
    from rocksplicator_tpu.storage.errors import Corruption
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu import format as fmt
    from rocksplicator_tpu.utils.checksum import poly_checksum

    rows, block_entries, stride = 500, 100, 17 + 16 + 8
    lanes = _file_lanes(rows, 16, 8, False, False)
    chks = [poly_checksum(
        fmt.encode_uniform_block(lanes, s, min(s + block_entries, rows),
                                 16, 8), length=block_entries * stride)
        for s in range(0, rows, block_entries)]
    path = str(tmp_path / "rows.tsst")
    assert fmt.write_sst_from_arrays(
        lanes, rows, path, block_entries=block_entries, compression=0,
        device_checksums=np.asarray(chks, dtype=np.uint32)) is not None
    monkeypatch.setattr(fmt, "_read_uniform_arrays", None)
    r = SSTReader(path)
    got = fmt.read_sst_arrays(r)
    for f in _LANE_FIELDS:
        assert np.array_equal(got[f], lanes[f]), f
    r.close()
    with open(path, "r+b") as f:
        f.seek(2 * block_entries * stride + 30)  # inside block 2
        b = f.read(1)
        f.seek(-1, 1)
        f.write(bytes([b[0] ^ 0x01]))
    r = SSTReader(path)
    with pytest.raises(Corruption, match="block 2 checksum mismatch"):
        fmt.read_sst_arrays(r)
    r.close()


def test_native_row_source_width_drift_is_not_lanes(tmp_path, monkeypatch):
    """Width drift still yields None (the tuple path's file), decided by
    the native source itself; a prop it cannot read is the Python
    source's to judge."""
    from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
    from rocksplicator_tpu.tpu import format as fmt
    from rocksplicator_tpu.utils.stats import Stats

    # 41 + 37 + 45 = 3 x 41: the block-0 probe passes, the rows do not
    tricky = str(tmp_path / "tricky.tsst")
    w = SSTWriter(tricky)
    w.add(b"a" * 16, 3, 1, b"12345678")
    w.add(b"b" * 16, 2, 1, b"1234")
    w.add(b"c" * 16, 1, 1, b"123456789012")
    w.finish()
    # drift in a later block only
    late = str(tmp_path / "late.tsst")
    w = SSTWriter(late, block_bytes=41 * 50)
    for i in range(120):
        w.add(f"key{i:013d}".encode(), 1, 1, b"12345678" if i < 110
              else b"1234")
    w.finish()
    before = Stats.get().get_counter("codec.python_files")
    with monkeypatch.context() as m:
        m.setattr(fmt, "_read_uniform_arrays", None)
        for path in (tricky, late):
            r = SSTReader(path)
            assert fmt.read_sst_arrays(r) is None
            r.close()
    # not lanes: counted with the files the interpreter decodes
    assert Stats.get().get_counter("codec.python_files") == before + 2
    foreign = str(tmp_path / "foreign.tsst")
    w = SSTWriter(foreign)
    w.add(b"k" * 30, 1, 1, b"v")
    w.finish(extra_props={"uniform": [30, 1]})
    r = SSTReader(foreign)
    assert fmt._read_lanes_native(r, False) is fmt._NOT_TAKEN
    assert fmt.read_sst_arrays(r) is None
    r.close()


def test_native_source_leaves_unknown_block_codecs_to_python(tmp_path):
    """A codec nibble the library does not know is not guessed at."""
    from rocksplicator_tpu.storage.errors import Corruption
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu import format as fmt

    path = str(tmp_path / "rows.tsst")
    _write_rows(path, 200, 0)
    r = SSTReader(path)
    r._index[0] = r._index[0][:3] + (9,)
    assert fmt._read_lanes_native(r, False) is fmt._NOT_TAKEN
    with pytest.raises(Corruption, match="unsupported block codec 9"):
        fmt.read_sst_arrays(r)
    r.close()


@pytest.mark.parametrize("library", ["native", "hidden"])
def test_codec_counters_say_which_codec_ran(library, tmp_path, monkeypatch):
    """A flush and a batched post-load compaction (CPU backend): every
    file read and written is counted, under the codec that took it —
    all native with the library, all Python with it hidden — and the
    answers are the same."""
    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.storage import DB, DBOptions
    from rocksplicator_tpu.storage.merge import UInt64AddOperator
    from rocksplicator_tpu.storage.native import binding
    from rocksplicator_tpu.storage.records import WriteBatch
    from rocksplicator_tpu.storage.sst import SSTWriter
    from rocksplicator_tpu.tpu.compaction_service import compact_dbs_batched
    from rocksplicator_tpu.utils.stats import Stats

    if library == "hidden":
        monkeypatch.setattr(binding, "_native", None)
    SpanCollector.reset_for_test()
    SpanCollector.get().configure(sample_rate=1.0)
    pack = struct.Struct("<Q").pack
    stats = Stats.get()
    before = {k: stats.get_counter(k)
              for k in ("codec.native_files", "codec.python_files")}
    dbs = []
    for s in range(2):
        db = DB(str(tmp_path / f"db{s}"),
                DBOptions(merge_operator=UInt64AddOperator()))
        for i in range(300):
            db.write(WriteBatch().merge(f"key{i:013d}".encode(), pack(i)))
        db.write(WriteBatch().delete(b"key" + b"0" * 13))
        db.flush()                                   # 1 file written
        sst = str(tmp_path / f"in{s}.tsst")
        w = SSTWriter(sst)
        for i in range(100, 400):
            w.add(f"key{i:013d}".encode(), 0, 1, pack(s * 1000 + i))
        w.finish()
        db.ingest_external_file([sst], move_files=True,
                                allow_global_seqno=True)
        dbs.append((f"db{s}", db))
    handled, remaining = compact_dbs_batched(dbs)    # 2 read, 1 written
    assert sorted(handled) == ["db0", "db1"] and remaining == []
    native = stats.get_counter("codec.native_files") - before[
        "codec.native_files"]
    python = stats.get_counter("codec.python_files") - before[
        "codec.python_files"]
    assert (native, python) == ((8, 0) if library == "native" else (0, 8))
    for s, (_name, db) in enumerate(dbs):
        assert db.get(b"key" + b"0" * 13) is None
        assert db.get(f"key{50:013d}".encode()) == pack(50)
        assert db.get(f"key{150:013d}".encode()) == pack(s * 1000 + 150)
        assert db.get(f"key{399:013d}".encode()) == pack(s * 1000 + 399)
        db.close()
    spans = [s for s in SpanCollector.get().snapshot()
             if s["name"] in ("flush.encode", "tpu.lanes.decode",
                              "tpu.planar.write")]
    assert {s["name"] for s in spans} == {
        "flush.encode", "tpu.lanes.decode", "tpu.planar.write"}
    assert all(s["annotations"]["native"] == int(library == "native")
               for s in spans)
    SpanCollector.reset_for_test()


@pytest.mark.parametrize("compression", [0, 1, 4])
@pytest.mark.parametrize("layout", ["planar", "rows"])
def test_native_read_block_matches_python(layout, compression, tmp_path,
                                          monkeypatch):
    """A point read's block through one native call (pread, inflate,
    block_chk) is the Python reader's block, byte for byte, and a block
    that fails its checksum still raises Corruption."""
    from rocksplicator_tpu.storage.errors import Corruption
    from rocksplicator_tpu.storage.sst import BlockCache, SSTReader
    from rocksplicator_tpu.tpu.format import write_sst_from_arrays

    monkeypatch.setattr(BlockCache, "_instance", None)
    monkeypatch.setattr(BlockCache, "_disabled", True)
    rows, block_entries = 500, 100
    lanes = _file_lanes(rows, 16, 8, False, layout == "planar")
    path = str(tmp_path / "f.tsst")
    assert write_sst_from_arrays(
        lanes, rows, path, block_entries=block_entries,
        compression=compression, planar=layout == "planar") is not None
    r = SSTReader(path)
    calls = []
    real = NATIVE.read_block
    monkeypatch.setattr(
        NATIVE, "read_block",
        lambda *a: calls.append(a) or real(*a))
    native_blocks = [r._read_block(i) for i in range(len(r._index))]
    assert len(calls) == len(r._index)
    if layout == "planar":  # each held to its poly1w value, then memoed
        assert r._verified_blocks == set(range(len(r._index)))
        assert all(c[4] == 2 for c in calls)
    entries = list(r.iterate())
    r.close()
    with monkeypatch.context() as m:
        _python_codecs(m)
        r = SSTReader(path)
        assert [r._read_block(i) for i in range(len(r._index))] \
            == native_blocks
        assert list(r.iterate()) == entries
        off1, size1 = r._index[1][1], r._index[1][2]
        r.close()
    if layout == "planar":
        with open(path, "r+b") as f:
            f.seek(off1 + size1 - 6)
            b = f.read(1)
            f.seek(-1, 1)
            f.write(bytes([b[0] ^ 0x04]))
        r = SSTReader(path)
        assert r._read_block(0) == native_blocks[0]
        with pytest.raises((Corruption, zlib.error, ValueError)):
            r._read_block(1)
        r.close()


def test_native_short_calls_keep_the_gil():
    """Point lookups and small RLZ transforms go through the PyDLL
    handle (no GIL hand-over for microseconds of C); a large buffer
    goes through the handle that drops it."""
    import ctypes

    from rocksplicator_tpu.storage.native import binding

    assert isinstance(NATIVE._held, ctypes.PyDLL)
    assert not isinstance(NATIVE._lib, ctypes.PyDLL)
    assert NATIVE._for_bytes(4096) is NATIVE._held
    assert NATIVE._for_bytes(binding._SHORT_CALL_BYTES + 1) is NATIVE._lib
    big = os.urandom(1024) * 100  # beyond the short-call size
    for data in (b"", b"abc" * 1000, big):
        packed = NATIVE.rlz_compress(data)
        assert NATIVE.rlz_decompress(packed, len(data) + 1) == data


def _random_frame(seed: int, num_ops: int) -> bytes:
    """A WriteBatch frame of all four op types, keys and values of 0 to
    300 bytes, built straight from the format."""
    import random

    r = random.Random(seed)
    parts = [struct.pack("<I", num_ops)]
    for _ in range(num_ops):
        key = r.randbytes(r.choice((0, 1, 9, 16, r.randint(0, 300))))
        val = r.randbytes(r.choice((0, 8, 8, r.randint(0, 300))))
        parts.append(struct.pack("<BI", r.randint(1, 4), len(key)) + key
                     + struct.pack("<I", len(val)) + val)
    return b"".join(parts)


@pytest.mark.parametrize("seed,num_ops", [
    (1, 0), (2, 1), (3, 2), (4, 17), (5, 512), (6, 1999), (7, 2000)])
def test_batch_index_matches_the_python_walk(seed, num_ops):
    """One native call over a frame's op headers gives what the Python
    walk gives: types, the four columns, the end; from any op on."""
    from rocksplicator_tpu.storage.records import _walk_ops

    raw = _random_frame(seed, num_ops)
    types, cols, end = NATIVE.batch_index(raw, 4, num_ops)
    ref_types, ref_cols, ref_end = _walk_ops(raw, 4, num_ops)
    assert end == ref_end == len(raw)
    assert types.dtype == ref_types.dtype == np.uint8
    assert cols.dtype == ref_cols.dtype == np.int64
    assert cols.shape == ref_cols.shape == (4, num_ops)
    np.testing.assert_array_equal(types, ref_types)
    np.testing.assert_array_equal(cols, ref_cols)
    # the columns say where the frame's own bytes lie
    for i in range(0, num_ops, 97):
        ko, kl, vo, vl = (int(c) for c in cols[:, i])
        assert raw[ko - 5] == types[i]
        assert struct.unpack_from("<I", raw, ko - 4)[0] == kl
        assert struct.unpack_from("<I", raw, ko + kl) == (vl,)
        assert vo == ko + kl + 4
    if num_ops > 2:  # from the third op on, as behind a frame's rows
        start = int(cols[2, 1] + cols[3, 1])
        tail = NATIVE.batch_index(raw, start, num_ops - 2)
        ref_tail = _walk_ops(raw, start, num_ops - 2)
        np.testing.assert_array_equal(tail[0], types[2:])
        np.testing.assert_array_equal(tail[1], cols[:, 2:])
        np.testing.assert_array_equal(ref_tail[1], cols[:, 2:])
        assert tail[2] == ref_tail[2] == end


@pytest.mark.parametrize("seed,num_ops", [(11, 0), (12, 3), (13, 512),
                                          (14, 2000)])
def test_decode_batch_is_the_same_with_the_library_hidden(
        seed, num_ops, monkeypatch):
    from rocksplicator_tpu.storage.native import binding
    from rocksplicator_tpu.storage.records import (
        decode_batch, scan_batch_meta)

    raw = _random_frame(seed, num_ops)
    with_lib = decode_batch(raw)
    meta = scan_batch_meta(raw)
    monkeypatch.setattr(binding, "_native", None)
    assert not binding.native_available()
    hidden = decode_batch(raw)
    assert hidden.columns() == with_lib.columns()
    assert hidden.columns().frame_pass == "indexed"
    assert scan_batch_meta(raw) == meta == (
        with_lib.count(), with_lib.extract_timestamp_ms())
    assert hidden._built is None and with_lib._built is None
    assert list(hidden.ops()) == list(with_lib.ops())
    assert len(list(hidden.ops())) == num_ops
