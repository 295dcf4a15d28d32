"""TpuCompactionService / backend / mesh tests (virtual CPU devices)."""

import struct

import numpy as np
import pytest

from rocksplicator_tpu.models import CompactionModel, synth_counter_batch
from rocksplicator_tpu.ops import MergeKind, pack_entries
from rocksplicator_tpu.storage import DB, DBOptions, UInt64AddOperator, WriteBatch
from rocksplicator_tpu.storage.bloom import BloomFilter
from rocksplicator_tpu.storage.compaction import CpuCompactionBackend
from rocksplicator_tpu.storage.records import OpType
from rocksplicator_tpu.tpu import (
    NumpyCompactionBackend,
    TpuCompactionBackend,
    TpuCompactionService,
)

pack64 = struct.Struct("<q").pack


def test_tpu_backend_in_real_db_compaction(tmp_path):
    """A DB whose compactions run through the TPU backend produces the
    same state as the CPU backend."""
    opts_tpu = DBOptions(
        merge_operator=UInt64AddOperator(),
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=2,
        memtable_bytes=1 << 30,
    )
    opts_cpu = DBOptions(
        merge_operator=UInt64AddOperator(),
        level0_compaction_trigger=2,
        memtable_bytes=1 << 30,
    )
    dbs = {}
    for name, opts in (("tpu", opts_tpu), ("cpu", opts_cpu)):
        db = DB(str(tmp_path / name), opts)
        for r in range(3):
            for i in range(40):
                db.merge(f"ctr{i:03d}".encode(), pack64(r + i))
            db.put(b"kill", b"x")
            db.delete(b"kill")
            db.flush()
        db.compact_range()
        dbs[name] = db
    tpu_items = list(dbs["tpu"].new_iterator())
    cpu_items = list(dbs["cpu"].new_iterator())
    assert tpu_items == cpu_items
    assert dbs["tpu"].get(b"ctr005") == pack64(3 * 5 + 3)
    for db in dbs.values():
        db.close()


def test_tpu_backend_fallback_long_keys(tmp_path):
    opts = DBOptions(
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100,
        memtable_bytes=1 << 30,
    )
    with DB(str(tmp_path / "db"), opts) as db:
        long_key = b"k" * 40  # exceeds the 24B lane width -> CPU fallback
        db.put(long_key, b"v1")
        db.put(b"short", b"v2")
        db.flush()
        db.compact_range()
        assert db.get(long_key) == b"v1"
        assert db.get(b"short") == b"v2"


def test_numpy_backend_matches_cpu():
    import random

    rng = random.Random(7)
    entries = []
    for seq in range(1, 400):
        k = f"k{rng.randrange(30):02d}".encode()
        r = rng.random()
        if r < 0.5:
            entries.append((k, seq, OpType.MERGE, pack64(rng.randrange(100))))
        elif r < 0.8:
            entries.append((k, seq, OpType.PUT, pack64(rng.randrange(100))))
        else:
            entries.append((k, seq, OpType.DELETE, b""))
    srt = sorted(entries, key=lambda e: (e[0], -e[1]))
    for drop in (True, False):
        got = [
            (k, int(vt), v) for k, s, vt, v in NumpyCompactionBackend().merge_runs(
                [srt], UInt64AddOperator(), drop)
        ]
        want = [
            (k, int(vt), v) for k, s, vt, v in CpuCompactionBackend().merge_runs(
                [srt], UInt64AddOperator(), drop)
        ]
        assert got == want


def test_service_shard_batch():
    service = TpuCompactionService()
    batches = []
    for s in range(3):
        entries = [
            (f"s{s}k{i:02d}".encode(), i + 1, OpType.MERGE, pack64(i))
            for i in range(20)
        ] + [(f"s{s}k00".encode(), 100, OpType.PUT, pack64(7))]
        batches.append(pack_entries(
            sorted(entries, key=lambda e: (e[0], -e[1]))
        ))
    results = service.compact_shard_batch(batches)
    assert len(results) == 3
    for s, res in enumerate(results):
        assert res["count"] == 20
        by_key = {k: v for k, _s, _vt, v in res["entries"]}
        assert by_key[f"s{s}k00".encode()] == pack64(7)  # PUT@100 shadows merge
        assert by_key[f"s{s}k05".encode()] == pack64(5)
        # TPU-built bloom matches all output keys
        bf = BloomFilter(len(res["bloom_words"]),
                         np.array(res["bloom_words"], dtype=np.uint32))
        for k in by_key:
            assert bf.may_contain(k)


def test_service_shard_stream_matches_batch():
    """The double-buffered streaming path is result-identical to the
    single-launch batch path, across group boundaries and padding."""
    service = TpuCompactionService()
    batches = []
    for s in range(7):  # not a multiple of group_size: last group padded
        entries = [
            (f"s{s}k{i:02d}".encode(), i + 1, OpType.MERGE, pack64(i))
            for i in range(16)
        ] + [(f"s{s}k00".encode(), 99, OpType.PUT, pack64(3))]
        batches.append(pack_entries(
            sorted(entries, key=lambda e: (e[0], -e[1]))
        ))
    want = service.compact_shard_batch(batches)
    got = service.compact_shard_stream(batches, group_size=3)
    assert len(got) == len(want) == 7
    for w, g in zip(want, got):
        assert g["count"] == w["count"]
        assert g["entries"] == w["entries"]
        assert np.array_equal(np.asarray(g["bloom_words"]),
                              np.asarray(w["bloom_words"]))


def test_model_forward_and_example_args():
    import jax

    model = CompactionModel(capacity=512)
    fn = jax.jit(model.forward)
    args = tuple(jax.numpy.asarray(a) for a in model.example_args())
    out = fn(*args)
    jax.block_until_ready(out)
    assert int(out["count"]) > 0
    assert np.asarray(out["bloom"]).any()


def test_sharded_compaction_step_on_mesh():
    """The multichip path on the virtual 8-device CPU mesh — the same code
    the driver dry-runs."""
    import __graft_entry__ as graft

    graft.dryrun_multichip(8)


def test_derive_block_axis():
    from rocksplicator_tpu.parallel.mesh import derive_block_axis

    # no size hint: legacy behavior (2 when even)
    assert derive_block_axis(8) == 2
    assert derive_block_axis(7) == 1
    assert derive_block_axis(1) == 1
    # job fits one device: all devices go to the shard axis
    assert derive_block_axis(8, shard_bytes=1 << 20) == 1
    # job 4x the per-device budget: 4-way block split
    target = 32 << 20
    assert derive_block_axis(8, shard_bytes=4 * target,
                             block_bytes_target=target) == 4
    # capped by the device count / divisibility
    assert derive_block_axis(8, shard_bytes=100 * target,
                             block_bytes_target=target) == 8
    assert derive_block_axis(6, shard_bytes=100 * target,
                             block_bytes_target=target) == 2


@pytest.mark.parametrize("block", [1, 2, 4])
def test_sharded_step_matches_single_device(block):
    """Blockwise-split merge must equal the single-batch merge, at every
    block-axis size the 8-device mesh supports (VERDICT item 10)."""
    import jax
    import jax.numpy as jnp

    from rocksplicator_tpu.parallel.mesh import (
        make_mesh, make_sharded_inputs, shard_inputs_on_mesh,
        sharded_compaction_step,
    )

    mesh = make_mesh(8, block=block)
    assert mesh.shape["block"] == block
    model = CompactionModel(capacity=128)
    step = sharded_compaction_step(mesh, model)
    arrays = make_sharded_inputs(mesh, shards_per_device=1,
                                 entries_per_block=128, model=model)
    out_final, bloom, counts, global_count, needs_fallback = step(
        *(jnp.asarray(arrays[k]) for k in (
            "key_words_be", "key_len", "seq_hi", "seq_lo",
            "vtype", "val_words", "val_len", "valid"))
    )
    # reference: single-device merge over each shard's concatenated blocks
    from rocksplicator_tpu.ops.compaction_kernel import merge_resolve_kernel

    S, B, N = arrays["key_len"].shape
    for s in range(S):
        concat = {
            k: np.concatenate([arrays[k][s, b] for b in range(B)])
            for k in arrays
        }
        ref = merge_resolve_kernel(
            jnp.asarray(concat["key_words_be"]),
            jnp.asarray(concat["key_len"]), jnp.asarray(concat["seq_hi"]),
            jnp.asarray(concat["seq_lo"]), jnp.asarray(concat["vtype"]),
            jnp.asarray(concat["val_words"]), jnp.asarray(concat["val_len"]),
            jnp.asarray(concat["valid"]),
            merge_kind=MergeKind.UINT64_ADD, drop_tombstones=True,
        )
        assert int(np.asarray(counts)[s, 0]) == int(ref["count"])
        n_out = int(ref["count"])
        got_keys = np.asarray(out_final["key_words_be"])[s, 0][:n_out]
        want_keys = np.asarray(ref["key_words_be"])[:n_out]
        assert np.array_equal(got_keys, want_keys)
        got_vals = np.asarray(out_final["val_words"])[s, 0][:n_out]
        want_vals = np.asarray(ref["val_words"])[:n_out]
        assert np.array_equal(got_vals, want_vals)


def test_chunked_merge_matches_single_shot():
    """Hierarchical chunked merging equals the single-launch kernel under
    the engine run invariant (runs hold disjoint ordered seq ranges)."""
    import numpy as np

    from rocksplicator_tpu.ops.kv_format import pack_entries, unpack_entries
    from rocksplicator_tpu.tpu.chunked import chunked_merge
    from rocksplicator_tpu.ops.compaction_kernel import merge_resolve_kernel
    import jax.numpy as jnp
    import random

    rng = random.Random(99)
    keys = [f"k{i:03d}".encode() for i in range(60)]
    runs = []
    seq = 1
    for _r in range(4):  # 4 runs with ascending disjoint seq ranges
        entries = []
        for _ in range(500):
            k = rng.choice(keys)
            x = rng.random()
            if x < 0.5:
                entries.append((k, seq, OpType.MERGE, pack64(rng.randrange(50))))
            elif x < 0.85:
                entries.append((k, seq, OpType.PUT, pack64(rng.randrange(100))))
            else:
                entries.append((k, seq, OpType.DELETE, b""))
            seq += 1
        entries.sort(key=lambda e: (e[0], -e[1]))
        runs.append(entries)

    for drop in (True, False):
        batches = [pack_entries(r) for r in runs]
        out = chunked_merge(batches, MergeKind.UINT64_ADD, drop,
                            chunk_entries=128, launch_entries=512)
        assert out is not None
        arrays, count = out
        got = unpack_entries(
            arrays["key_words_be"], arrays["key_len"], arrays["seq_hi"],
            arrays["seq_lo"], arrays["vtype"], arrays["val_words"],
            arrays["val_len"], count,
        )
        # reference: single big launch
        all_entries = [e for r in runs for e in r]
        big = pack_entries(all_entries)
        ref = merge_resolve_kernel(
            jnp.asarray(big.key_words_be),
            jnp.asarray(big.key_len), jnp.asarray(big.seq_hi),
            jnp.asarray(big.seq_lo), jnp.asarray(big.vtype),
            jnp.asarray(big.val_words), jnp.asarray(big.val_len),
            jnp.asarray(big.valid),
            merge_kind=MergeKind.UINT64_ADD, drop_tombstones=drop,
        )
        want = unpack_entries(
            np.asarray(ref["key_words_be"]), np.asarray(ref["key_len"]),
            np.asarray(ref["seq_hi"]), np.asarray(ref["seq_lo"]),
            np.asarray(ref["vtype"]), np.asarray(ref["val_words"]),
            np.asarray(ref["val_len"]), int(ref["count"]),
        )
        # values and keys must match exactly (seqs of folded entries may
        # differ between fold orders only if... they must match too: top
        # seq per key is fold-order independent)
        assert [(k, vt, v) for k, s, vt, v in got] == [
            (k, vt, v) for k, s, vt, v in want
        ], f"drop={drop}"


def test_backend_chunked_path_used_for_large_batches(monkeypatch):
    import rocksplicator_tpu.tpu.backend as backend_mod
    from rocksplicator_tpu.tpu.backend import TpuCompactionBackend

    monkeypatch.setattr(backend_mod, "MAX_TPU_ENTRIES", 256)
    entries1 = sorted(
        [(f"k{i:03d}".encode(), i + 1, OpType.MERGE, pack64(1))
         for i in range(200)], key=lambda e: (e[0], -e[1]))
    entries2 = sorted(
        [(f"k{i:03d}".encode(), 1000 + i, OpType.MERGE, pack64(2))
         for i in range(200)], key=lambda e: (e[0], -e[1]))
    got = sorted(TpuCompactionBackend().merge_runs(
        [entries1, entries2], UInt64AddOperator(), True),
        key=lambda e: e[0])
    assert len(got) == 200
    for k, s, vt, v in got:
        assert v == pack64(3)  # both runs' operands folded


def test_chunked_merge_level_ordered_runs_no_resurrection():
    """The exact review scenario: runs arrive level-ordered (L0 old, L0
    new, L1) — NOT seq-ordered — with a DELETE in the middle seq interval.
    Chunked grouping must not resurrect the deleted L1 base."""
    from rocksplicator_tpu.ops.kv_format import pack_entries, unpack_entries
    from rocksplicator_tpu.tpu.chunked import chunked_merge

    # shared filler keys so merged summaries SHRINK (otherwise the
    # reduction cannot converge at this tiny launch size); disjoint global
    # seq intervals per run (the engine invariant): l1=1..99,
    # l0_old=100..299, l0_new=300..499
    def fillers(base_seq):
        return [(f"f{i:03d}".encode(), base_seq + i, OpType.PUT, pack64(0))
                for i in range(50)]

    l1 = sorted(fillers(1) + [(b"k", 60, OpType.PUT, pack64(1000))],
                key=lambda e: (e[0], -e[1]))
    l0_old = sorted(fillers(100) + [(b"k", 200, OpType.DELETE, b"")],
                    key=lambda e: (e[0], -e[1]))
    l0_new = sorted(fillers(300) + [(b"k", 400, OpType.MERGE, pack64(7))],
                    key=lambda e: (e[0], -e[1]))
    # adversarial input order: greedy consecutive grouping would pair
    # l0_new with l1 (folding MERGE@400 onto PUT@60, skipping DELETE@200)
    # unless summaries are seq-sorted first
    batches = [pack_entries(r) for r in (l0_new, l1, l0_old)]
    out = chunked_merge(batches, MergeKind.UINT64_ADD, True,
                        chunk_entries=64, launch_entries=110)
    assert out is not None
    arrays, count = out
    got = {k: v for k, s, vt, v in unpack_entries(
        arrays["key_words_be"], arrays["key_len"], arrays["seq_hi"],
        arrays["seq_lo"], arrays["vtype"], arrays["val_words"],
        arrays["val_len"], count)}
    # DELETE@200 shadows PUT@60; MERGE@7 folds over the tombstone -> 7
    assert got[b"k"] == pack64(7), got.get(b"k")


def test_backend_chunked_path_actually_runs(monkeypatch):
    import rocksplicator_tpu.tpu.backend as backend_mod
    from rocksplicator_tpu.tpu.backend import TpuCompactionBackend

    monkeypatch.setattr(backend_mod, "MAX_TPU_ENTRIES", 256)
    calls = []
    import rocksplicator_tpu.tpu.chunked as chunked_mod

    real = chunked_mod.chunked_merge

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(backend_mod, "MAX_TPU_ENTRIES", 256)
    # patch where backend imports it (function-local import of the module)
    monkeypatch.setattr(chunked_mod, "chunked_merge", spy)
    entries1 = sorted(
        [(f"k{i:03d}".encode(), i + 1, OpType.MERGE, pack64(1))
         for i in range(200)], key=lambda e: (e[0], -e[1]))
    entries2 = sorted(
        [(f"k{i:03d}".encode(), 1000 + i, OpType.MERGE, pack64(2))
         for i in range(200)], key=lambda e: (e[0], -e[1]))
    got = list(TpuCompactionBackend().merge_runs(
        [entries1, entries2], UInt64AddOperator(), True))
    assert calls, "chunked path did not run"
    assert len(got) == 200


def test_direct_file_sink_matches_tuple_path(tmp_path):
    """TPU-backed compaction writing SSTs via the vectorized array sink
    (kernel bloom included) must produce the same DB state as the CPU
    tuple path, and the file must be fully readable."""
    opts_tpu = DBOptions(
        merge_operator=UInt64AddOperator(),
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
    )
    opts_cpu = DBOptions(
        merge_operator=UInt64AddOperator(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
    )
    dbs = {}
    for name, opts in (("tpu", opts_tpu), ("cpu", opts_cpu)):
        db = DB(str(tmp_path / name), opts)
        for r in range(2):
            for i in range(200):
                # uniform widths: 8-byte keys, 8-byte values
                db.merge(f"k{i:06d}".encode(), pack64(r * 10 + i))
            db.put(b"dltme00", pack64(1))
            db.delete(b"dltme00")
            db.flush()
        db.compact_range()
        dbs[name] = db
    assert list(dbs["tpu"].new_iterator()) == list(dbs["cpu"].new_iterator())
    # bloom-backed point reads on the TPU-written file
    assert dbs["tpu"].get(b"k000123") == pack64(123 + 10 + 123)
    assert dbs["tpu"].get(b"k999999") is None
    assert dbs["tpu"].get(b"dltme00") is None
    # the direct sink actually wrote the compacted level (one file)
    import os as _os
    tpu_files = [f for f in _os.listdir(str(tmp_path / "tpu"))
                 if f.endswith(".tsst")]
    assert len(tpu_files) == 1
    for db in dbs.values():
        db.close()


def test_direct_sink_falls_back_on_mixed_widths(tmp_path):
    opts = DBOptions(
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
    )
    with DB(str(tmp_path / "db"), opts) as db:
        db.put(b"short", b"v")
        db.put(b"a-much-longer-key", b"value-of-other-len")
        db.flush()
        db.compact_range()  # mixed widths -> tuple path, still correct
        assert db.get(b"short") == b"v"
        assert db.get(b"a-much-longer-key") == b"value-of-other-len"


def test_direct_sink_splits_at_target_file_bytes(tmp_path):
    opts = DBOptions(
        merge_operator=UInt64AddOperator(),
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
        target_file_bytes=8 * 1024,  # tiny: force splitting
    )
    with DB(str(tmp_path / "db"), opts) as db:
        for i in range(2000):
            db.put(f"k{i:06d}".encode(), pack64(i))
        db.flush()
        db.compact_range()
        import os as _os
        files = [f for f in _os.listdir(str(tmp_path / "db"))
                 if f.endswith(".tsst")]
        assert len(files) > 1  # split into multiple target-sized files
        for i in range(0, 2000, 333):
            assert db.get(f"k{i:06d}".encode()) == pack64(i)
        assert len(list(db.new_iterator())) == 2000


def test_direct_sink_empty_result_writes_nothing(tmp_path):
    opts = DBOptions(
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
    )
    with DB(str(tmp_path / "db"), opts) as db:
        for i in range(20):
            db.put(f"k{i:03d}".encode(), pack64(i))
            db.delete(f"k{i:03d}".encode())
        db.flush()
        db.compact_range()  # everything tombstoned away
        assert list(db.new_iterator()) == []
        import os as _os
        files = [f for f in _os.listdir(str(tmp_path / "db"))
                 if f.endswith(".tsst")]
        assert files == []


def test_vectorized_source_roundtrip(tmp_path):
    """Sink-written files decode array-to-array (read_sst_arrays) and a
    second compaction over them matches the CPU engine's state."""
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    opts = DBOptions(
        merge_operator=UInt64AddOperator(),
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30,
    )
    with DB(str(tmp_path / "db"), opts) as db:
        for i in range(300):
            db.merge(f"k{i:06d}".encode(), pack64(i))
        db.flush()
        db.compact_range()  # sink writes a uniform file
        import os as _os
        files = [f for f in _os.listdir(str(tmp_path / "db"))
                 if f.endswith(".tsst")]
        assert len(files) == 1
        r = SSTReader(str(tmp_path / "db" / files[0]))
        arrays = read_sst_arrays(r)
        assert arrays is not None  # vectorized source engaged
        assert arrays["key_len"].shape[0] == 300
        r.close()
        # second round: more data + compaction over the sink-written file
        # (vectorized source feeds the kernel directly)
        for i in range(300):
            db.merge(f"k{i:06d}".encode(), pack64(1))
        db.flush()
        db.compact_range()
        for i in range(0, 300, 37):
            assert db.get(f"k{i:06d}".encode()) == pack64(i + 1)
        assert len(list(db.new_iterator())) == 300


def test_vectorized_source_respects_global_seqno(tmp_path):
    """Ingested (global-seqno-stamped) sink-format files must surface the
    override through the vectorized source."""
    import numpy as np
    from rocksplicator_tpu.storage.sst import SSTReader
    from rocksplicator_tpu.tpu.format import read_sst_arrays, write_sst_from_arrays
    from rocksplicator_tpu.models.compaction_model import synth_counter_batch

    b = synth_counter_batch(64, seed=5, merge_frac=0.0, delete_frac=0.0,
                            key_bytes=16)
    order = np.lexsort(tuple(
        b["key_words_be"][:, w] for w in range(5, -1, -1)))
    arrays = {k: v[order] for k, v in b.items() if k != "valid"}
    path = str(tmp_path / "g.tsst")
    props = write_sst_from_arrays(arrays, 64, path)
    assert props is not None
    with DB(str(tmp_path / "db")) as db:
        db.put(b"zzz", b"v")
        db.ingest_external_file([path])
        # ingest stamped a global seqno; vectorized read must reflect it
        name = [f for f in __import__("os").listdir(str(tmp_path / "db"))
                if f.endswith(".tsst")]
        for f in name:
            r = SSTReader(str(tmp_path / "db" / f))
            if r.global_seqno is not None:
                out = read_sst_arrays(r)
                assert out is not None
                seqs = (out["seq_hi"].astype(np.uint64) << np.uint64(32)) | \
                    out["seq_lo"].astype(np.uint64)
                assert (seqs == r.global_seqno).all()
            r.close()


def test_device_block_encode_matches_host_sink():
    """encode_rows_tpu must be byte-identical to the host sink's
    encode_uniform_block, and device checksums must match the numpy
    reference (incl. the zero-padded short tail block)."""
    import jax.numpy as jnp

    from rocksplicator_tpu.ops.block_encode import (
        block_checksums_tpu, encode_rows_tpu, poly_checksum_np,
    )
    from rocksplicator_tpu.tpu.format import encode_uniform_block
    from rocksplicator_tpu.models.compaction_model import synth_counter_batch

    n, klen, vlen = 300, 16, 8
    b = synth_counter_batch(n, seed=11, merge_frac=0.0, delete_frac=0.0,
                            key_bytes=klen)
    arrays = {k: v for k, v in b.items()}
    rows = np.asarray(encode_rows_tpu(
        jnp.asarray(arrays["key_words_be"]), jnp.asarray(arrays["seq_hi"]),
        jnp.asarray(arrays["seq_lo"]), jnp.asarray(arrays["vtype"]),
        jnp.asarray(arrays["val_words"]), klen=klen, vlen=vlen,
    ))
    want = encode_uniform_block(arrays, 0, n, klen, vlen)
    assert rows.tobytes() == want
    # checksums: 128-entry blocks -> 2 full + 1 short tail
    block_entries = 128
    chks = np.asarray(block_checksums_tpu(
        jnp.asarray(rows), block_entries=block_entries))
    stride = rows.shape[1]
    for i, chk in enumerate(chks):
        blk = rows[i * block_entries:(i + 1) * block_entries].tobytes()
        assert int(chk) == poly_checksum_np(
            blk, length=block_entries * stride)


def test_device_encoded_file_detects_corruption(tmp_path):
    """merge_runs_to_files writes device-encoded blocks with device
    checksums; flipping one byte in a data block must raise Corruption
    on read, while intact files round-trip exactly."""
    from rocksplicator_tpu.storage.errors import Corruption
    from rocksplicator_tpu.storage.sst import COMPRESSION_NONE, SSTReader

    backend = TpuCompactionBackend()
    entries = [
        (f"key{i:06d}".encode(), i + 1, OpType.PUT, pack64(i))
        for i in range(500)
    ]
    paths = []
    out = backend.merge_runs_to_files(
        [entries], UInt64AddOperator(), True,
        path_factory=lambda: paths.append(
            str(tmp_path / f"o{len(paths)}.tsst")) or paths[-1],
        block_bytes=4096, compression=COMPRESSION_NONE, bits_per_key=10,
        target_file_bytes=1 << 30,
    )
    assert out and len(out) == 1
    path, props = out[0]
    assert "block_chk" in props and props["block_chk"]["values"]
    r = SSTReader(path)
    got = list(r.iterate())
    assert [(k, v) for k, _s, _vt, v in got] == [
        (k, v) for k, _s, _vt, v in entries
    ]
    r.close()
    # corrupt one byte inside the first data block
    with open(path, "r+b") as f:
        f.seek(100)
        orig = f.read(1)
        f.seek(100)
        f.write(bytes([orig[0] ^ 0xFF]))
    r2 = SSTReader(path)
    with pytest.raises(Corruption):
        list(r2.iterate())
    r2.close()


def test_read_sst_arrays_rejects_foreign_uniform_props(tmp_path):
    """Crafted/foreign 'uniform' props must return None, not raise."""
    from rocksplicator_tpu.storage.sst import SSTReader, SSTWriter
    from rocksplicator_tpu.tpu.format import read_sst_arrays

    path = str(tmp_path / "f.tsst")
    w = SSTWriter(path)
    w.add(b"k" * 30, 1, OpType.PUT, b"v")  # 30-byte key (beyond lanes)
    w.finish(extra_props={"uniform": [30, 1]})
    r = SSTReader(path)
    assert read_sst_arrays(r) is None  # falls back, no ValueError
    r.close()


def test_tpu_backend_default_fallback_is_vectorized():
    """The production CPU fallback is the vectorized numpy path — the
    degraded bench's value_source semantics rely on this default."""
    assert isinstance(TpuCompactionBackend()._fallback,
                      NumpyCompactionBackend)
