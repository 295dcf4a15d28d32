"""Parity tests: TPU kernels vs the CPU storage-engine semantics.

The 'XLA assumption tests' SURVEY §4 calls for: the TPU merge-resolve
kernel must produce exactly what compaction.py's resolve_stream produces,
and the TPU bloom must be byte-identical to storage/bloom.py.
"""

import random
import struct

import numpy as np
import pytest

from rocksplicator_tpu.ops import (
    KVBatch,
    MergeKind,
    bloom_build_tpu,
    merge_resolve_kernel,
    pack_entries,
    unpack_entries,
)
from rocksplicator_tpu.ops.kv_format import UnsupportedBatch
from rocksplicator_tpu.storage.bloom import BloomFilter, num_words_for
from rocksplicator_tpu.storage.compaction import CpuCompactionBackend
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.records import OpType

import jax
import jax.numpy as jnp

pack64 = struct.Struct("<q").pack


def run_kernel(entries, merge_kind=MergeKind.UINT64_ADD, drop_tombstones=True,
               capacity=None):
    batch = pack_entries(entries, capacity=capacity)
    out = merge_resolve_kernel(
        jnp.asarray(batch.key_words_be),
        jnp.asarray(batch.key_len), jnp.asarray(batch.seq_hi),
        jnp.asarray(batch.seq_lo), jnp.asarray(batch.vtype),
        jnp.asarray(batch.val_words), jnp.asarray(batch.val_len),
        jnp.asarray(batch.valid),
        merge_kind=merge_kind, drop_tombstones=drop_tombstones,
    )
    return unpack_entries(
        np.asarray(out["key_words_be"]), np.asarray(out["key_len"]),
        np.asarray(out["seq_hi"]), np.asarray(out["seq_lo"]),
        np.asarray(out["vtype"]), np.asarray(out["val_words"]),
        np.asarray(out["val_len"]), int(out["count"]),
    )


def keys_only(result):
    return [(k, int(vt), v) for k, s, vt, v in result]


def test_kernel_put_delete_basic():
    entries = [
        (b"a", 1, OpType.PUT, pack64(10)),
        (b"a", 5, OpType.PUT, pack64(20)),
        (b"b", 2, OpType.PUT, pack64(7)),
        (b"c", 3, OpType.PUT, pack64(1)),
        (b"c", 4, OpType.DELETE, b""),
    ]
    got = run_kernel(entries)
    assert keys_only(got) == [
        (b"a", OpType.PUT, pack64(20)),
        (b"b", OpType.PUT, pack64(7)),
    ]
    # keep tombstones mid-level
    got2 = run_kernel(entries, drop_tombstones=False)
    assert keys_only(got2) == [
        (b"a", OpType.PUT, pack64(20)),
        (b"b", OpType.PUT, pack64(7)),
        (b"c", OpType.DELETE, b""),
    ]


def test_kernel_merge_folding():
    entries = [
        (b"ctr", 1, OpType.PUT, pack64(100)),
        (b"ctr", 2, OpType.MERGE, pack64(5)),
        (b"ctr", 3, OpType.MERGE, pack64(7)),
        (b"del", 1, OpType.PUT, pack64(1)),
        (b"del", 2, OpType.DELETE, b""),
        (b"del", 3, OpType.MERGE, pack64(9)),
        (b"pure", 4, OpType.MERGE, pack64(3)),
        (b"pure", 5, OpType.MERGE, pack64(4)),
    ]
    got = run_kernel(entries)
    assert keys_only(got) == [
        (b"ctr", OpType.PUT, pack64(112)),
        (b"del", OpType.PUT, pack64(9)),
        (b"pure", OpType.PUT, pack64(7)),   # bottom: fold to PUT
    ]
    got_mid = run_kernel(entries, drop_tombstones=False)
    assert keys_only(got_mid) == [
        (b"ctr", OpType.PUT, pack64(112)),
        (b"del", OpType.PUT, pack64(9)),
        (b"pure", OpType.MERGE, pack64(7)),  # mid-level: partial merge
    ]


def test_kernel_negative_and_large_values():
    entries = [
        (b"n", 1, OpType.PUT, pack64(-5)),
        (b"n", 2, OpType.MERGE, pack64(-10)),
        (b"big", 1, OpType.MERGE, pack64(2**40)),
        (b"big", 2, OpType.MERGE, pack64(2**40 + 3)),
    ]
    got = dict((k, v) for k, s, vt, v in run_kernel(entries))
    assert got[b"n"] == pack64(-15)
    assert got[b"big"] == pack64(2**41 + 3)


def test_kernel_matches_cpu_reference_randomized():
    rng = random.Random(42)
    keys = [f"key{i:02d}".encode() for i in range(20)]
    entries = []
    seq = 1
    for _ in range(300):
        k = rng.choice(keys)
        r = rng.random()
        if r < 0.5:
            entries.append((k, seq, OpType.MERGE, pack64(rng.randrange(-50, 50))))
        elif r < 0.8:
            entries.append((k, seq, OpType.PUT, pack64(rng.randrange(1000))))
        else:
            entries.append((k, seq, OpType.DELETE, b""))
        seq += 1
    rng.shuffle(entries)  # kernel sorts internally
    for drop in (True, False):
        got = keys_only(run_kernel(entries, drop_tombstones=drop))
        want = keys_only(
            CpuCompactionBackend().merge_runs(
                [sorted(entries, key=lambda e: (e[0], -e[1]))],
                UInt64AddOperator(), drop,
            )
        )
        assert got == want, f"drop_tombstones={drop}"


def test_kernel_with_padding_capacity():
    entries = [(b"a", 1, OpType.PUT, pack64(1)), (b"b", 2, OpType.PUT, pack64(2))]
    got = run_kernel(entries, capacity=64)  # 62 invalid rows of padding
    assert keys_only(got) == [
        (b"a", OpType.PUT, pack64(1)),
        (b"b", OpType.PUT, pack64(2)),
    ]


def test_pack_rejects_oversize():
    with pytest.raises(UnsupportedBatch):
        pack_entries([(b"x" * 25, 1, OpType.PUT, b"")])
    with pytest.raises(UnsupportedBatch):
        pack_entries([(b"x", 1, OpType.PUT, b"v" * 9)])


def test_bloom_tpu_byte_identical_to_cpu():
    keys = [f"key-{i}".encode() for i in range(2000)]
    nw = num_words_for(len(keys), 10)
    cpu = BloomFilter(nw)
    for k in keys:
        cpu.add(k)
    batch = pack_entries([(k, 1, OpType.PUT, b"") for k in keys])
    tpu_words = np.asarray(bloom_build_tpu(
        jnp.asarray(batch.key_words_le), jnp.asarray(batch.key_len),
        jnp.asarray(batch.valid), num_words=nw,
    ))
    assert np.array_equal(tpu_words, cpu.words)


def test_bloom_tpu_invalid_rows_excluded():
    batch = pack_entries([(b"real", 1, OpType.PUT, b"")], capacity=8)
    nw = 4
    tpu_words = np.asarray(bloom_build_tpu(
        jnp.asarray(batch.key_words_le), jnp.asarray(batch.key_len),
        jnp.asarray(batch.valid), num_words=nw,
    ))
    cpu = BloomFilter(nw)
    cpu.add(b"real")
    assert np.array_equal(tpu_words, cpu.words)


# ---------------------------------------------------------------------------
# regression tests from code review
# ---------------------------------------------------------------------------


def test_kernel_short_merge_operand_parses_as_zero():
    """UInt64AddOperator parity: non-8-byte values count as 0."""
    entries = [
        (b"k", 1, OpType.PUT, pack64(10)),
        (b"k", 2, OpType.MERGE, b"\x01\x00\x00\x00"),  # 4 bytes -> 0
        (b"k", 3, OpType.MERGE, pack64(5)),
    ]
    got = dict((k, v) for k, s, vt, v in run_kernel(entries))
    want = UInt64AddOperator().merge(
        b"k", pack64(10), [b"\x01\x00\x00\x00", pack64(5)]
    )
    assert got[b"k"] == want == pack64(15)


def test_backend_none_with_merge_records_falls_back():
    from rocksplicator_tpu.tpu.backend import TpuCompactionBackend

    entries = sorted([
        (b"k", 2, OpType.MERGE, b"op2"),
        (b"k", 1, OpType.PUT, b"base"),
    ], key=lambda e: (e[0], -e[1]))
    got = list(TpuCompactionBackend().merge_runs([entries], None, False))
    want = list(CpuCompactionBackend().merge_runs([entries], None, False))
    assert got == want  # unresolved chain preserved, base not lost


def test_kernel_flags_oversize_merge_group():
    import jax.numpy as jnp

    n = 1 << 17
    entries_kw = np.zeros((n, 6), dtype=np.uint32)  # all same key
    out = merge_resolve_kernel(
        jnp.asarray(entries_kw),
        jnp.full(n, 8, jnp.uint32),
        jnp.zeros(n, jnp.uint32), jnp.asarray(np.arange(n, dtype=np.uint32)),
        jnp.full(n, 3, jnp.uint32),  # all MERGE
        jnp.ones((n, 2), jnp.uint32), jnp.full(n, 8, jnp.uint32),
        jnp.ones(n, bool),
        merge_kind=MergeKind.UINT64_ADD, drop_tombstones=True,
    )
    assert bool(out["needs_cpu_fallback"])


def test_service_cpu_recompute_on_oversize_group():
    from rocksplicator_tpu.tpu.compaction_service import TpuCompactionService

    n = 1 << 17
    entries = [(b"hot", i + 1, OpType.MERGE, pack64(1)) for i in range(n)]
    batch = pack_entries(sorted(entries, key=lambda e: (e[0], -e[1])))
    service = TpuCompactionService()
    results = service.compact_shard_batch([batch])
    assert results[0]["count"] == 1
    k, s, vt, v = results[0]["entries"][0]
    assert k == b"hot" and v == pack64(n)  # exact despite 2^17 operands


def test_fast_flags_variants_match_baseline():
    """uniform_klen/seq32 fast paths must be result-identical."""
    import jax.numpy as jnp

    from rocksplicator_tpu.ops.kv_format import fast_flags

    entries = [
        (b"k0000001", 5, OpType.MERGE, pack64(3)),
        (b"k0000001", 2, OpType.PUT, pack64(10)),
        (b"k0000002", 4, OpType.DELETE, b""),
        (b"k0000003", 1, OpType.PUT, pack64(7)),
    ]
    batch = pack_entries(entries, capacity=16)
    uk, s32, kwords = fast_flags(batch.key_len, batch.seq_hi, batch.valid)
    assert uk is True   # all keys are 8 bytes
    assert s32 is True  # seqs < 2^32
    assert kwords == 2  # 8-byte keys live in the first 2 u32 lanes

    def run(uniform_klen, seq32, key_words=6):
        out = merge_resolve_kernel(
            jnp.asarray(batch.key_words_be),
            jnp.asarray(batch.key_len), jnp.asarray(batch.seq_hi),
            jnp.asarray(batch.seq_lo), jnp.asarray(batch.vtype),
            jnp.asarray(batch.val_words), jnp.asarray(batch.val_len),
            jnp.asarray(batch.valid),
            merge_kind=MergeKind.UINT64_ADD, drop_tombstones=True,
            uniform_klen=uniform_klen, seq32=seq32, key_words=key_words,
        )
        return unpack_entries(
            np.asarray(out["key_words_be"]), np.asarray(out["key_len"]),
            np.asarray(out["seq_hi"]), np.asarray(out["seq_lo"]),
            np.asarray(out["vtype"]), np.asarray(out["val_words"]),
            np.asarray(out["val_len"]), int(out["count"]),
        )

    base = run(False, False)
    assert run(True, True) == base
    assert run(True, False) == base
    assert run(False, True) == base
    assert run(True, True, key_words=kwords) == base
    assert run(False, False, key_words=kwords) == base
    assert [k for k, *_ in base] == [b"k0000001", b"k0000003"]


def test_fast_flags_negative_cases():
    from rocksplicator_tpu.ops.kv_format import fast_flags

    mixed = pack_entries([
        (b"ab", 1, OpType.PUT, b"v"),
        (b"ab\x00", 2, OpType.PUT, b"w"),  # same padded words, diff length!
    ])
    uk, s32, kw = fast_flags(mixed.key_len, mixed.seq_hi, mixed.valid)
    assert uk is False  # promising uniform here would merge distinct keys
    assert kw == 1      # 3-byte max key still needs one lane
    big_seq = pack_entries([(b"k", (1 << 40), OpType.PUT, b"v")])
    uk2, s32_2, _ = fast_flags(big_seq.key_len, big_seq.seq_hi, big_seq.valid)
    assert s32_2 is False
    assert uk2 is True


def test_synth_counter_batch_jax_matches_numpy_contract():
    """The device-side input generator must produce the same lane
    shapes/dtypes and distribution as the numpy generator (the bench
    compares throughput across the two — distribution-matched data)."""
    import jax

    from rocksplicator_tpu.models.compaction_model import (
        synth_counter_batch, synth_counter_batch_jax)

    n = 4096
    ref = synth_counter_batch(n, seed=7)
    got = {k: np.asarray(v)
           for k, v in jax.jit(
               lambda: synth_counter_batch_jax(n, seed=7))().items()}
    assert set(got) == set(ref)
    for k in ref:
        assert got[k].shape == ref[k].shape, k
        assert got[k].dtype == ref[k].dtype, k
    # LE lanes really are byteswaps of the BE lanes over the same bytes
    kb = np.ascontiguousarray(got["key_words_be"].astype(">u4")).view(np.uint8)
    assert (kb.reshape(n, 24).view("<u4") == got["key_words_le"]).all()
    # distribution: vtype mix within a few percent of the configured fracs
    frac_merge = (got["vtype"] == 3).mean()
    frac_del = (got["vtype"] == 2).mean()
    assert abs(frac_merge - 0.6) < 0.05 and abs(frac_del - 0.05) < 0.02
    # key ids live in the first 8 BE bytes within key_space
    assert (got["key_words_be"][:, 0] == 0).all()
    assert got["key_words_be"][:, 1].max() < n // 8
    assert (got["val_len"] == np.where(got["vtype"] == 2, 0, 8)).all()
    assert got["valid"].all()
