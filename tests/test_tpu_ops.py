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


# ---------------------------------------------------------------------
# sorted-runs merge network (ops/merge_network.py)
# ---------------------------------------------------------------------

def _pack_runs(runs, run_capacity):
    """Per-run entry lists -> stacked (R, L) lanes + valid matrix."""
    batches = [pack_entries(r, capacity=run_capacity) for r in runs]
    stack = lambda f: np.stack([getattr(b, f) for b in batches])  # noqa: E731
    return {
        "key_words_be": stack("key_words_be"),
        "key_len": stack("key_len"),
        "seq_hi": stack("seq_hi"),
        "seq_lo": stack("seq_lo"),
        "vtype": stack("vtype"),
        "val_words": stack("val_words"),
        "val_len": stack("val_len"),
        "valid": stack("valid"),
    }


def _run_runs_kernel(runs, run_capacity, merge_kind=MergeKind.UINT64_ADD,
                     drop_tombstones=True, **flags):
    from rocksplicator_tpu.ops.merge_network import (
        merge_resolve_runs_kernel, runs_are_sorted)

    lanes = _pack_runs(runs, run_capacity)
    assert runs_are_sorted(
        lanes["key_words_be"], lanes["key_len"], lanes["seq_hi"],
        lanes["seq_lo"], lanes["valid"])
    out = merge_resolve_runs_kernel(
        jnp.asarray(lanes["key_words_be"]), jnp.asarray(lanes["key_len"]),
        jnp.asarray(lanes["seq_hi"]), jnp.asarray(lanes["seq_lo"]),
        jnp.asarray(lanes["vtype"]), jnp.asarray(lanes["val_words"]),
        jnp.asarray(lanes["val_len"]), jnp.asarray(lanes["valid"]),
        merge_kind=merge_kind, drop_tombstones=drop_tombstones, **flags)
    return unpack_entries(
        np.asarray(out["key_words_be"]), np.asarray(out["key_len"]),
        np.asarray(out["seq_hi"]), np.asarray(out["seq_lo"]),
        np.asarray(out["vtype"]), np.asarray(out["val_words"]),
        np.asarray(out["val_len"]), int(out["count"]),
    )


def _split_sorted_runs(entries, n_runs, rng):
    """Assign entries to runs at random; each run sorted (key asc, seq
    desc) — the precondition real SST/memtable runs satisfy."""
    runs = [[] for _ in range(n_runs)]
    for e in entries:
        runs[rng.randrange(n_runs)].append(e)
    return [sorted(r, key=lambda e: (e[0], -e[1])) for r in runs]


@pytest.mark.parametrize("merge_kind,drop", [
    (MergeKind.UINT64_ADD, True),
    (MergeKind.UINT64_ADD, False),
    (MergeKind.NONE, True),
    (MergeKind.NONE, False),
])
def test_merge_network_matches_full_sort_kernel(merge_kind, drop):
    rng = random.Random(42)
    entries = []
    seq = 1
    for _ in range(500):
        k = f"key{rng.randrange(60):04d}".encode()
        r = rng.random()
        if merge_kind is MergeKind.NONE:
            vt = OpType.PUT if r < 0.8 else OpType.DELETE
        else:
            vt = (OpType.MERGE if r < 0.5
                  else OpType.PUT if r < 0.85 else OpType.DELETE)
        v = b"" if vt == OpType.DELETE else pack64(rng.randrange(1000))
        entries.append((k, seq, vt, v))
        seq += 1
    want = run_kernel(entries, merge_kind=merge_kind, drop_tombstones=drop,
                      capacity=1024)
    for n_runs in (1, 2, 4, 8):
        runs = _split_sorted_runs(entries, n_runs, random.Random(n_runs))
        cap = 1
        while cap < max(len(r) for r in runs):
            cap *= 2
        got = _run_runs_kernel(runs, cap, merge_kind=merge_kind,
                               drop_tombstones=drop)
        assert got == want, f"n_runs={n_runs}"


def test_merge_network_fast_flags_parity():
    rng = random.Random(7)
    entries = []
    for i in range(300):
        k = f"k{rng.randrange(40):06d}".encode()  # uniform 7-byte keys
        entries.append((k, i + 1, OpType.MERGE, pack64(i)))
    want = run_kernel(entries, capacity=512)
    runs = _split_sorted_runs(entries, 4, rng)
    got = _run_runs_kernel(runs, 128, uniform_klen=True, seq32=True,
                           key_words=2)
    assert got == want


def test_merge_network_uneven_and_empty_runs():
    entries = [
        (b"a", 3, OpType.PUT, pack64(1)),
        (b"b", 2, OpType.DELETE, b""),
        (b"c", 1, OpType.PUT, pack64(2)),
    ]
    want = run_kernel(entries, capacity=8)
    runs = [sorted(entries, key=lambda e: (e[0], -e[1])), []]
    got = _run_runs_kernel(runs, 4)
    assert got == want


def test_runs_are_sorted_detects_violations():
    from rocksplicator_tpu.ops.merge_network import runs_are_sorted

    ok = _pack_runs([[
        (b"a", 2, OpType.PUT, b"x"),
        (b"a", 1, OpType.PUT, b"y"),  # same key: seq desc
        (b"b", 9, OpType.PUT, b"z"),
    ]], 4)
    assert runs_are_sorted(ok["key_words_be"], ok["key_len"], ok["seq_hi"],
                           ok["seq_lo"], ok["valid"])
    bad_key = _pack_runs([[
        (b"b", 1, OpType.PUT, b"x"),
        (b"a", 2, OpType.PUT, b"y"),
    ]], 2)
    assert not runs_are_sorted(
        bad_key["key_words_be"], bad_key["key_len"], bad_key["seq_hi"],
        bad_key["seq_lo"], bad_key["valid"])
    bad_seq = _pack_runs([[
        (b"a", 1, OpType.PUT, b"x"),
        (b"a", 2, OpType.PUT, b"y"),  # seq ascending: newest must be first
    ]], 2)
    assert not runs_are_sorted(
        bad_seq["key_words_be"], bad_seq["key_len"], bad_seq["seq_hi"],
        bad_seq["seq_lo"], bad_seq["valid"])
    # valid rows must form a prefix (a hole breaks run order)
    hole = _pack_runs([[(b"a", 1, OpType.PUT, b"x")]], 2)
    hole["valid"][0] = np.array([False, True])
    assert not runs_are_sorted(
        hole["key_words_be"], hole["key_len"], hole["seq_hi"],
        hole["seq_lo"], hole["valid"])


def test_merge_network_rejects_non_pow2_shapes():
    from rocksplicator_tpu.ops.merge_network import merge_sorted_lanes

    with pytest.raises(ValueError):
        merge_sorted_lanes([jnp.zeros((2, 6), jnp.uint32)], 1)
    with pytest.raises(ValueError):
        merge_sorted_lanes([jnp.zeros((3, 4), jnp.uint32)], 1)


def test_pallas_bitonic_sort_parity_with_lax():
    """The VMEM-resident bitonic sort must order lanes EXACTLY like
    lax.sort on the same (keys, payload) operands (interpret mode on
    CPU; on-chip it is the same network)."""
    import numpy as _np

    from rocksplicator_tpu.ops.pallas_sort import bitonic_sort_lanes

    rng = _np.random.default_rng(7)
    n = 512  # interpret mode executes the full 45-stage network in pure
    # python — keep the size small; the network is size-generic
    for num_keys, n_payload in ((1, 0), (6, 4)):
        ops = [rng.integers(0, 1 << 32, n, dtype=_np.uint32)
               for _ in range(num_keys + n_payload)]
        # duplicate keys to exercise payload stability-independence:
        # compare VALUE-wise (payload under equal keys may permute in
        # either unstable sort, so pin payload = f(keys) for determinism)
        for i in range(num_keys):  # narrow ALL key lanes: real ties
            ops[i] = (ops[i] % 7).astype(_np.uint32)
        for i in range(num_keys, num_keys + n_payload):
            ops[i] = sum(ops[:num_keys]).astype(_np.uint32)
        want = jax.lax.sort(
            tuple(jnp.asarray(o) for o in ops), num_keys=num_keys,
            is_stable=False)
        got = bitonic_sort_lanes(
            tuple(jnp.asarray(o) for o in ops), num_keys=num_keys,
            interpret=True)
        for w, g in zip(want, got):
            _np.testing.assert_array_equal(_np.asarray(w), _np.asarray(g))


def test_pallas_sort_dispatch_is_loud():
    """backend="pallas" means the kernel: a shape it does not take
    RAISES (it used to warn and run lax.sort under the kernel's name);
    a power-of-two N takes the pallas kernel and must match lax exactly.
    An unknown backend name raises too."""
    import numpy as _np

    from rocksplicator_tpu.ops.pallas_sort import sort_lanes

    rng = _np.random.default_rng(3)

    def ops(n):
        return (jnp.asarray(rng.integers(0, 99, n, dtype=_np.uint32)),
                jnp.asarray(rng.integers(0, 99, n, dtype=_np.uint32)))

    with pytest.raises(ValueError, match="power-of-two"):
        sort_lanes(ops(1000), num_keys=1, backend="pallas", interpret=True)
    with pytest.raises(ValueError, match="unknown sort backend"):
        sort_lanes(ops(256), num_keys=1, backend="palas")
    o = ops(256)
    got = sort_lanes(o, num_keys=1, backend="pallas", interpret=True)
    want = jax.lax.sort(o, num_keys=1, is_stable=False)
    _np.testing.assert_array_equal(_np.asarray(want[0]),
                                   _np.asarray(got[0]))


def test_merge_resolve_kernel_pallas_sort_backend_parity():
    """Full merge-resolve with sort_backend="pallas" must produce results
    identical to the lax backend (the sort is a drop-in)."""
    import numpy as _np

    from rocksplicator_tpu.models.compaction_model import (
        CompactionModel, synth_counter_batch)

    b = synth_counter_batch(1024, key_space=128, seed=5, key_bytes=16)
    args = (b["key_words_be"], b["key_len"], b["seq_hi"], b["seq_lo"],
            b["vtype"], b["val_words"], b["val_len"], b["valid"])
    base = CompactionModel(capacity=1024, uniform_klen=True, seq32=True,
                           key_words=4)
    pall = CompactionModel(capacity=1024, uniform_klen=True, seq32=True,
                           key_words=4, sort_backend="pallas")
    out_l = base.forward(*args)
    out_p = pall.forward(*args)
    assert int(out_l["count"]) == int(out_p["count"])
    n = int(out_l["count"])
    for k in ("key_words_be", "seq_lo", "vtype", "val_words", "val_len"):
        _np.testing.assert_array_equal(
            _np.asarray(out_l[k])[:n], _np.asarray(out_p[k])[:n], err_msg=k)


def _assert_fused_matches_lax(args, **flags):
    """Full-array parity (including the zero-masked dead rows, the count,
    and the overflow flag) between the lax path and the fused VMEM
    kernel."""
    import numpy as _np

    out_l = merge_resolve_kernel(*args, **flags)
    out_f = merge_resolve_kernel(*args, sort_backend="pallas_fused",
                                 **flags)
    assert int(out_l["count"]) == int(out_f["count"])
    assert (bool(out_l["needs_cpu_fallback"])
            == bool(out_f["needs_cpu_fallback"]))
    for k in ("key_words_be", "key_words_le", "key_len", "seq_lo",
              "seq_hi", "vtype", "val_words", "val_len"):
        _np.testing.assert_array_equal(
            _np.asarray(out_l[k]), _np.asarray(out_f[k]), err_msg=k)


def test_fused_merge_resolve_parity_counter_batch():
    """The fully-fused pallas kernel (sort + resolve + compaction in one
    VMEM residency) must match the lax path element-exactly on the bench
    configuration (uniform klen, 32-bit seqs, uint64-add merges)."""
    from rocksplicator_tpu.models.compaction_model import synth_counter_batch

    b = synth_counter_batch(512, key_space=64, seed=5, key_bytes=16)
    args = (b["key_words_be"], b["key_len"], b["seq_hi"], b["seq_lo"],
            b["vtype"], b["val_words"], b["val_len"], b["valid"])
    _assert_fused_matches_lax(args, uniform_klen=True, seq32=True,
                              key_words=4)


def test_fused_merge_resolve_parity_general_lanes():
    """General configuration: ragged key lengths, seqs above 2^32, a
    duplicate-key merge stack ending in a DELETE, padding rows — across
    both merge kinds and both tombstone policies."""
    rng = np.random.default_rng(11)
    entries = []
    seq = 1 << 33
    for _ in range(180):
        klen = int(rng.integers(1, 20))
        key = bytes(rng.integers(97, 123, klen, dtype=np.uint8))
        r = rng.random()
        if r < 0.5:
            entries.append((key, seq, OpType.MERGE,
                            pack64(int(rng.integers(0, 99)))))
        elif r < 0.6:
            entries.append((key, seq, OpType.DELETE, b""))
        else:
            entries.append((key, seq, OpType.PUT,
                            pack64(int(rng.integers(0, 99)))))
        seq += 1
    for _ in range(40):
        entries.append((b"hotkey", seq, OpType.MERGE, pack64(1)))
        seq += 1
    entries.append((b"hotkey", seq, OpType.DELETE, b""))

    batch = pack_entries(entries, capacity=256)
    args = tuple(jnp.asarray(x) for x in (
        batch.key_words_be, batch.key_len, batch.seq_hi, batch.seq_lo,
        batch.vtype, batch.val_words, batch.val_len, batch.valid))
    # two configs cover both merge kinds AND both keep policies; the
    # remaining cross terms only recombine already-exercised branches
    # (interpret-mode runs re-trace the whole unrolled ladder, so each
    # config costs minutes on a small CPU)
    for mk, drop in ((MergeKind.UINT64_ADD, True), (MergeKind.NONE, False)):
        _assert_fused_matches_lax(args, merge_kind=mk,
                                  drop_tombstones=drop)


def test_fused_merge_resolve_non_pow2_raises():
    """Capacities the fused kernel can't take (non-power-of-two) RAISE:
    sort_backend="pallas_fused" never runs the lax path under the fused
    kernel's name (it used to, with a warning)."""
    entries = [
        (b"a", 1, OpType.PUT, pack64(10)),
        (b"a", 2, OpType.MERGE, pack64(5)),
        (b"b", 3, OpType.DELETE, b""),
    ]
    batch = pack_entries(entries, capacity=100)
    args = tuple(jnp.asarray(x) for x in (
        batch.key_words_be, batch.key_len, batch.seq_hi, batch.seq_lo,
        batch.vtype, batch.val_words, batch.val_len, batch.valid))
    with pytest.raises(ValueError, match="power-of-two"):
        merge_resolve_kernel(*args, sort_backend="pallas_fused")
    with pytest.raises(ValueError, match="unknown sort backend"):
        merge_resolve_kernel(*args, sort_backend="bogus")


def test_vmem_scan_ladder_primitives_match_1d():
    """The fused kernel's (R,128) Hillis-Steele shift/scan ladders must
    reproduce the 1-D primitives exactly (cheap pinpoint coverage — the
    interpret-mode kernel tests are minutes each; this isolates the scan
    math in milliseconds)."""
    import numpy as _np

    from rocksplicator_tpu.ops.compaction_kernel import (
        _seg_fill_backward, _seg_fill_forward)
    from rocksplicator_tpu.ops.pallas_resolve import (
        _cumsum_tuple, _fill_backward, _fill_forward, _shift_down,
        _shift_up)

    n, lanes = 1024, 128
    r = n // lanes
    rng = _np.random.default_rng(2)
    x_np = rng.integers(0, 1000, n, dtype=_np.int32)
    x1 = jnp.asarray(x_np)
    x2 = x1.reshape(r, lanes)
    iota2 = (jax.lax.broadcasted_iota(jnp.int32, (r, lanes), 0) * lanes
             + jax.lax.broadcasted_iota(jnp.int32, (r, lanes), 1))

    # linear-order shifts at lane, row, and multi-row distances
    for d in (1, 2, 64, 128, 256):
        want_dn = _np.concatenate([_np.zeros(d, _np.int32), x_np[:-d]])
        want_up = _np.concatenate([x_np[d:], _np.zeros(d, _np.int32)])
        _np.testing.assert_array_equal(
            _np.asarray(_shift_down(x2, d)).reshape(n), want_dn, err_msg=f"down d={d}")
        _np.testing.assert_array_equal(
            _np.asarray(_shift_up(x2, d)).reshape(n), want_up, err_msg=f"up d={d}")

    # batched inclusive prefix sums
    y_np = rng.integers(0, 7, n, dtype=_np.int32)
    got = _cumsum_tuple((x2, jnp.asarray(y_np).reshape(r, lanes)), n)
    _np.testing.assert_array_equal(
        _np.asarray(got[0]).reshape(n), _np.cumsum(x_np, dtype=_np.int32))
    _np.testing.assert_array_equal(
        _np.asarray(got[1]).reshape(n), _np.cumsum(y_np, dtype=_np.int32))

    # segmented fills vs the associative_scan originals (row 0 / last
    # row flagged per the contract)
    flag_np = rng.random(n) < 0.07
    flag_np[0] = True
    flag1 = jnp.asarray(flag_np)
    want_f = _seg_fill_forward(flag1, (x1, jnp.asarray(y_np)))
    got_f = _fill_forward(flag1.reshape(r, lanes),
                          (x2, jnp.asarray(y_np).reshape(r, lanes)),
                          iota2, n)
    for w, g in zip(want_f, got_f):
        _np.testing.assert_array_equal(
            _np.asarray(g).reshape(n), _np.asarray(w), err_msg="fwd")

    lflag_np = rng.random(n) < 0.07
    lflag_np[-1] = True
    lflag1 = jnp.asarray(lflag_np)
    want_b = _seg_fill_backward(lflag1, (x1, jnp.asarray(y_np)))
    got_b = _fill_backward(lflag1.reshape(r, lanes),
                           (x2, jnp.asarray(y_np).reshape(r, lanes)),
                           iota2, n)
    for w, g in zip(want_b, got_b):
        _np.testing.assert_array_equal(
            _np.asarray(g).reshape(n), _np.asarray(w), err_msg="bwd")
