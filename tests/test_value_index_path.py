"""The index path of the merge-resolve kernel: values wider than
``RIDE_MAX_VAL_WORDS`` do not ride the two sorts; one row-index lane
does, and the values are moved once by the resolved order. Held, byte
for byte, to the plain reference (``storage/compaction.resolve_stream``
over a heap merge), and to the riding path at widths both take."""

import functools
import heapq
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rocksplicator_tpu.ops import MergeKind, merge_resolve_kernel, pack_entries
from rocksplicator_tpu.ops import compaction_kernel as ck
from rocksplicator_tpu.ops.kv_format import fast_flags, unpack_entries
from rocksplicator_tpu.storage.compaction import resolve_stream
from rocksplicator_tpu.storage.records import OpType

LANES = ("key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
         "val_words", "val_len", "valid")


def make_runs(seed: int, vlen: int, n_runs: int = 4, keys: int = 60,
              per_run: int = 40):
    """``n_runs`` sorted runs of PUT/DELETE over one small key space, so
    keys repeat across runs; every entry has a seq of its own and a value
    of ``vlen`` random bytes (a DELETE has none)."""
    rng = random.Random(seed)
    seq = 0
    runs = []
    for _ in range(n_runs):
        run = []
        for i in sorted(rng.sample(range(keys), per_run)):
            seq += 1
            if rng.random() < 0.25:
                run.append((b"key%05d" % i, seq, OpType.DELETE, b""))
            else:
                run.append((b"key%05d" % i, seq, OpType.PUT, rng.randbytes(vlen)))
        runs.append(run)
    return runs


def reference(runs, drop: bool):
    merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
    return [(k, s, int(t), v) for k, s, t, v in resolve_stream(merged, None, drop)]


def pack(runs, vlen: int, capacity: int):
    entries = [e for run in runs for e in run]
    random.Random(len(entries)).shuffle(entries)  # the kernel sorts
    return pack_entries(entries, capacity=capacity,
                        val_bytes=max(4, -(-vlen // 4) * 4))


def as_entries(out):
    return [(k, s, int(t), v) for k, s, t, v in unpack_entries(
        *(np.asarray(out[f]) for f in (
            "key_words_be", "key_len", "seq_hi", "seq_lo", "vtype",
            "val_words", "val_len")), int(out["count"]))]


@pytest.mark.parametrize("drop", [True, False])
@pytest.mark.parametrize("vlen", [8, 100, 1000, 1024])
def test_index_path_matches_resolve_stream(vlen, drop):
    runs = make_runs(1000 * vlen + drop, vlen)
    batch = pack(runs, vlen, capacity=256)
    words = batch.val_words.shape[1]
    # 8-byte values ride; the index path is driven below its threshold
    # through its own two pieces, as the service drives it
    if ck.value_path(MergeKind.NONE, words) == "index":
        out = merge_resolve_kernel(
            *(jnp.asarray(getattr(batch, f)) for f in LANES),
            merge_kind=MergeKind.NONE, drop_tombstones=drop)
    else:
        out = index_path(batch, drop)
    assert as_entries(out) == reference(runs, drop)
    # rows past the count are zero, as the riding path leaves them
    count = int(out["count"])
    assert not np.asarray(out["val_words"])[count:].any()


def index_path(batch, drop, **flags):
    lanes = {f: jnp.asarray(getattr(batch, f)) for f in LANES}

    @jax.jit
    def run(lanes):
        vw = lanes.pop("val_words")
        out = ck.merge_resolve_rows(
            lanes["key_words_be"], lanes["key_len"], lanes["seq_hi"],
            lanes["seq_lo"], lanes["vtype"], lanes["val_len"],
            lanes["valid"], drop_tombstones=drop, **flags)
        out["val_words"] = ck.gather_value_rows(
            vw, out.pop("val_row"), out["count"])
        return out

    return run(lanes)


@pytest.mark.parametrize("fast", [False, True])
@pytest.mark.parametrize("vlen", [8, 100])
def test_index_and_riding_paths_give_identical_lanes(vlen, fast):
    """At a width both take, every output lane is the same array, padding
    included — with and without the fast-path flags."""
    runs = make_runs(77 + vlen, vlen)
    batch = pack(runs, vlen, capacity=256)
    flags = dict(uniform_klen=False, seq32=False, key_words=ck.KEY_WORDS)
    if fast:
        u, s32, kw = fast_flags(batch.key_len, batch.seq_hi, batch.valid)
        flags = dict(uniform_klen=u, seq32=s32, key_words=kw)
    ride = jax.jit(functools.partial(
        ck._sort_resolve, index=False, merge_kind=MergeKind.NONE,
        drop_tombstones=True, **flags))
    a = ride(*(jnp.asarray(getattr(batch, f)) for f in LANES))
    b = index_path(batch, True, **flags)
    assert set(a) == set(b)
    for name in a:
        np.testing.assert_array_equal(np.asarray(a[name]),
                                      np.asarray(b[name]), err_msg=name)


def test_value_path_is_chosen_from_the_static_width_alone():
    assert ck.value_path(MergeKind.UINT64_ADD, 2) == "ride"
    assert ck.value_path(MergeKind.UINT64_ADD, 256) == "ride"
    assert ck.value_path(MergeKind.NONE, ck.RIDE_MAX_VAL_WORDS) == "ride"
    assert ck.value_path(MergeKind.NONE, ck.RIDE_MAX_VAL_WORDS + 1) == "index"
    assert ck.value_path(MergeKind.NONE, 256) == "index"
