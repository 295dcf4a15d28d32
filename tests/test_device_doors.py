"""The two device doors — the engine seam's sink
(``TpuCompactionBackend.merge_runs_to_files``) and the cross-shard
post-load compaction (``compact_dbs_batched``) — share ONE rule
(``tpu.backend.device_decline_reason``), the host array path's run
reader and its PLANAR writer. What only the doors do is checked here:
what they decline and why, key-range subcompactions as one device
batch, the streaming merge's device chunk resolver, and the sweep of
output files when a write fails midway. XLA-CPU under conftest.py's
explicit JAX_PLATFORMS=cpu; the plain reference is ``resolve_stream``
over a heap merge.
"""

import heapq
import os
import random
import struct

import pytest

import rocksplicator_tpu.storage.native_compaction as nc
import rocksplicator_tpu.storage.stream_merge as sm
from rocksplicator_tpu.storage import DB, DBOptions
from rocksplicator_tpu.storage.compaction import (host_fallback_counts,
                                                  resolve_stream)
from rocksplicator_tpu.storage.merge import MergeOperator, UInt64AddOperator
from rocksplicator_tpu.storage.records import WriteBatch
from rocksplicator_tpu.storage.sst import SSTReader
from rocksplicator_tpu.tpu import backend as tb
from rocksplicator_tpu.tpu import compaction_service as cs
from rocksplicator_tpu.tpu import format as tf
from rocksplicator_tpu.utils.stats import Stats

P, D, M = 1, 2, 3
pack64 = struct.Struct("<q").pack


def key(i: int) -> bytes:
    return b"door-key%08d" % i


def reference(runs, merge_op, drop):
    """(key, vtype, value) of every entry the plain resolve keeps."""
    merged = heapq.merge(*runs, key=lambda e: (e[0], -e[1]))
    return [(k, int(t), bytes(v))
            for k, _s, t, v in resolve_stream(merged, merge_op, drop)]


def file_entries(paths):
    """(key, vtype, value) of every entry in these SSTs, in key order."""
    out = []
    for p in paths:
        r = SSTReader(p)
        out.extend((k, int(t), bytes(v)) for k, _s, t, v in r.iterate())
        r.close()
    return sorted(out)


def db_runs(db):
    return [list(db._readers[n].iterate())
            for level in db._levels for n in level]


def db_entries(db):
    return file_entries(os.path.join(db.path, n)
                        for level in db._levels for n in level)


# ---------------------------------------------------------------------------
# one rule, two doors
# ---------------------------------------------------------------------------


class ConcatOperator(MergeOperator):
    """A custom operator: arbitrary Python, the host path's to run."""

    def merge(self, key, existing, operands):
        return (existing or b"") + b"".join(operands)

    def partial_merge(self, key, operands):
        return None


def _put(i, value, k=None):
    return ("put", k or key(i), value)


# reason -> (merge operator, first flushed run, second flushed run)
DECLINES = {
    "custom_operator": (
        ConcatOperator(),
        [_put(i, b"a" * 8) for i in range(20)],
        [("merge", key(i), b"b" * 8) for i in range(10, 30)]),
    "merge_without_operator": (
        None,
        [_put(i, pack64(i)) for i in range(20)],
        [("merge", key(i), pack64(1)) for i in range(10, 30)]),
    "uint64add_width": (
        UInt64AddOperator(),
        [_put(i, b"\x01\x00\x00\x00") for i in range(20)],
        [_put(i, b"\x02\x00\x00\x00") for i in range(10, 30)]),
    # ("key_width": keys that only DIFFER in length are taken, and one of
    # over 24 bytes is declined by the lane read before the rule sees
    # lanes: both doors' cases are in tests/test_mixed_key_widths.py)
    "value_width_mixed": (
        None,
        [_put(i, b"v" * 8) for i in range(20)],
        [_put(i, b"w" * 12) for i in range(10, 30)]),
    "value_width": (
        None,
        [_put(i, bytes([65 + i]) * (tb.DEVICE_VALUE_BYTES_MAX + 4))
         for i in range(20)],
        [_put(i, bytes([97 + i]) * (tb.DEVICE_VALUE_BYTES_MAX + 4))
         for i in range(10, 30)]),
}


@pytest.mark.parametrize("door", ["engine_seam", "batched"])
@pytest.mark.parametrize("reason", sorted(DECLINES))
def test_device_decline_rule(reason, door, tmp_path, monkeypatch):
    """Each door asks the one rule, declines for the rule's reason
    before any program is built, counts a decline for width under its
    own name, and the host path leaves what ``resolve_stream`` gives."""
    merge_op, first, second = DECLINES[reason]
    # tools/chaos_soak.py sets the streaming merge to "always" when it is
    # imported, for the rest of an xdist worker's life: after a test file
    # that imports it, the engine door would stream these 40 rows before
    # it asks the rule about their lanes
    monkeypatch.setattr(sm, "STREAM_MODE_OVERRIDE", None)
    options = DBOptions(merge_operator=merge_op)
    if door == "engine_seam":
        options.compaction_backend = tb.TpuCompactionBackend()
    db = DB(str(tmp_path / "db"), options)
    for run in (first, second):
        for op, k, v in run:
            wb = WriteBatch()
            wb.put(k, v) if op == "put" else wb.merge(k, v)
            db.write(wb)
        db.flush()
    want = reference(db_runs(db), merge_op, True)
    assert len(want) >= 30

    module = tb if door == "engine_seam" else cs
    said = []
    rule = module.device_decline_reason

    def spy(lanes, op):
        said.append(rule(lanes, op))
        return said[-1]

    monkeypatch.setattr(module, "device_decline_reason", spy)
    monkeypatch.setattr(
        cs.TpuCompactionService, "_pipeline",
        lambda self, *a, **k: pytest.fail("a program was built"))
    launched = []
    monkeypatch.setattr(
        "rocksplicator_tpu.tpu.chunked.run_kernel_arrays",
        lambda *a, **k: launched.append(a) or pytest.fail("a launch"))
    was = host_fallback_counts()
    if door == "engine_seam":
        made = []
        sink = db._backend.merge_runs_to_files

        def direct(*a, **k):
            made.append(sink(*a, **k))
            return made[-1]

        monkeypatch.setattr(db._backend, "merge_runs_to_files", direct)
        db.compact_range()
        assert made == [None]  # the door declined; the tuple path ran
    else:
        handled, remaining = cs.compact_dbs_batched([("db", db)])
        assert handled == [] and [n for n, _ in remaining] == ["db"]
        db.compact_range()  # what the caller does with ``remaining``
    assert said[-1] == reason and not launched
    now = host_fallback_counts()
    assert now.get("value_width", 0) - was.get("value_width", 0) == int(
        reason == "value_width")
    assert db_entries(db) == sorted(want)
    for k, t, v in want:
        if t == P:
            assert db.get(k) == v
    db.close()


def test_the_rule_passes_what_the_device_path_takes():
    import numpy as np

    lanes = {"key_len": np.full(4, 16, np.uint32),
             "vtype": np.array([P, D, M, P], np.uint32),
             "val_len": np.array([8, 0, 8, 8], np.uint32)}
    assert tb.device_decline_reason(lanes, UInt64AddOperator()) is None
    assert tb.device_decline_reason(None, None) is None
    lanes["vtype"][2] = P
    lanes["val_len"][:] = [1024, 0, 1024, 1024]
    assert tb.device_decline_reason(lanes, None) is None
    # wider than the fold is defined on: the device's limit speaks first
    assert tb.device_decline_reason(
        lanes, UInt64AddOperator()) == "value_width"


# ---------------------------------------------------------------------------
# key-range subcompactions: one padded device batch of slices
# ---------------------------------------------------------------------------


def _overlapping_runs(rng, with_merges: bool):
    """Three (key asc, seq desc) runs over one key space: overwrites,
    tombstones shadowing other runs' PUTs and, with an operator, MERGE
    operand chains above and below a base."""
    runs, seq = [], 1
    for r in range(3):
        run = {}
        for i in rng.sample(range(600), 380):
            roll = rng.random()
            if roll < 0.12:
                run[key(i)] = (key(i), seq, D, b"")
            elif with_merges and roll < 0.55:
                run[key(i)] = (key(i), seq, M, pack64(rng.randrange(100)))
            else:
                run[key(i)] = (key(i), seq, P,
                               pack64(rng.randrange(-50, 1 << 40)))
            seq += 1
        runs.append(sorted(run.values(), key=lambda e: (e[0], -e[1])))
    return runs


@pytest.mark.parametrize("drop", [True, False],
                         ids=["drop_tombstones", "keep_tombstones"])
@pytest.mark.parametrize("merge_op", [UInt64AddOperator(), None],
                         ids=["uint64add", "no_operator"])
def test_device_subcompactions_match_single_shot(merge_op, drop, tmp_path,
                                                 monkeypatch):
    """``max_subcompactions=4`` resolves the key-range slices as ONE
    vmapped launch (``resolve_slices_batched``) and writes what the
    single-shot kernel writes: the plain reference's entries."""
    monkeypatch.setattr(nc, "MIN_SLICE_ENTRIES", 128)
    runs = _overlapping_runs(random.Random(31), merge_op is not None)
    backend = tb.TpuCompactionBackend()
    batches = []
    real = cs.resolve_slices_batched

    def spy(slices, *a, **k):
        batches.append(len(slices))
        return real(slices, *a, **k)

    monkeypatch.setattr(cs, "resolve_slices_batched", spy)
    stats = Stats.get()

    def compact(tag, max_subcompactions):
        made = []

        def path_factory():
            made.append(str(tmp_path / f"{tag}-{len(made)}.tsst"))
            return made[-1]

        outs = backend.merge_runs_to_files(
            [list(r) for r in runs], merge_op, drop, path_factory,
            block_bytes=4096, compression=0, bits_per_key=10,
            target_file_bytes=1 << 20,
            max_subcompactions=max_subcompactions)
        assert outs is not None and [p for p, _ in outs] == made
        return file_entries(made)

    before = stats.get_counter("compaction.subcompactions")
    single = compact("single", 1)
    assert batches == [] and stats.get_counter(
        "compaction.subcompactions") == before
    sliced = compact("sliced", 4)
    assert len(batches) == 1 and batches[0] >= 3
    assert stats.get_counter(
        "compaction.subcompactions") == before + batches[0]
    assert sliced == single == sorted(reference(runs, merge_op, drop))
    assert len(single) > 300


def test_flagged_shard_of_pre_read_lanes_recomputes_on_the_host(tmp_path):
    """A shard the kernel flags (2^16 operands of one key) is recomputed
    on the host from the batch it was launched from; the served door's
    batches are pre-read lanes (``_LaneBatch``), which that recompute
    has to take as it takes a ``KVBatch``."""
    from rocksplicator_tpu.ops.compaction_kernel import MergeKind
    from rocksplicator_tpu.ops.kv_format import pack_entries
    from rocksplicator_tpu.storage.bloom import num_words_for

    runs = _overlapping_runs(random.Random(3), True)
    entries = [e for run in runs for e in run]
    lanes = nc.NativeCompactionBackend._arrays_from_entries(
        entries, pack_entries)
    res = cs.TpuCompactionService()._cpu_recompute(
        cs._LaneBatch(lanes), MergeKind.UINT64_ADD, True,
        num_words_for(len(entries), 10), return_arrays=True)
    out = str(tmp_path / "recomputed.tsst")
    assert tf.write_sst_from_arrays(
        res["arrays"], res["count"], out, block_entries=64, compression=0,
        bits_per_key=10, planar=True) is not None
    assert file_entries([out]) == sorted(
        reference(runs, UInt64AddOperator(), True))


# ---------------------------------------------------------------------------
# the streaming merge's device chunk resolver
# ---------------------------------------------------------------------------


@pytest.fixture
def stream_knobs():
    yield
    sm.STREAM_MODE_OVERRIDE = None
    sm.CHUNK_ENTRIES_OVERRIDE = None
    sm.CompactionMemoryBudget.reset_for_test()


@pytest.mark.parametrize("merge_op", [UInt64AddOperator(), None],
                         ids=["uint64add", "no_operator"])
def test_stream_merge_device_resolver_matches_in_ram(merge_op, tmp_path,
                                                     stream_knobs):
    """The same runs through ``maybe_stream_merge`` with
    ``TpuChunkResolver`` under a budget a fraction of their lane image,
    and through the door in RAM: the same entries, the reference's."""
    from rocksplicator_tpu.ops.kv_format import pack_entries

    runs = _overlapping_runs(random.Random(17), merge_op is not None)
    paths = []
    for r, run in enumerate(runs):
        # PLANAR files, as the engine's flush writes them: tombstones
        # beside fixed-width values stream from no other layout
        arr = nc.NativeCompactionBackend._arrays_from_entries(
            run, pack_entries)
        paths.append(str(tmp_path / f"run{r}.tsst"))
        assert tf.write_sst_from_arrays(
            arr, len(run), paths[-1], block_entries=64, compression=0,
            bits_per_key=10, planar=True) is not None

    def outputs(tag):
        made = []

        def path_factory():
            made.append(str(tmp_path / f"{tag}-{len(made)}.tsst"))
            return made[-1]

        return made, path_factory

    sm.CHUNK_ENTRIES_OVERRIDE = 256
    chunks = Stats.get().get_counter("compaction.stream_chunks")
    made, path_factory = outputs("streamed")
    readers = [SSTReader(p) for p in paths]
    streamed = sm.maybe_stream_merge(
        readers, merge_op, True, path_factory, 4096, 0, 10, 8192,
        memory_budget_bytes=64 * 1024, resolver=cs.TpuChunkResolver())
    assert streamed is not None and [p for p, _ in streamed] == made
    assert Stats.get().get_counter("compaction.stream_chunks") >= chunks + 3
    got = file_entries(made)

    sm.STREAM_MODE_OVERRIDE = "never"
    made, path_factory = outputs("in-ram")
    in_ram = tb.TpuCompactionBackend().merge_runs_to_files(
        readers, merge_op, True, path_factory, 4096, 0, 10, 8192)
    assert in_ram is not None
    for r in readers:
        r.close()
    assert got == file_entries(made) == sorted(
        reference(runs, merge_op, True))
    assert len(got) > 300


# ---------------------------------------------------------------------------
# a write that fails midway leaves no file behind
# ---------------------------------------------------------------------------


def _fail_second_write(monkeypatch):
    """``write_sst_from_arrays`` writes its first file and raises on the
    second. Returns the list of paths it was asked to write."""
    asked = []
    real = tf.write_sst_from_arrays

    def write(arrays, count, path, **kw):
        asked.append(path)
        if len(asked) == 2:
            raise OSError("disk full (simulated)")
        return real(arrays, count, path, **kw)

    monkeypatch.setattr(tf, "write_sst_from_arrays", write)
    return asked


@pytest.mark.parametrize("door", ["engine_seam", "batched"])
def test_device_sink_removes_its_files_when_a_write_fails(door, tmp_path,
                                                          monkeypatch):
    """Nothing would ever reference or collect an output file written
    before the failure (the host sink's
    ``test_direct_sink_midloop_failure_cleans_outputs``, at the device
    doors)."""
    rows = 5000  # 32-byte rows against 16,000-byte files: several files
    if door == "engine_seam":
        entries = [(key(i), i + 1, P, pack64(i)) for i in range(rows)]
        made = []

        def path_factory():
            made.append(str(tmp_path / f"out{len(made)}.tsst"))
            return made[-1]

        asked = _fail_second_write(monkeypatch)
        with pytest.raises(OSError):
            tb.TpuCompactionBackend().merge_runs_to_files(
                [entries], UInt64AddOperator(), True, path_factory,
                block_bytes=4096, compression=0, bits_per_key=10,
                target_file_bytes=16_000)
        assert asked == made[:2]
        assert not any(os.path.exists(p) for p in made)
        return
    db = DB(str(tmp_path / "db"), DBOptions(
        merge_operator=UInt64AddOperator(), target_file_bytes=16_000))
    for lo in range(0, rows, 500):
        wb = WriteBatch()
        for i in range(lo, lo + 500):
            wb.put(key(i), pack64(i))
        db.write(wb)
    db.flush()
    wb = WriteBatch()
    for i in range(0, rows, 7):
        wb.merge(key(i), pack64(1))
    db.write(wb)
    db.flush()
    live = {n for level in db._levels for n in level}
    asked = _fail_second_write(monkeypatch)
    handled, remaining = cs.compact_dbs_batched([("db", db)])
    assert len(asked) == 2
    assert handled == [] and [n for n, _ in remaining] == ["db"]
    on_disk = {n for n in os.listdir(db.path) if n.endswith(".tsst")}
    assert on_disk == live, "an output file was left behind"
    monkeypatch.undo()
    db.compact_range()  # the plan's mutex was handed back
    assert db.get(key(7)) == pack64(8) and db.get(key(8)) == pack64(8)
    db.close()
