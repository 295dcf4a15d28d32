"""Streaming bounded-memory compaction (round 17).

Covers the chunked k-way merge tentpole — byte-identical output vs the
in-RAM single pass with resolution state (MERGE operand chains, dup-key
stacks, tombstones) straddling chunk boundaries, the hard memory
ceiling asserted through the compaction.peak_bytes_materialized gauge,
the crash-at-chunk matrix over the compact.stream.* seams, the
probe-don't-fill block-cache contract, the TPU double-buffered chunk
resolver, and the /cluster_stats merge of the peak gauge.
"""

import hashlib
import os
import struct

import pytest

import rocksplicator_tpu.storage.native_compaction as nc
import rocksplicator_tpu.storage.stream_merge as sm
from rocksplicator_tpu.storage.engine import (DB, DBOptions,
                                              register_db_gauges,
                                              unregister_db_gauges)
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.storage.sst import BlockCache, SSTReader, SSTWriter
from rocksplicator_tpu.testing import failpoints as fp
from rocksplicator_tpu.utils.stats import Stats

P, D, M = 1, 2, 3
pack_u64 = struct.Struct("<q").pack


def counter(name: str) -> float:
    return Stats.get().get_counter(name)


@pytest.fixture(autouse=True)
def _reset_stream_knobs():
    yield
    sm.STREAM_MODE_OVERRIDE = None
    sm.CHUNK_ENTRIES_OVERRIDE = None
    sm.CompactionMemoryBudget.reset_for_test()


def _write_run(path, entries, block_bytes=4096):
    entries = sorted(entries, key=lambda e: (e[0], -e[1]))
    w = SSTWriter(path, block_bytes)
    for k, s, t, v in entries:
        w.add(k, s, t, v)
    w.finish()
    return path


def _write_planar_run(path, entries, block_bytes=4096):
    """Runs that mix tombstones with values stream only from PLANAR
    files (empty-value deletes break the uniform row stride) — which is
    exactly how the engine's flush emits them."""
    from rocksplicator_tpu.ops.kv_format import pack_entries
    from rocksplicator_tpu.tpu.format import (planar_stride,
                                              write_sst_from_arrays)

    entries = sorted(entries, key=lambda e: (e[0], -e[1]))
    arr = nc.NativeCompactionBackend._arrays_from_entries(
        entries, pack_entries)
    n = arr["key_len"].shape[0]
    vl = arr["val_len"][arr["vtype"] != D]
    vlen = int(vl[0]) if len(vl) else 0
    stride = planar_stride(int(arr["key_len"][0]), vlen)
    props = write_sst_from_arrays(
        arr, n, path, block_entries=max(64, block_bytes // stride),
        compression=0, bits_per_key=10, planar=True)
    assert props is not None
    return path


def _straddle_runs(root):
    """Three overlapping runs stressing every chunk-boundary hazard:
    a MERGE-operand chain long enough to span many blocks (and so many
    windows), dup-key stacks at many seqs, tombstones shadowing puts
    from other runs — the round-16 slice matrix plus the
    state-straddles-a-window cases only streaming can hit."""
    runs = [_write_run(os.path.join(root, "r0.tsst"), [
        (b"k%05d" % i, 1000 + i, P, pack_u64(i))
        for i in range(0, 3000, 2)])]
    e = [(b"k%05d" % i, 50000 + i, M, pack_u64(7))
         for i in range(0, 3000, 3)]
    e += [(b"k%05d" % i, 56000 + i, M, pack_u64(5))
          for i in range(0, 3000, 6)]
    # one key's operand chain spans MANY 4 KiB blocks: its group cannot
    # fit a window, so its rows must carry across chunk boundaries
    e += [(b"k01500", 90000 + j, M, pack_u64(1)) for j in range(2000)]
    runs.append(_write_run(os.path.join(root, "r1.tsst"), e))
    e = []
    for i in range(0, 3000, 5):
        if i % 10:
            e.append((b"k%05d" % i, 70000 + i, D, b""))
        else:
            e.append((b"k%05d" % i, 70000 + i, P, pack_u64(1)))
    # a dup-key PUT stack spanning blocks (no-operator straddle case)
    e += [(b"k00777", 80000 + j, P, pack_u64(j)) for j in range(1500)]
    runs.append(_write_planar_run(os.path.join(root, "r2.tsst"), e))
    return runs


def _sha_files(outs):
    hs = []
    for p, _props in outs:
        with open(p, "rb") as f:
            hs.append(hashlib.sha256(f.read()).hexdigest())
    return hs


def _merge(paths, tag, root, merge_op, drop, mode, chunk=None,
           tracker=None):
    sm.STREAM_MODE_OVERRIDE = mode
    sm.CHUNK_ENTRIES_OVERRIDE = chunk
    cnt = [0]

    def pf():
        cnt[0] += 1
        return os.path.join(root, f"out-{tag}-{cnt[0]}.tsst")

    outs = nc.direct_merge_runs_to_files(
        [SSTReader(p) for p in paths], merge_op, drop, pf,
        4096, 0, 10, 8192, mem_tracker=tracker)
    assert outs is not None, tag
    return outs


# ---------------------------------------------------------------------------
# byte identity with state straddling chunk boundaries
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("drop_tombstones", [False, True])
@pytest.mark.parametrize("merge_op", [None, UInt64AddOperator()],
                         ids=["no-op", "uint64add"])
def test_stream_chunk_matrix_byte_identical(
        tmp_path, drop_tombstones, merge_op):
    """The acceptance matrix: the streamed output is byte-identical
    file-for-file to the unsliced in-RAM merge across uint64add MERGE
    chains, dup-key runs, and tombstones split across chunks — with
    chunk windows small enough that the giant groups straddle many
    chunk boundaries (the carried-state path)."""
    paths = _straddle_runs(str(tmp_path))
    if merge_op is None:
        # MERGE records without an operator decline the array path in
        # BOTH modes; use the put/tombstone runs only
        paths = [paths[0], paths[2]]
    base_chunks = counter("compaction.stream_chunks")
    unstreamed = _merge(paths, f"u{drop_tombstones}", str(tmp_path),
                        merge_op, drop_tombstones, "never")
    assert counter("compaction.stream_chunks") == base_chunks
    streamed = _merge(paths, f"s{drop_tombstones}", str(tmp_path),
                      merge_op, drop_tombstones, "always", chunk=300)
    # tiny windows: the merge really crossed many chunk seams
    assert counter("compaction.stream_chunks") >= base_chunks + 3
    assert _sha_files(streamed) == _sha_files(unstreamed)
    assert len(streamed) > 0


def test_stream_output_readable_and_resolved(tmp_path):
    """Sanity beyond hashes: the streamed outputs decode to the same
    resolved entries the scalar reference fold produces."""
    paths = _straddle_runs(str(tmp_path))
    op = UInt64AddOperator()
    streamed = _merge(paths, "r", str(tmp_path), op, True, "always",
                      chunk=300)
    got = []
    for p, _props in sorted(
            streamed, key=lambda o: SSTReader(o[0]).min_key() or b""):
        r = SSTReader(p)
        got.extend(r.iterate())
        r.close()
    # the giant chain folded to one PUT: 2000 operands + shadowed bases
    chain = [e for e in got if e[0] == b"k01500"]
    assert len(chain) == 1 and chain[0][2] == P
    keys = [e[0] for e in got]
    assert keys == sorted(keys)
    assert len(set(keys)) == len(keys)  # one entry per key at bottom


# ---------------------------------------------------------------------------
# the hard memory ceiling (acceptance: peak <= budget, input >> budget)
# ---------------------------------------------------------------------------


def test_budget_ceiling_holds_for_input_far_over_budget(tmp_path):
    """A compaction whose lane image is ~20x the configured budget
    completes with peak_bytes_materialized <= budget and byte-identical
    (checksummed) output vs the in-RAM pass on the same runs."""
    big = _write_run(os.path.join(str(tmp_path), "big.tsst"), [
        (b"b%07d" % i, i + 1, P, pack_u64(i)) for i in range(120000)])
    big2 = _write_run(os.path.join(str(tmp_path), "big2.tsst"), [
        (b"b%07d" % i, 200000 + i, P, pack_u64(i * 3))
        for i in range(0, 120000, 2)])
    budget = 512 * 1024  # lane image ~27 MB >> 512 KiB
    sm.CompactionMemoryBudget.reset_for_test(budget)
    tracker = sm.CompactionMemoryBudget.get().tracker()
    streamed = _merge([big, big2], "b", str(tmp_path), None, True,
                      None, tracker=tracker)  # auto mode: must stream
    assert counter("compaction.stream_merges") >= 1
    assert 0 < tracker.peak <= budget
    sm.CompactionMemoryBudget.reset_for_test()
    unstreamed = _merge([big, big2], "ub", str(tmp_path), None, True,
                        "never")
    assert _sha_files(streamed) == _sha_files(unstreamed)


def test_auto_mode_keeps_small_compactions_in_ram(tmp_path):
    """Below the budget the in-RAM path (and its subcompaction
    parallelism) stays the default — streaming costs the serving path
    nothing on workloads that already fit."""
    p = _write_run(os.path.join(str(tmp_path), "s.tsst"), [
        (b"k%04d" % i, i + 1, P, pack_u64(i)) for i in range(500)])
    base = counter("compaction.stream_merges")
    _merge([p], "small", str(tmp_path), None, True, None)
    assert counter("compaction.stream_merges") == base


def test_degrades_to_block_floor_never_aborts(tmp_path):
    """A budget below the block-granularity floor cannot be honored —
    the pipeline degrades to one-block windows and completes (never
    aborts), reporting the honest peak."""
    big = _write_run(os.path.join(str(tmp_path), "g.tsst"), [
        (b"g%06d" % i, i + 1, P, pack_u64(i)) for i in range(30000)],
        block_bytes=32 * 1024)
    sm.CompactionMemoryBudget.reset_for_test(16 * 1024)  # absurdly low
    tracker = sm.CompactionMemoryBudget.get().tracker()
    outs = _merge([big], "g", str(tmp_path), None, True, "always",
                  tracker=tracker)
    assert outs and tracker.peak > 16 * 1024  # honest, not clamped


def test_tombstone_prefix_does_not_defeat_the_ceiling(tmp_path):
    """An all-tombstone resolved PREFIX (every early key deleted,
    drop_tombstones=False) must not buffer unboundedly while the sink
    waits for a value row to derive vlen from: once one file's worth is
    buffered the sink seeds widths from the PLAN, stays byte-identical
    (the later value row matches the planned width, as the per-block
    checks guarantee), and the peak stays bounded."""
    dels = [(b"a%06d" % i, 10000 + i, D, b"") for i in range(40000)]
    tail = [(b"z%06d" % i, 50000 + i, P, pack_u64(i)) for i in range(50)]
    p = _write_planar_run(os.path.join(str(tmp_path), "tp.tsst"),
                          dels + tail)
    budget = 768 * 1024
    sm.CompactionMemoryBudget.reset_for_test(budget)
    tracker = sm.CompactionMemoryBudget.get().tracker()
    streamed = _merge([p], "tp", str(tmp_path), None, False, "always",
                      chunk=2048, tracker=tracker)
    # the tombstone prefix is ~40k rows against an epf of ~1-2k: without
    # the plan-width valve the sink would hold the whole prefix
    assert 0 < tracker.peak <= budget
    sm.CompactionMemoryBudget.reset_for_test()
    unstreamed = _merge([p], "utp", str(tmp_path), None, False, "never")
    assert _sha_files(streamed) == _sha_files(unstreamed)


def test_dboptions_budget_is_mutable(tmp_path):
    opts = DBOptions(memtable_bytes=1 << 30)
    with DB(str(tmp_path / "db"), opts) as db:
        db.set_options({"compaction_memory_budget_bytes": 123456})
        assert db.options.compaction_memory_budget_bytes == 123456


# ---------------------------------------------------------------------------
# engine integration + the peak gauge end to end
# ---------------------------------------------------------------------------


def test_engine_compaction_streams_with_gauge(tmp_path):
    """compact_range over input >> budget streams, content is intact,
    and compaction.peak_bytes_materialized lands on the gauge registry
    (<= budget) and in the Prometheus dump."""
    budget = 1 << 20
    sm.CompactionMemoryBudget.reset_for_test(budget)
    opts = DBOptions(memtable_bytes=1 << 30, target_file_bytes=64 * 1024)
    with DB(str(tmp_path / "db"), opts) as db:
        for burst in range(3):
            for i in range(20000):
                db.put(b"k%06d" % i, (b"%03d" % burst) + b"v" * 13)
            db.flush()
        before = list(db.new_iterator())
        base = counter("compaction.stream_merges")
        db.compact_range()
        assert counter("compaction.stream_merges") == base + 1
        assert list(db.new_iterator()) == before
        peak = db.metrics_snapshot(max_age=0)[
            "compaction_peak_bytes_materialized"]
        assert 0 < peak <= budget
        names = register_db_gauges("stream00001", db)
        try:
            vals = Stats.get().gauge_values()
            hits = {k: v for k, v in vals.items()
                    if k.startswith("compaction.peak_bytes_materialized")}
            assert hits and max(hits.values()) == peak
            dump = Stats.get().dump_prometheus()
            assert "compaction_peak_bytes_materialized" in dump
        finally:
            unregister_db_gauges(names)


def test_cluster_stats_merges_peak_gauge():
    """/cluster_stats carries the worst replica's compaction memory
    high-water per shard (max, like debt — the fleet view of the
    ceiling)."""
    from rocksplicator_tpu.cluster.stats_aggregator import \
        ClusterStatsAggregator
    from rocksplicator_tpu.utils.stats import tagged

    mk = lambda peak: {
        "gauges": {
            tagged("compaction.peak_bytes_materialized", db="seg00000",
                   port="1"): peak,
        },
        "shard_roles": {"seg00000": "FOLLOWER"},
    }
    cs = ClusterStatsAggregator.aggregate(
        {"h1:1": mk(100.0), "h2:1": mk(250.0)})
    assert cs["per_shard"]["seg00000"][
        "compaction_peak_bytes_materialized"] == 250.0


# ---------------------------------------------------------------------------
# crash-at-chunk matrix (compact.stream.* seams)
# ---------------------------------------------------------------------------


def _fill_two_l0(db, n=2000):
    for i in range(n):
        db.put(b"a%05d" % i, b"v" * 16)
    db.flush()
    for i in range(0, n, 2):
        db.put(b"a%05d" % i, b"w" * 16)
    db.flush()


@pytest.mark.parametrize("seam,policy", [
    ("compact.stream.refill", "fail_nth:1"),
    ("compact.stream.chunk", "fail_nth:1"),
    ("compact.stream.chunk", "fail_nth:3"),  # mid-stream, outputs exist
])
def test_stream_fault_sweeps_outputs_and_falls_back(
        tmp_path, seam, policy):
    """A fault at any stream seam sweeps every partial output; the
    engine's fallback still completes the compaction with identical
    content and no orphan files."""
    sm.STREAM_MODE_OVERRIDE = "always"
    sm.CHUNK_ENTRIES_OVERRIDE = 512
    with DB(str(tmp_path / "db"), DBOptions(memtable_bytes=1 << 30)) as db:
        _fill_two_l0(db)
        before = list(db.new_iterator())
        fp.activate(seam, policy)
        try:
            db.compact_range()  # stream raises, fallback completes
        finally:
            fp.deactivate(seam)
        assert list(db.new_iterator()) == before
        live = {n for files in db._levels for n in files}
        disk = {f for f in os.listdir(db.path) if f.endswith(".tsst")}
        assert disk == live, f"{seam} leaked orphan outputs"


@pytest.mark.parametrize("seam", ["compact.stream.refill",
                                  "compact.stream.chunk"])
def test_crash_at_stream_seam_reopen_is_pre_compaction(tmp_path, seam):
    """The crash story: a kill at any stream seam (with the fallback's
    install also dying, as a crash would take both) leaves reopen
    exactly pre-compaction — outputs never installed, inputs never
    dropped."""
    sm.STREAM_MODE_OVERRIDE = "always"
    sm.CHUNK_ENTRIES_OVERRIDE = 512
    path = str(tmp_path / ("db-" + seam.replace(".", "_")))
    with DB(path, DBOptions(memtable_bytes=1 << 30)) as db:
        _fill_two_l0(db)
        before = list(db.new_iterator())
        fp.activate(seam, "fail_nth:1")
        fp.activate("compact.install", "fail_nth:1")
        try:
            with pytest.raises(Exception):
                db.compact_range()
        finally:
            fp.deactivate(seam)
            fp.deactivate("compact.install")
    with DB(path, DBOptions()) as db2:
        assert list(db2.new_iterator()) == before
        live = {n for files in db2._levels for n in files}
        disk = {f for f in os.listdir(db2.path) if f.endswith(".tsst")}
        assert disk == live


# ---------------------------------------------------------------------------
# probe-don't-fill: a streaming compaction must not evict hot blocks
# ---------------------------------------------------------------------------


def test_streaming_compaction_does_not_evict_hot_blocks(tmp_path):
    """Block-cache hit-rate stability across a background compaction:
    db_hot's working set stays cached while db_cold streams a
    compaction far larger than the cache — streaming decode probes the
    LRU but never fills it (the bulk-scan convention)."""
    BlockCache.reset_for_test(64 * 1024)
    try:
        sm.STREAM_MODE_OVERRIDE = "always"
        sm.CHUNK_ENTRIES_OVERRIDE = 1024
        with DB(str(tmp_path / "hot"),
                DBOptions(memtable_bytes=1 << 30)) as hot, \
                DB(str(tmp_path / "cold"),
                   DBOptions(memtable_bytes=1 << 30)) as cold:
            for i in range(500):
                hot.put(b"h%04d" % i, b"v" * 16)
            hot.flush()
            hot.compact_range()
            hot_keys = [b"h%04d" % i for i in range(500)]
            for k in hot_keys:  # warm the cache (point-read fills)
                assert hot.get(k) is not None
            for k in hot_keys:  # now fully cache-served
                hot.get(k)
            misses_before = counter("storage.block_cache.miss")
            # a cold compaction several times the cache capacity
            for i in range(8000):
                cold.put(b"c%05d" % i, b"x" * 16)
            cold.flush()
            for i in range(0, 8000, 2):
                cold.put(b"c%05d" % i, b"y" * 16)
            cold.flush()
            base = counter("compaction.stream_merges")
            cold.compact_range()
            assert counter("compaction.stream_merges") == base + 1
            # the hot working set must still be cache-resident
            for k in hot_keys:
                assert hot.get(k) is not None
            assert counter("storage.block_cache.miss") == misses_before
    finally:
        BlockCache.reset_for_test()


# ---------------------------------------------------------------------------
# declines, probes, TPU resolver, adaptive sizing
# ---------------------------------------------------------------------------


def test_mid_stream_width_drift_declines_cleanly(tmp_path):
    """A file whose later blocks violate the probed uniform stride
    declines streaming mid-flight: written outputs are swept, and the
    in-RAM path (which takes values of differing widths, with no merge
    operator) writes the result whole; with an operator it would hand
    off to the tuple merge (None)."""
    path = os.path.join(str(tmp_path), "drift.tsst")
    entries = [(b"d%05d" % i, i + 1, P, b"v" * 8) for i in range(600)]
    entries += [(b"e%05d" % i, i + 1, P, b"w" * 16) for i in range(600)]
    _write_run(path, entries, block_bytes=1024)
    sm.STREAM_MODE_OVERRIDE = "always"
    sm.CHUNK_ENTRIES_OVERRIDE = 256
    cnt = [0]

    def pf():
        cnt[0] += 1
        return os.path.join(str(tmp_path), f"o{cnt[0]}.tsst")

    base = counter("compaction.stream_declines")
    outs = nc.direct_merge_runs_to_files(
        [SSTReader(path)], None, True, pf, 4096, 0, 10, 8192)
    assert counter("compaction.stream_declines") == base + 1
    leftovers = sorted(f for f in os.listdir(str(tmp_path))
                       if f.startswith("o"))
    assert leftovers == sorted(os.path.basename(p) for p, _ in outs), (
        "decline leaked partial outputs")
    got = [(k, s, t, v) for p, _ in outs
           for k, s, t, v in SSTReader(p).iterate()]
    assert got == sorted(entries, key=lambda e: (e[0], -e[1]))


def test_block_lane_source_probe_matrix(tmp_path):
    """probe() recognizes planar, uniform-prop, and inferred-uniform
    files; mixed-key-width files are not streamable."""
    from rocksplicator_tpu.tpu.format import SstBlockLaneSource

    uni = _write_run(os.path.join(str(tmp_path), "u.tsst"), [
        (b"u%04d" % i, i + 1, P, pack_u64(i)) for i in range(300)])
    src = SstBlockLaneSource.probe(SSTReader(uni))
    assert src is not None and src.kind == "uniform"
    assert src.klen == 5 and src.vlen == 8
    lanes = src.decode_blocks(0, 1)
    assert lanes["key_len"].shape[0] > 0
    with DB(str(tmp_path / "db"),
            DBOptions(memtable_bytes=1 << 30)) as db:
        for i in range(2000):
            db.put(b"p%05d" % i, b"v" * 16)
        db.flush()
        name = db._levels[0][0]
        psrc = SstBlockLaneSource.probe(db._readers[name])
        assert psrc is not None and psrc.kind == "planar"
    mixed = _write_run(os.path.join(str(tmp_path), "m.tsst"), [
        (b"k" * (3 + (i % 4)), i + 1, P, b"v") for i in range(64)])
    assert SstBlockLaneSource.probe(SSTReader(mixed)) is None


def test_tpu_backend_streams_byte_identical(tmp_path):
    """The TPU backend's streaming path (device chunk resolver, double
    buffered) produces the same bytes as the CPU pipeline."""
    from rocksplicator_tpu.tpu.backend import TpuCompactionBackend

    paths = _straddle_runs(str(tmp_path))
    op = UInt64AddOperator()
    ram = _merge(paths, "ram", str(tmp_path), op, True, "never")
    sm.STREAM_MODE_OVERRIDE = "always"
    sm.CHUNK_ENTRIES_OVERRIDE = 400
    cnt = [0]

    def pf():
        cnt[0] += 1
        return os.path.join(str(tmp_path), f"tpu-{cnt[0]}.tsst")

    base = counter("compaction.stream_chunks")
    outs = TpuCompactionBackend().merge_runs_to_files(
        [SSTReader(p) for p in paths], op, True, pf, 4096, 0, 10, 8192)
    assert outs is not None
    assert counter("compaction.stream_chunks") > base
    assert _sha_files(outs) == _sha_files(ram)


def test_adaptive_chunk_entries_shrinks_under_stall():
    from rocksplicator_tpu.storage.compaction_scheduler import (
        IoBudget, adaptive_chunk_entries)

    budget = IoBudget(0)
    assert adaptive_chunk_entries(4096, None) == 4096
    assert adaptive_chunk_entries(4096, budget) == 4096
    budget.note_stall(500.0)  # heavy admission stalls
    shrunk = adaptive_chunk_entries(4096, budget)
    assert 4096 // 4 <= shrunk < 4096


# ---------------------------------------------------------------------------
# stream-merge-bench artifact shape (the make stream-merge-smoke contract)
# ---------------------------------------------------------------------------


def test_stream_merge_bench_smoke_artifact_shape(tmp_path):
    """Tiny in-process run of benchmarks/stream_merge_bench.py pinning
    the artifact contract the make target and PERF round 17 rely on:
    both arms complete, checksums equal, the streamed peak is under the
    budget while the in-RAM peak exceeds it, and the stream crossed
    chunk seams."""
    import json

    from benchmarks.stream_merge_bench import main as bench_main

    out = tmp_path / "smb.json"
    rc = bench_main([
        "--keys", "12000", "--runs", "3", "--reps", "1",
        "--budget_kb", "256", "--target_file_kb", "32",
        "--chunk_entries", "1024", "--out", str(out),
    ])
    assert rc == 0
    art = json.loads(out.read_text())
    assert art["bench"] == "stream_merge_bench"
    assert art["failures"] == []
    assert "host_calibration" in art["ab"]
    budget = art["budget_bytes"]
    ram = art["ab"]["samples"]["in_ram"][0]
    streamed = art["ab"]["samples"]["streamed"][0]
    assert streamed["output_sha256"] == ram["output_sha256"]
    assert 0 < streamed["peak_bytes_materialized"] <= budget
    assert ram["peak_bytes_materialized"] > budget
    assert streamed["stream_chunks"] >= 2
    assert streamed["stream_refills"] >= 2
    assert ram["stream_chunks"] == 0
    for arm in (ram, streamed):
        assert arm["mb_per_sec"] > 0
        assert arm["output_files"] > 0


def test_stream_mode_env(monkeypatch):
    monkeypatch.setenv(sm.ENV_STREAM_MODE, "0")
    assert sm.stream_mode() == "never"
    monkeypatch.setenv(sm.ENV_STREAM_MODE, "always")
    assert sm.stream_mode() == "always"
    monkeypatch.delenv(sm.ENV_STREAM_MODE, raising=False)
    assert sm.stream_mode() == "auto"
    sm.STREAM_MODE_OVERRIDE = "never"
    assert sm.stream_mode() == "never"
