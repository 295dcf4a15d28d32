"""The YCSB E cell's reference and readers, no chip: YCSB's hashed keys
and its RocksDB binding's record, the scan model and its two controls,
the least bytes of a unit, the three new per-layer readers, the driver's
question to the program, and the cell run by ``run.py`` at its rehearsal
size on the CPU."""

import json
import os
import struct
import subprocess
import sys
import types

import numpy as np
import pytest

from chipbench import workload_ycsb as wy
from chipbench.drivers import refresh_scan
from chipbench.layers import (scan_blocks_per_scan, scan_engine_ms,
                              ycsbe_pipeline_roofline)
from chipbench.reduce import Run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                     "ycsb_e_32x20k.json")))
CELL = "ycsb_e_32x20k.refresh"


def fnv_java(keynum):
    """YCSB's Utils.fnvhash64, line by line in Java's signed longs."""
    def s64(x):
        x &= (1 << 64) - 1
        return x - (1 << 64) if x >= 1 << 63 else x

    h = s64(0xCBF29CE484222325)
    for _ in range(8):
        octet = keynum & 0xFF
        keynum >>= 8
        h = s64(h ^ octet)
        h = s64(h * 1099511628211)
    return h if h == -(1 << 63) else abs(h)


def test_keys_are_ycsb_s_hashed_keys():
    nums = [0, 1, 2, 31, 655359, 655360, 2**40 + 7]
    assert wy.keys_of(nums) == [b"user%d" % fnv_java(n) for n in nums]
    slot = wy.slot_keynums(32, 20480, 5)
    assert len(slot) == 20480 + 1024 and (slot % 32 == 5).all()
    assert slot[20480] == 32 * 20480 + 5  # the first live insert
    keys = wy.keys_of(slot)
    lens = [len(k) for k in keys]
    assert len(set(keys)) == len(keys)
    assert wy.key_lengths(slot).tolist() == lens
    assert 5 <= min(lens) and max(lens) <= 23 and len(set(lens)) >= 4
    assert np.mean([n >= 22 for n in lens]) > 0.9


def test_a_record_is_the_binding_s_serialization():
    (rec,) = wy.records(9, 3, [77])
    assert len(rec) == wy.RECORD_BYTES == 1140
    raw, pos = rec.tobytes(), 0
    for f in range(10):
        (n,) = struct.unpack_from(">i", raw, pos)
        assert raw[pos + 4:pos + 4 + n] == b"field%d" % f
        pos += 4 + n
        (v,) = struct.unpack_from(">i", raw, pos)
        assert v == 100
        assert all(32 <= c < 127 for c in raw[pos + 4:pos + 104])
        pos += 4 + v
    assert pos == 1140
    assert not np.array_equal(rec, wy.records(10, 3, [77])[0])


def test_the_scan_model_and_its_controls():
    seed, slots, rows, slot = 11, 12, 256, 4
    m = wy.ScanModel(seed, slots, rows, slot)
    assert len(m) == rows + wy.inserts(rows) and m.keys == sorted(m.keys)
    bulk = wy.bulk_rows(seed, slots, rows, slot)
    live = wy.insert_rows(seed, slots, rows, slot)
    truth = dict(bulk + live)
    assert [k for k, _v in bulk] == sorted(k for k, _v in bulk)
    keys = sorted(truth)
    for start in (b"", b"user", keys[0], keys[100], keys[-1] + b"0",
                  keys[50][:-1], b"\xff"):
        for n in (1, 37, 100, 10_000):
            i = next((j for j, k in enumerate(keys) if k >= start),
                     len(keys))
            assert m.scan(start, n) == [(k, truth[k])
                                        for k in keys[i:i + n]]
    probes = wy.probe_keys(slots, rows, slot, 64, seed)
    assert all(m.get(k) == truth.get(k) for k in probes)
    assert sum(k not in truth for k in probes) >= 8
    bits = wy.ScanModel(seed, slots, rows, slot, "bits32")
    fold = wy.ScanModel(seed, slots, rows, slot, "fold32")
    assert all(v[8:] == bytes(1132) for _k, v in bits.scan(b"", 50))
    assert len(fold) == rows and fold.scan(b"", 10**6) != m.scan(b"", 10**6)


def test_unit_bytes_are_the_keys_and_records_not_the_launch():
    got = wy.unit_bytes(CONFIG)
    n = 20480 + 1024
    keys = np.mean([wy.key_lengths(wy.slot_keynums(32, 20480, s)).sum()
                    for s in range(32)])
    assert got == pytest.approx(2 * (keys + n * (9 + 1140))
                                + (n * 10 + 7) // 8)
    # 512-word value rows and 24-byte key lanes would be ~1.8x as many
    assert got < 2 * n * (24 + 9 + 2048) / 1.7


def run_of(spans, modules=None):
    run = Run(config=CONFIG, traffic={}, seconds=51.0, t0=0.0, setup_s=1.0,
              ops=[], units=[], spans=spans,
              peaks={"hbm_bytes_per_s": 819e9})
    if modules is not None:
        run.trace = {"modules": modules}
    return run


def test_the_three_readers():
    spans = [{"name": "storage.scan", "duration_ms": ms,
              "annotations": {"rows": 5, "blocks": b, "files": 1,
                              "mem_rows": 0, "limit": 5}}
             for ms, b in ((2.0, 1), (4.0, 2), (3.0, 3))]
    spans += [{"name": "tpu.compact_stream", "duration_ms": 1.0,
               "annotations": {"shards": 8, "group_size": 8}}]
    run = run_of(spans, {"jit_one_shard_index": {"count": 2,
                                                 "seconds": 0.05}})
    assert scan_engine_ms.read(run) == pytest.approx(3.0)
    assert scan_blocks_per_scan.read(run) == pytest.approx(2.0)
    share = ycsbe_pipeline_roofline.read(run)
    assert share == pytest.approx(
        100 * 2 * 8 * wy.unit_bytes(CONFIG) / 819e9 / 0.05)
    assert 0 < share < 100
    # a program without the span and the module (the parent's) gives
    # nothing
    bare = run_of([], {})
    assert scan_engine_ms.read(bare) is None
    assert scan_blocks_per_scan.read(bare) is None
    assert ycsbe_pipeline_roofline.read(bare) is None


def test_the_driver_asks_the_program(monkeypatch):
    assert refresh_scan.program_takes(CONFIG) == ""
    # a program without the scan's question (the parent's): nothing is
    # built
    fake = types.ModuleType("rocksplicator_tpu.storage.engine")
    monkeypatch.setitem(sys.modules, fake.__name__, fake)
    assert "cannot say" in refresh_scan.program_takes(CONFIG)
    fake.scan_seeks = lambda: True
    svc = types.ModuleType("rocksplicator_tpu.tpu.compaction_service")
    svc.device_mixed_key_bytes_max = lambda op: 16
    monkeypatch.setitem(sys.modules, svc.__name__, svc)
    assert "keys reach 24 B" in refresh_scan.program_takes(CONFIG)


def test_the_cell_is_declared_as_the_issue_says():
    (cell,) = [w for w in BENCH["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ycsb_e_32x20k", "refresh_insert_scan", 1)
    declared = {m["name"] for g in ("end_to_end", "per_layer")
                for m in BENCH[g] if CELL in m.get("workloads", ())}
    assert {"refresh_rows_per_s", "ingest_p95_s", "read_p95_ms",
            "write_p95_ms", "ingest_compact_ms", "launch_fill_pct",
            "device_idle_pct", "index_path_pct", "mixed_key_path_pct",
            "value_fill_pct", "scan_engine_ms", "scan_blocks_per_scan",
            "ycsbe_pipeline_roofline"} <= declared
    assert len([n for n in declared if n.startswith(("read_", "write_"))
                and n.endswith("_ms") and not n.endswith("p95_ms")]) == 11
    assert "compact_pipeline_roofline" not in declared
    for name in ("scan_engine_ms", "scan_blocks_per_scan",
                 "ycsbe_pipeline_roofline"):
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert m["workloads"] == [CELL]


def run_py(*extra, seed=2**31 + 77):
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "2", "--rehearse", *extra],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    marked = [line for line in out.stdout.splitlines()
              if "REHEARSAL ONLY" in line]
    result = json.loads(marked[-1].split("not a chip run: ", 1)[1]
                        ) if marked else None
    return out, result


def test_sound_rehearsal_scans_through_the_seek():
    out, result = run_py("--trace", "1")
    assert out.returncode == 3, out.stderr[-2000:]
    assert result["correct"] is True and result["failed"] == 0
    assert result["window_compilations"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["index_path_pct"] == 100.0
    assert metrics["mixed_key_path_pct"] == 100.0
    assert 1.0 <= metrics["scan_blocks_per_scan"] <= 3.0
    assert metrics["scan_engine_ms"] > 0


@pytest.mark.parametrize("control", ["bits32", "fold32"])
def test_control_exits_1_with_correct_false(control):
    out, result = run_py("--control", control)
    assert out.returncode == 1
    assert result["correct"] is False
    assert result["compared"]["mismatched_answers"]["value"] > 0
    assert result["compared"]["failed_rpcs"]["value"] == 0
