"""The MixGraph cell's reference and readers, no chip: db_bench's keys,
the value size rule, the key-range draw, the model and its two controls,
the least bytes of a unit, the three new per-layer readers and the
driver's question to the program."""

import json
import os
import struct
import sys
import types

import numpy as np
import pytest

from chipbench import workload_mixgraph as wm
from chipbench.drivers import refresh_mixgraph as rmg
from chipbench.layers import (mixgraph_pipeline_roofline, value_fill_pct,
                              wide_key_path_pct)
from chipbench.reduce import Run

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CONFIG = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                     "mixgraph_64x20k.json")))


def test_keys_are_db_bench_keys_in_number_order():
    k = wm.bulk_key(7, 3)
    assert len(k) == 48 and k[8:] == b"0" * 40
    assert struct.unpack(">Q", k[:8])[0] == 7 + 2500 * 3
    keys = [wm.bulk_key(5, i) for i in range(300)]
    assert keys == sorted(keys)  # bytewise order is number order
    assert wm.live_key(5, 0) > wm.bulk_key(5, 19999)
    assert wm._slot_index(wm.bulk_key(5, 42)) == 42
    assert wm._slot_index(wm.live_key(5, 9)) == wm.LIVE_INDEX + 9


def test_value_sizes_follow_the_rule_and_the_seed():
    idx, zero = np.arange(20000), np.zeros(20000, np.uint64)
    a = wm.value_lengths(2147483647, 3, idx, zero)
    assert np.array_equal(a, wm.value_lengths(2147483647, 3, idx, zero))
    assert not np.array_equal(a, wm.value_lengths(2147483648, 3, idx, zero))
    assert a.min() >= 0 and a.max() <= 1024
    assert 0.28 < (a == 10).mean() < 0.34
    assert abs(a.mean() - wm.mean_value_bytes()) < 2.0
    assert a.max() > 512  # a shard's widest value: the 256-word bucket
    # the rule at its edges: the clamp, and the wrap above 1,024
    u = np.array([0.99999, 0.9, 1e-12])
    assert wm.size_of(u).tolist()[:2] == [10, 10]
    assert 0 <= wm.size_of(u)[2] < 1024
    vals = wm.values(1, 2, [0, 1, 5], [0, 0, 3])
    assert [len(v) for v in vals] == wm.value_lengths(
        1, 2, [0, 1, 5], [0, 0, 3]).tolist()
    assert all(32 <= c < 127 for v in vals for c in v)


def test_the_model_and_its_controls():
    seed, slot, rows = 11, 4, 256
    exact = wm.slot_model(seed, slot, rows, True)
    assert len(exact) == rows + wm.live_counters(rows)
    bits = wm.slot_model(seed, slot, rows, True, "bits32")
    fold = wm.slot_model(seed, slot, rows, True, "fold32")
    keys = wm.probe_keys(seed, slot, rows, 64, True)
    wrong_bits = sum(exact.get(k) != bits.get(k) for k in keys)
    wrong_fold = sum(exact.get(k) != fold.get(k) for k in keys)
    assert wrong_bits > len(keys) // 2 and wrong_fold > 0
    for k in keys:
        v = exact.get(k)
        if v is not None:
            assert bits.get(k)[:len(v)] == v
            assert len(bits.get(k)) == wm.bucket_bytes(len(v))
    # absent keys that share a written key's first 24 bytes, or all of
    # them but the last
    absent = [k for k in keys if exact.get(k) is None]
    written = {k[:24] for k in keys if exact.get(k) is not None}
    assert any(len(k) == 48 and k[:24] in written for k in absent)
    assert any(len(k) == 47 for k in absent)


def test_reads_draw_ranges_by_the_two_term_exponential():
    w = wm.range_weights(3)
    assert abs(w.sum() - 1) < 1e-12 and len(w) == 30
    assert 0.75 < w.max() < 0.82  # the hottest range
    per = wm.read_weights(3, 8, 2000, True)
    assert len(per) == 8 * (2000 + wm.live_counters(2000))
    assert abs(per.sum() - 1) < 1e-9 and (per > 0).any()


def test_unit_bytes_are_the_rule_s_and_not_the_launch_s():
    got = wm.unit_bytes(CONFIG)
    rows_in, rows_out = wm.unit_row_counts(20000, True)
    assert (rows_in, rows_out) == (25875, 20250)
    row = 48 + 9 + wm.mean_value_bytes()
    assert got == pytest.approx((rows_in + rows_out) * row
                                + (rows_out * 10 + 7) // 8)
    assert 36.0 < wm.mean_value_bytes() < 37.0


def run_of(streams, modules=None):
    spans = [{"name": "tpu.compact_stream", "annotations": a}
             for a in streams]
    run = Run(config=CONFIG, traffic={}, seconds=51.0, t0=0.0, setup_s=1.0,
              ops=[], units=[], spans=spans,
              peaks={"hbm_bytes_per_s": 819e9})
    if modules is not None:
        run.trace = {"modules": modules}
    return run


def test_the_three_readers():
    a = {"shards": 8, "group_size": 8, "key_lanes": 12,
         "value_bytes": 7000, "value_bytes_padded": 200000}
    b = dict(a, key_lanes=6, value_bytes=3000)
    run = run_of([a, b], {"jit_one_shard_index": {"count": 2,
                                                  "seconds": 0.014}})
    assert wide_key_path_pct.read(run) == 50.0
    assert value_fill_pct.read(run) == pytest.approx(2.5)
    share = mixgraph_pipeline_roofline.read(run)
    assert share == pytest.approx(
        100 * 2 * 8 * wm.unit_bytes(CONFIG) / 819e9 / 0.014)
    assert 0 < share < 100
    # a program without the annotations (the parent's) gives nothing
    bare = run_of([{"shards": 8, "group_size": 8}])
    assert wide_key_path_pct.read(bare) is None
    assert value_fill_pct.read(bare) is None
    assert mixgraph_pipeline_roofline.read(bare) is None


def test_the_driver_asks_the_program(monkeypatch):
    assert rmg.device_takes(CONFIG) == ""
    # a program without the question (the parent's): nothing is built
    fake = types.ModuleType("rocksplicator_tpu.tpu.compaction_service")
    fake.device_mixed_key_bytes_max = lambda op: 24
    monkeypatch.setitem(sys.modules, fake.__name__, fake)
    assert "cannot say" in rmg.device_takes(CONFIG)
    fake.device_mixed_value_bytes_max = lambda op: 4096
    assert "keys up to 24 B" in rmg.device_takes(CONFIG)


def test_the_cell_is_declared_as_the_issue_says():
    (cell,) = [w for w in BENCH["workloads"]
               if w["name"] == "mixgraph_64x20k.refresh"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mixgraph_64x20k", "refresh_put_mixgraph", 1)
    declared = {m["name"] for g in ("end_to_end", "per_layer")
                for m in BENCH[g] if cell["name"] in m.get("workloads", ())}
    assert {"wide_key_path_pct", "value_fill_pct",
            "mixgraph_pipeline_roofline", "index_path_pct",
            "launch_fill_pct", "ingest_compact_ms",
            "device_idle_pct"} <= declared
    assert "compact_pipeline_roofline" not in declared
    for name in ("wide_key_path_pct", "value_fill_pct",
                 "mixgraph_pipeline_roofline"):
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert m["workloads"] == [cell["name"]]
