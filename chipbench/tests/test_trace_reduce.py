"""trace_reduce.reduce on recordings kept as plain data: a small one
made by hand, every number of which is worked out below, and a cut-down
recording of the chip (see the fixture's own note)."""

import json
import os

import pytest

from chipbench import trace_reduce as tr

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def load(name):
    with open(os.path.join(FIXTURES, name)) as f:
        return json.load(f)


def test_small_recording_by_hand():
    rec = load("recording_small.json")
    assert tr.clock_mark_ns(rec) == 0.0
    spans = [("admin.ingest.compact", 0.0, 5000.0, "a", None),
             ("tpu.unpack", 4000.0, 5000.0, "b", "a"),  # child of the first
             ("repl.read", 0.0, 500.0, "c", None)]      # another request
    out = tr.reduce(rec, (0.0, 10000.0), spans)
    assert out["window_s"] == pytest.approx(10e-6)
    # ops: [1000,2000) u [1500,3500) = 2500; [6000,7000) = 1000;
    # [9500,10500) clipped at the slice's end = 500
    assert out["busy_s"] == pytest.approx(4e-6)
    # idle share = 1 - 4000 / 10000
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(0.6)
    # module events that start inside the slice, whole durations
    assert out["modules"] == {
        "jit_one_shard(123)": {"count": 1, "seconds": pytest.approx(3e-6)},
        "jit_bloom_build_tpu(5)": {"count": 1,
                                   "seconds": pytest.approx(1e-6)}}
    ops = dict(out["device_ops"])
    assert ops == {"fusion": pytest.approx(2e-6),
                   "sort": pytest.approx(2e-6),
                   "copy": pytest.approx(0.5e-6)}
    # gaps [0,1000) [3500,6000) [7000,9500). [0,500): two requests are
    # open, half each; [500,1000) and [3500,4000): admin.ingest.compact
    # alone; [4000,5000): its child tpu.unpack is open, so the child takes
    # it; nothing is open in [5000,6000) and [7000,9500)
    gaps = dict(out["idle_gaps"])
    assert gaps == {"unattributed": pytest.approx(3.5e-6),
                    "admin.ingest.compact": pytest.approx(1.25e-6),
                    "tpu.unpack": pytest.approx(1e-6),
                    "repl.read": pytest.approx(0.25e-6)}
    assert sum(gaps.values()) == pytest.approx(
        out["window_s"] - out["busy_s"])


def test_a_recording_without_a_device_is_an_error():
    rec = {"planes": [load("recording_small.json")["planes"][0]]}
    with pytest.raises(ValueError):
        tr.reduce(rec, (0.0, 10.0))


def test_op_group_strips_the_instance_number_and_the_hlo_text():
    assert tr.op_group("fusion.123") == "fusion"
    assert tr.op_group("sort") == "sort"
    assert tr.op_group(
        "%sort.11 = (u32[4096]{0:T(1024)S(1)}, u32[4096]{0}) sort(u32[4096] "
        "%fusion.6), dimensions={0}, to_apply=%region_0") == "sort"
    assert tr.op_group("%copy-start.3 = (u32[8]) copy-start(%x)") \
        == "copy-start"


def test_cut_recording_of_the_chip():
    """One launch of the batched (8, 32768) pipeline as the v5e recorded
    it (the fixture's note says how it was cut). The expected numbers
    were worked out apart from the reducer, by counting open events
    along the sorted start and end times: 1,688 ops cover 9,096,057 ns
    of the 17,098,670 ns cut, inside one module event of 9,098,670 ns."""
    rec = load("recording_v5e_cut.json")
    out = tr.reduce(rec, (0.0, 17098670.0))
    assert out["busy_s"] == pytest.approx(9096057e-9, rel=1e-9)
    assert 1 - out["busy_s"] / out["window_s"] == pytest.approx(
        0.4680254663, rel=1e-9)
    assert out["modules"] == {"jit_one_shard(10127807663228427872)": {
        "count": 1, "seconds": pytest.approx(9098670e-9)}}
    assert dict(out["idle_gaps"]) == {
        "unattributed": pytest.approx(out["window_s"] - out["busy_s"])}
    groups = dict(out["device_ops"])
    assert {"fusion", "sort", "slice", "pad"} <= set(groups)
    # ops of one launch run one after another: their sum is the busy time
    assert sum(groups.values()) <= out["busy_s"] * 1.0001


def test_roofline_reader_on_the_cut_recording():
    """compact_pipeline_roofline by hand: one launch of 9.09867 ms; the
    window's spans say 4 real shards a launched group; a counter unit
    of 16,384 bulk rows needs (21,193 + 16,588) x 33 + 20,735 = 1,267,508
    bytes: 4 x 1,267,508 / 819e9 = 6.190 us of least time, 0.0680 % of
    the launch."""
    from chipbench.layers import compact_pipeline_roofline as reader
    from chipbench.reduce import Run

    config = {"rows_per_slot": 16384, "live_counters": True,
              "key_bytes": 16, "value_bytes": 8,
              "options": {"bits_per_key": 10}}
    spans = [{"name": "tpu.compact_stream", "duration_ms": 1.0,
              "annotations": {"shards": s, "group_size": 8,
                              "capacity": 32768}} for s in (1, 7)]
    run = Run(config=config, traffic={}, seconds=10.0, t0=0.0,
              setup_s=1.0, ops=[], units=[], spans=spans,
              peaks={"hbm_bytes_per_s": 819e9},
              trace=tr.reduce(load("recording_v5e_cut.json"),
                              (0.0, 17098670.0)))
    assert reader.read(run) == pytest.approx(
        100 * 4 * 1267508 / 819e9 / 9098670e-9, rel=1e-9)
    assert reader.read(run) == pytest.approx(0.0680, abs=1e-4)
    run.trace = None
    assert reader.read(run) is None  # nothing to read: silent, never 0
