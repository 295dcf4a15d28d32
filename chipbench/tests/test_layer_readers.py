"""The per-layer readers that take the program's spans by name, each on
a small hand-written ``Run``: the number by hand, and ``None`` wherever
the run holds nothing for the reader (a program without the span, as a
parent commit is; no recording)."""

import importlib
import json
import os

import pytest

from chipbench.reduce import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def span(name, ms, **annotations):
    return {"name": name, "duration_ms": ms, "annotations": annotations,
            "start_ms": 0.0, "span_id": "s", "parent_id": None,
            "trace_id": "t"}


def run_of(spans=(), trace=None):
    return Run(config={}, traffic={}, seconds=1.0, t0=0.0, setup_s=1.0,
               ops=[], units=[], spans=list(spans), peaks={}, trace=trace)


def read(name, run):
    return importlib.import_module(f"chipbench.layers.{name}").read(run)


SPANS = [
    span("rpc.server.read", 2.0, method="read", queue_wait_ms=0.5),
    span("rpc.server.read", 4.0, method="read", queue_wait_ms=1.5),
    span("rpc.server.write", 90.0, method="write", queue_wait_ms=4.0),
    span("rpc.server.add_db", 7.0, method="add_db", queue_wait_ms=0.0),
    span("storage.flush", 30.0), span("storage.flush", 50.0),
    span("admin.compact.wait", 0.0, batch=1),
    span("admin.compact.wait", 300.0, batch=7),
    span("tpu.lanes.decode", 10.0, rows=25875),
    span("tpu.lanes.decode", 14.0, rows=25875),
    span("tpu.planar.write", 20.0, rows=20250),
    span("tpu.planar.write", 22.0, rows=20250),
    span("tpu.planar.write", 2.0, rows=100),  # a shard's second file
    span("tpu.h2d", 3.0), span("tpu.h2d", 5.0),
    span("tpu.readback", 9.0), span("tpu.readback", 12.0),
    # the names a program before these spans has: read by none of them
    span("rpc.server", 120.0, method="write", tail_kept=True),
    span("tpu.kernel", 11.0),
]

BY_HAND = {
    "rpc_queue_wait_ms": (0.5 + 1.5 + 4.0 + 0.0) / 4,
    "read_server_ms": 3.0,
    "write_server_ms": 90.0,
    "flush_ms": 40.0,
    "compact_queue_wait_ms": 150.0,
    "codec_ms_per_shard": (10.0 + 14.0 + 20.0 + 22.0 + 2.0) / 2,
    "h2d_ms": 4.0,
    "readback_ms": 10.5,
}


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_span_reader_by_hand(name):
    assert read(name, run_of(SPANS)) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_span_reader_finds_nothing_to_read(name):
    assert read(name, run_of()) is None
    # the parent's spans: a bare rpc.server root without queue_wait_ms,
    # tpu.kernel, the admin.ingest.* phases
    old = [span("rpc.server", 3.0, method="read"), span("tpu.kernel", 9.0),
           span("admin.ingest.compact", 500.0, batch=7)]
    assert read(name, run_of(old)) is None


@pytest.mark.parametrize("name,spans", [
    ("rpc_queue_wait_ms", [span("rpc.server.read", 1.0, method="read",
                                queue_wait_ms=0.0)]),
    ("compact_queue_wait_ms", [span("admin.compact.wait", 0.0, batch=1)]),
    ("codec_ms_per_shard", [span("tpu.planar.write", 5.0)]),  # no shard
])
def test_a_mean_of_zero_or_of_no_shard_is_left_out(name, spans):
    assert read(name, run_of(spans)) is None


def trace(unattributed=None, window_s=4.0, busy_s=0.06):
    gaps = [["admin.compact.wait", 1.2], ["rpc.server.read", 0.9]]
    if unattributed is not None:
        gaps.insert(1, ["unattributed", unattributed])
    return {"window_s": window_s, "busy_s": busy_s, "modules": {},
            "device_ops": [], "idle_gaps": gaps}


def test_idle_attributed_pct():
    assert read("idle_attributed_pct", run_of(SPANS, trace(0.394))) == \
        pytest.approx(90.0)
    # the ledger's line of PR 26: 1.326 s of 4.0 - 0.0619
    assert read("idle_attributed_pct", run_of(
        trace=trace(1.326, busy_s=0.0619))) == pytest.approx(66.33, abs=0.01)
    # unattributed is not among the ten: all of it has an owner
    assert read("idle_attributed_pct", run_of(trace=trace())) == 100.0


def test_idle_attributed_pct_finds_nothing_to_read():
    assert read("idle_attributed_pct", run_of(SPANS)) is None  # no recording
    assert read("idle_attributed_pct", run_of(
        trace=trace(0.1, window_s=1.0, busy_s=1.0))) is None  # never idle
    assert read("idle_attributed_pct", run_of(
        trace=trace(3.94, busy_s=0.06))) is None  # nothing has an owner


def test_every_new_reader_is_declared_for_the_cell():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name in list(BY_HAND) + ["idle_attributed_pct"]:
        assert declared[name]["workloads"] == ["counter_64x20k.refresh"]
        value = read(name, run_of(SPANS, trace(0.394)))
        assert isinstance(value, float) and value > 0
