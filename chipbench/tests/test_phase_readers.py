"""The eleven readers of a served RPC's phases (PR 37), each on a small
hand-written ``Run``: the mean by hand; ``None`` on the spans a program
without phases records (the parent's roots carry no such annotation);
and every new entry of BENCHMARK.json as declared."""

import importlib
import json
import os

import pytest

from chipbench.reduce import Run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [c["name"] for c in BENCH["workloads"]]


def span(name, ms, **annotations):
    return {"name": name, "duration_ms": ms, "annotations": annotations,
            "start_ms": 0.0, "span_id": "s", "parent_id": None,
            "trace_id": "t"}


def run_of(spans=()):
    return Run(config={}, traffic={}, seconds=1.0, t0=0.0, setup_s=1.0,
               ops=[], units=[], spans=list(spans), peaks={}, trace=None)


def read(name, run):
    return importlib.import_module(f"chipbench.layers.{name}").read(run)


def phases(**ms):
    return {k + "_ms": v for k, v in ms.items()}


SPANS = [
    span("rpc.server.write", 14.0, method="write", queue_wait_ms=1.0,
         **phases(hop_in=3.0, exec=6.0, exec_cpu=2.5, parse=3.0, commit=2.0,
                  hop_out=2.0, ack_wait=1.0, reply=1.5)),
    span("rpc.server.write", 10.0, method="write", queue_wait_ms=0.5,
         **phases(hop_in=1.0, exec=4.0, exec_cpu=4.5, parse=2.0, commit=1.5,
                  hop_out=4.0, ack_wait=0.5, reply=0.5)),
    # a write refused before it hopped: a reply, and nothing of the hop
    span("rpc.server.write", 0.4, method="write", queue_wait_ms=0.1,
         error_code="WRITE_WINDOW_FULL", **phases(reply=0.25)),
    span("rpc.server.read", 5.0, method="read", queue_wait_ms=1.5,
         **phases(hop_in=2.0, exec=0.5, exec_cpu=0.25, hop_out=1.5,
                  reply=0.75)),
    span("rpc.server.read", 3.0, method="read", queue_wait_ms=0.5,
         **phases(hop_in=1.0, exec=1.5, exec_cpu=0.5, hop_out=0.5,
                  reply=0.25)),
    # the phases as snapshot() also yields them: children, read by none
    span("rpc.server.write:hop_in", 3.0), span("rpc.server.write:exec", 6.0),
    span("rpc.server.read:reply", 0.75),
    # other methods' roots carry phases too: read by none of the eleven
    span("rpc.server.add_db", 30.0, method="add_db", queue_wait_ms=0.0,
         **phases(hop_in=2.0, exec=25.0, exec_cpu=9.0, hop_out=2.0,
                  reply=0.5, **{"db.open": 14.0, "db.register": 10.0})),
]

BY_HAND = {
    "write_hop_in_ms": (3.0 + 1.0) / 2,
    "write_hop_out_ms": (2.0 + 4.0) / 2,
    "write_reply_ms": (1.5 + 0.5 + 0.25) / 3,
    "write_exec_ms": (6.0 + 4.0) / 2,
    "write_exec_off_cpu_ms": (3.5 + 0.0) / 2,  # 4.0 - 4.5 reads 0
    "write_parse_ms": (3.0 + 2.0) / 2,
    "read_hop_in_ms": (2.0 + 1.0) / 2,
    "read_hop_out_ms": (1.5 + 0.5) / 2,
    "read_reply_ms": (0.75 + 0.25) / 2,
    "read_exec_ms": (0.5 + 1.5) / 2,
    "read_exec_off_cpu_ms": (0.25 + 1.0) / 2,
}

# the roots a program without phases records (the parent commit's)
PARENT_SPANS = [
    span("rpc.server.write", 13.9, method="write", queue_wait_ms=1.3),
    span("rpc.server.read", 4.8, method="read", queue_wait_ms=1.2),
    span("rpc.server.add_db", 33.0, method="add_db", queue_wait_ms=0.0),
    span("repl.write", 2.0, db="seg00001", seq=7, bytes=12800),
]


def test_the_issue_names_eleven():
    assert len(BY_HAND) == 11


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_phase_reader_by_hand(name):
    assert read(name, run_of(SPANS)) == pytest.approx(BY_HAND[name])


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_phase_reader_is_silent_on_the_parents_spans(name):
    assert read(name, run_of()) is None
    assert read(name, run_of(PARENT_SPANS)) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_a_mean_of_zero_is_left_out(name):
    method = name.split("_")[0]
    zero = span("rpc.server." + method, 1.0, method=method, **phases(
        hop_in=0.0, exec=0.0, exec_cpu=0.0, parse=0.0, hop_out=0.0,
        reply=0.0))
    assert read(name, run_of([zero])) is None


@pytest.mark.parametrize("name", sorted(BY_HAND))
def test_phase_reader_is_declared_as_the_issue_says(name):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == "program_span"
    assert entry["workloads"] == CELLS  # all four cells
    assert entry["moves"] == name.split("_")[0] + "_p95_ms"
    assert entry["layer"] == (
        "engine" if name.endswith("_parse_ms")
        else "data plane" if "_exec_" in name else "wire")
    # a layer BENCHMARK.json already names, letter for letter
    older = BENCH["per_layer"][:-11]
    assert entry not in older
    assert entry["layer"] in {m["layer"] for m in older}


def test_new_entries_are_the_last_eleven_and_nothing_else_moved():
    assert sorted(m["name"] for m in BENCH["per_layer"][-11:]) == sorted(
        BY_HAND)
    assert BENCH["per_layer"][-12]["name"] == "names_pipeline_roofline"
