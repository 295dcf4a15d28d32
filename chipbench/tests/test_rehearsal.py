"""The rest of a run, with the look for a chip skipped: every cell at
its rehearsal size (12 slots x 256 rows) on the CPU, the node in this
process and the client in its child.

- sound: zero mismatches, ``correct`` true, a well-formed result object
  on a fake device record;
- the controls (the reference in narrower arithmetic in the program's
  place: everything in 32 bits; 64-bit adds in 32-bit lanes with the
  carry lost): ``correct`` false;
- the timed path broken underneath, once for each fault a cell can have:
  an answer altered where it is produced; a load acknowledged with its
  state left unchanged; half of a write batch left out. Each time
  ``correct`` comes out false.
"""

import argparse
import json
import os

import pytest

from chipbench import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
FAKE_DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CELLS = [c["name"] for c in BENCH["workloads"]]


def rehearse(cell, seed=5, trace=0, control=None, seconds=1.5):
    args = argparse.Namespace(
        workload=cell, seed=seed, seconds=seconds, trace=trace,
        rehearse=True, control=control, dump_trace=None)
    return harness.run_cell(args, BENCH, dict(FAKE_DEVICE), on_chip=False)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_sound_run_is_correct_and_well_formed(cell, trace):
    result, ok = rehearse(cell, seed=2**31 + 11, trace=trace)
    assert ok and result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compared"
    assert result["compared"]["mismatched_answers"] == {"value": 0,
                                                        "limit": 0}
    assert result["compared"]["device_dispatches"]["value"] >= 1
    assert result["window_compilations"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        result["device"])
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in BENCH[group]
                if "workloads" not in m or cell in m["workloads"]}
    assert result["metrics"], "a run reports at least one metric"
    for name, m in result["metrics"].items():
        assert m["unit"] == declared[name]["unit"]
        assert isinstance(m["value"], float) and m["value"] > 0
    if not trace:
        assert set(result["metrics"]) == set(declared)
    else:  # no device in a CPU recording: the device's readers stay silent
        assert {"ingest_compact_ms", "launch_fill_pct"} <= set(
            result["metrics"])
        assert "compact_pipeline_roofline" not in result["metrics"]
    json.dumps(result)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("control", harness.CONTROLS)
def test_control_in_narrower_arithmetic_is_not_correct(cell, control):
    result, ok = rehearse(cell, control=control)
    assert not ok and result["correct"] is False
    assert result["compared"]["mismatched_answers"]["value"] > 0


def alter_an_answer(monkeypatch):
    from rocksplicator_tpu.storage.engine import DB

    real, real_get = DB.multi_get, DB.get

    def flip(v):  # one bit of a value
        return bytes([v[0] ^ 1]) + bytes(v[1:])

    def multi_get(self, keys):
        values = real(self, keys)
        for i, v in enumerate(values):
            if v is not None:  # one value of the reply
                values[i] = flip(v)
                break
        return values

    def get(self, key, *a, **kw):
        v = real_get(self, key, *a, **kw)
        return v if v is None else flip(v)

    monkeypatch.setattr(DB, "multi_get", multi_get)
    monkeypatch.setattr(DB, "get", get)


def acknowledge_a_load_without_loading(monkeypatch):
    from rocksplicator_tpu.storage.engine import DB

    monkeypatch.setattr(DB, "ingest_external_file",
                        lambda self, *a, **kw: None)


def drop_half_of_each_write_batch(monkeypatch):
    from rocksplicator_tpu.replication import replicated_db

    real = replicated_db.decode_batch

    def decode_batch(raw):
        batch = real(raw)
        batch._ops = batch._ops[: len(batch._ops) // 2]
        return batch

    monkeypatch.setattr(replicated_db, "decode_batch", decode_batch)


@pytest.mark.parametrize("cell,fault", [
    (cell, fault) for cell in CELLS for fault in (
        alter_an_answer, acknowledge_a_load_without_loading,
        drop_half_of_each_write_batch)
    # only a configuration with live counters sends write batches
    if fault is not drop_half_of_each_write_batch or "counter" in cell],
    ids=lambda p: getattr(p, "__name__", p))
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    try:
        result, ok = rehearse(cell)
    except RuntimeError as e:  # version 0 itself did not load
        assert "did not load" in str(e)
        return
    assert not ok and result["correct"] is False
    bad = result["compared"]
    assert bad["mismatched_answers"]["value"] + bad["failed_rpcs"]["value"] > 0
