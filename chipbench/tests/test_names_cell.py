"""The cell ``counter_names_64x15k.refresh``: counter_service under its
own key shape, names ``counter-<n>`` of 9 to 15 bytes in one shard.

- ``workload_names`` by hand: the six key lengths' counts at 1M names,
  the bulk file's bytewise order, a unit's row counts and key bytes;
- its two per-layer readers on hand-written runs: the number by hand,
  and ``None`` wherever the run holds nothing for the reader (a program
  that does not say ``key_widths``, as a parent commit is; no recording);
- the driver's question to the program: a program that cannot say how
  long a key of a mixed shard its device path takes, or says fewer bytes
  than the longest name, ends the run, nonzero, before a file is built;
- the cell's reference imports nothing of the program; the
  configuration's file holds the published shapes.

The parametrised rehearsal tests (``test_rehearsal.py``) run the cell
itself: sound, both controls, the three planted faults.
"""

import ast
import json
import os

import pytest

from chipbench import workload as wl
from chipbench import workload_names as wn
from chipbench.drivers import refresh_names
from chipbench.layers import mixed_key_path_pct, names_pipeline_roofline
from chipbench.tests.test_layer_readers import run_of, span

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "counter_names_64x15k.refresh"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "counter_names_64x15k.json")) as f:
        return json.load(f)


def stream(shards, **annotations):
    return span("tpu.compact_stream", 50.0, shards=shards, group_size=8,
                capacity=32768, **annotations)


def trace(modules):
    return {"window_s": 4.0, "busy_s": 0.1, "modules": modules,
            "device_ops": [], "idle_gaps": []}


# -- the reference, by hand -------------------------------------------------


def test_the_six_key_lengths_of_a_million_names():
    counts = {}
    for slot in range(64):
        for i in range(15625):
            n = len(wn.bulk_key(slot, i))
            counts[n] = counts.get(n, 0) + 1
    assert counts == {9: 10, 10: 90, 11: 900, 12: 9000, 13: 90000,
                      14: 900000}
    total = sum(n * c for n, c in counts.items())
    assert total == wn.name_bytes(0, 1_000_000) == 13_888_890
    assert round(total / 1e6, 3) == config()["key_bytes_mean"] == 13.889
    # every name once, shard = n mod 64
    assert wn.bulk_key(5, 0) == b"counter-5"
    assert wn.bulk_key(63, 15624) == b"counter-999999"
    assert wn.live_key(0, 0) == b"counter-1000000"
    assert {len(wn.live_key(s, i)) for s in (0, 63) for i in (0, 194)} == {15}
    assert wn.key_bytes_max(15625, True) == 15
    assert wn.key_bytes_max(15625, False) == 14


def test_every_slot_holds_six_lengths_and_the_rehearsal_four():
    for slot in (0, 17, 63):
        assert {len(wn.bulk_key(slot, i)) for i in range(15625)} == {
            9, 10, 11, 12, 13, 14} - ({9} if slot > 9 else set())
    cfg = config()
    for slot in range(cfg["rehearse"]["slots"]):
        lens = {len(wn.bulk_key(slot, i))
                for i in range(cfg["rehearse"]["rows_per_slot"])}
        assert len(lens) >= 4


def test_bulk_rows_are_in_bytewise_order_with_workload_pys_values():
    rows = wn.bulk_rows(7, 3, 2000)
    keys = [k for k, _v in rows]
    assert keys == sorted(keys) and len(set(keys)) == 2000
    assert keys[:3] == [b"counter-100035", b"counter-100099",
                        b"counter-100163"]
    # 1027 lies after 102659 (its fourth digit decides), 3 after both,
    # and the names that 3 is a prefix of right behind it
    at = keys.index(b"counter-3")
    assert keys.index(b"counter-102659") < keys.index(b"counter-1027") < at
    assert keys[at + 1].startswith(b"counter-3") and len(keys[at + 1]) > 9
    # numeric order is another order
    assert keys != sorted(keys, key=lambda k: int(k[8:]))
    # counter i of the slot has workload.bulk_rows' value
    theirs = dict((wn.bulk_key(3, i), v) for i, (_k, v) in enumerate(
        wl.bulk_rows(7, 3, 2000)))
    assert dict(rows) == theirs


def test_unit_row_counts_and_key_bytes_by_hand():
    assert wn.unit_row_counts is wl.unit_row_counts
    assert wn.SlotModel is wl.SlotModel
    assert wn.unit_row_counts(15625, True) == (20214, 15820)
    ops = wn.preload_ops(11, 9, 15625)
    assert len(ops) == 4589 == 20214 - 15625  # 9 write RPCs of 512
    assert -(-len(ops) // 512) == 9
    # a slot's bulk keys: a 64th of the million names' bytes; 195
    # live-only counters of 15 B; 3,906 MERGEs on bulk keys at the mean;
    # 683 operations on live-only counters
    bulk = 13_888_890 / 64
    keys_in, keys_out = wn.unit_key_bytes(15625, True)
    assert keys_out == pytest.approx(bulk + 195 * 15)
    assert keys_in == pytest.approx(
        bulk + 3906 * bulk / 15625 + 683 * 15)
    assert wn.unit_key_bytes(15625, False) == (bulk, bulk)
    # the exact sum of one seed's unit is within a thousandth of it
    exact_in = (sum(len(k) for k, _v in wn.bulk_rows(11, 9, 15625))
                + sum(len(k) for _t, k, _v in ops))
    assert exact_in == pytest.approx(keys_in, rel=1e-3)
    model = wn.slot_model(11, 9, 15625, True)
    assert len(model) == 15820
    assert sum(len(k) for k in model._m) == pytest.approx(keys_out, rel=1e-3)


def test_the_read_back_holds_a_prefix_absent_beside_the_longer_name():
    model = wn.slot_model(5, 10, 256, True)
    probes = wn.probe_keys(5, 10, 256, 256, True)
    assert len(probes) == len(set(probes))
    absent = [k for k in probes if model.get(k) is None]
    present = set(probes) - set(absent)
    # a written name cut by a digit, and one with a digit more, read absent
    assert any(k + b"0" in absent or k[:-1] in absent for k in present)
    assert sum(k.startswith(b"counter-20") and len(k) == 15
               for k in absent) == 8  # the absent names
    assert all(wn.live_key(10, i) in present for i in range(3))


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(ROOT, "chipbench", "workload_names.py")
    tree = ast.parse(open(path).read())
    modules = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            modules |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            modules.add(node.module)
    assert modules == {"__future__", "typing", "chipbench.workload"}


def test_the_configuration_holds_the_published_shapes():
    cfg = config()
    assert cfg["slots"] * cfg["rows_per_slot"] == 1_000_000
    assert cfg["slots"] == wn.SHARDS == 64
    assert cfg["key_bytes"] == "9-15" and cfg["value_bytes"] == 8
    assert cfg["options"] == json.load(open(os.path.join(
        ROOT, "chipbench", "configs", "counter_64x20k.json")))["options"]
    assert cfg["reduced"] == {} and len(cfg["assumed"]) >= 3
    assert len(cfg["guarantees"]) == 3
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "counter_names_64x15k"]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    (cell,) = [c for c in BENCH["workloads"] if c["name"] == CELL]
    assert cell["chips"] == 1 and BENCH["workloads"][-1] == cell


# -- the readers, by hand ---------------------------------------------------


def test_mixed_key_path_pct_by_hand():
    run = run_of([stream(8, key_widths="mixed", key_bytes_max=15),
                  stream(8, key_widths="mixed", key_bytes_max=15)])
    assert mixed_key_path_pct.read(run) == 100.0
    # a straggler's launch of one uniform shard among mixed dispatches
    run = run_of([stream(8, key_widths="mixed"), stream(2,
                                                        key_widths="uniform"),
                  span("tpu.h2d", 3.0)])
    assert mixed_key_path_pct.read(run) == 80.0
    assert mixed_key_path_pct.read(
        run_of([stream(8, key_widths="uniform")])) == 0.0


def test_names_pipeline_roofline_by_hand():
    cfg = config()
    bulk = 13_888_890 / 64
    keys = (bulk + 3906 * bulk / 15625 + 683 * 15) + (bulk + 195 * 15)
    unit = keys + (20214 + 15820) * (8 + 1 + 8) + (15820 * 10 + 7) // 8
    assert names_pipeline_roofline.unit_bytes(cfg) == pytest.approx(unit)
    assert 1.12e6 < unit < 1.14e6
    # seven launches of 8 real shards in the slice, 9.8 ms each
    run = run_of([stream(8, key_widths="mixed")] * 7,
                 trace({"jit_one_shard": {"count": 7, "seconds": 0.0686},
                        "jit_bloom_build_tpu": {"count": 50,
                                                "seconds": 0.015}}))
    run.config, run.peaks = cfg, {"hbm_bytes_per_s": 819e9}
    by_hand = 100.0 * (7 * 8 * unit / 819e9) / 0.0686
    assert names_pipeline_roofline.read(run) == pytest.approx(by_hand)
    assert 0.10 < by_hand < 0.13  # bytes-bound, far under the roof
    # a short group counts its real shards only
    run.spans = [stream(8), stream(3)]
    assert names_pipeline_roofline.read(run) == pytest.approx(
        100.0 * (7 * 11 / 2 * unit / 819e9) / 0.0686)


@pytest.mark.parametrize("reader", [mixed_key_path_pct,
                                    names_pipeline_roofline],
                         ids=lambda r: r.__name__.rsplit(".", 1)[1])
def test_reader_finds_nothing_to_read(reader):
    assert reader.read(run_of()) is None
    modules = {"jit_one_shard": {"count": 2, "seconds": 0.018}}
    # the parent's spans say no key_widths; no recording of the device
    old = [stream(8, dbs=8), span("tpu.lanes.decode", 13.0, rows=20214)]
    run = run_of(old)
    run.config, run.peaks = config(), {"hbm_bytes_per_s": 819e9}
    assert reader.read(run) is None
    assert mixed_key_path_pct.read(run_of(old, trace(modules))) is None
    said = run_of([stream(8, key_widths="mixed")], trace({}))
    said.config, said.peaks = config(), {"hbm_bytes_per_s": 819e9}
    assert names_pipeline_roofline.read(said) is None


def test_new_readers_are_declared_for_the_new_cell_alone():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in (("mixed_key_path_pct", "compaction seam"),
                        ("names_pipeline_roofline", "kernels")):
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == layer
        assert declared[name]["moves"] == "refresh_rows_per_s"
    for name in ("compact_pipeline_roofline", "range_pipeline_roofline"):
        assert CELL not in declared[name]["workloads"]
    for name in ("ingest_compact_ms", "launch_fill_pct", "device_idle_pct"):
        assert CELL in declared[name]["workloads"]
    for m in BENCH["end_to_end"]:
        assert "workloads" not in m or CELL in m["workloads"]


# -- the driver's question --------------------------------------------------


def test_prepare_asks_the_program_and_builds_nothing_where_it_says_no(
        tmp_path, monkeypatch):
    from rocksplicator_tpu.tpu import compaction_service as cs

    assert refresh_names.device_takes(config()) == ""
    monkeypatch.setattr(cs, "device_mixed_key_bytes_max", lambda op: 12)
    assert "up to 12 B a key" in refresh_names.device_takes(config())
    assert "longest name has 15 B" in refresh_names.device_takes(config())
    driver = refresh_names.make(None, str(tmp_path), config(), {}, 1, None)
    with pytest.raises(SystemExit) as e:
        driver.prepare()
    assert e.value.code not in (0, None) and driver.child is None
    assert "nothing was built" in str(e.value.code)
    assert os.listdir(tmp_path) == []
    # a program from before the function (the parent commit) cannot say
    monkeypatch.delattr(cs, "device_mixed_key_bytes_max")
    assert "cannot say" in refresh_names.device_takes(config())
    with pytest.raises(SystemExit) as e:
        refresh_names.make(None, str(tmp_path), config(), {}, 1,
                           None).prepare()
    assert "nothing was built" in str(e.value.code)
    assert os.listdir(tmp_path) == []
