"""The cell ``counter_32x64k.refresh``: shards larger than one launch of
the device path, cut by key range into places of the launch.

- its three per-layer readers on hand-written runs: the number by hand,
  and ``None`` wherever the run holds nothing for the reader (a program
  that does not say ``dbs`` or has no ``tpu.range_cut``, as a parent
  commit is; no recording);
- the driver's question to the program: a program that cannot say how
  large a shard its device path takes, or says fewer rows than a unit
  brings, ends the run, nonzero, before a file is built;
- the cell as the driver runs it (``run.py`` in a process of its own, at
  the configuration's rehearsal size, on the CPU): a sound run exits 3
  and WAS CUT (``places_per_shard`` at least 2, a ``range_cut_ms``);
- the cut broken underneath (rows placed by position, so that a key's
  entry stack lies in two places): ``correct`` comes out false.
"""

import json
import os
import subprocess
import sys

import pytest

from chipbench import work_model
from chipbench.drivers import refresh_ranges
from chipbench.layers import (places_per_shard, range_cut_ms,
                              range_pipeline_roofline)
from chipbench.tests.test_layer_readers import run_of, span
from chipbench.tests.test_rehearsal import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "counter_32x64k.refresh"
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def config():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "counter_32x64k.json")) as f:
        return json.load(f)


def stream(shards, **annotations):
    return span("tpu.compact_stream", 50.0, shards=shards, group_size=8,
                capacity=32768, **annotations)


# -- the readers, by hand ---------------------------------------------------


def test_places_per_shard_by_hand():
    # a full dispatch of 8 shards in 3 places each, and a straggler's
    run = run_of([stream(24, dbs=8), stream(3, dbs=1), span("tpu.h2d", 3.0)])
    assert places_per_shard.read(run) == 27 / 9
    # no shard is cut
    assert places_per_shard.read(run_of([stream(8, dbs=8)])) == 1.0
    # two of eight shards were over a place
    assert places_per_shard.read(run_of([stream(12, dbs=8)])) == 1.5


def test_range_cut_ms_by_hand():
    run = run_of([span("tpu.range_cut", 9.0, rows=84786, places=3,
                       capacity=32768),
                  span("tpu.range_cut", 12.0, rows=84786, places=3,
                       capacity=32768),
                  span("tpu.lanes.decode", 40.0, rows=84786)])
    assert range_cut_ms.read(run) == 10.5


def trace(modules):
    return {"window_s": 4.0, "busy_s": 0.1, "modules": modules,
            "device_ops": [], "idle_gaps": []}


def test_range_pipeline_roofline_by_hand():
    cfg = config()
    # a unit's least bytes: every row in and out once, and the filter
    rows_in, rows_out = 84786, 66355
    unit = (rows_in + rows_out) * (16 + 8 + 1 + 8) + (rows_out * 10 + 7) // 8
    assert work_model.unit_rows(cfg) == (rows_in, rows_out)
    assert work_model.unit_bytes(cfg) == unit
    # six launches in the slice, 9 ms each: two dispatches of 8 shards
    # in 24 places (3 groups each); 8 / 3 whole shards a launch
    run = run_of([stream(24, dbs=8), stream(24, dbs=8)],
                 trace({"jit_one_shard": {"count": 6, "seconds": 0.054},
                        "jit_bloom_build_tpu": {"count": 9,
                                                "seconds": 0.003}}))
    run.config, run.peaks = cfg, {"hbm_bytes_per_s": 819e9}
    by_hand = 100.0 * (6 * 16 / 6 * unit / 819e9) / 0.054
    assert range_pipeline_roofline.read(run) == pytest.approx(by_hand)
    assert 0 < by_hand < 1.0  # bytes-bound, far under the roof
    # the accepted reader counts a whole unit a PLACE: three times high
    from chipbench.layers import compact_pipeline_roofline
    assert compact_pipeline_roofline.read(run) == pytest.approx(3 * by_hand)


@pytest.mark.parametrize("reader", [places_per_shard, range_cut_ms,
                                    range_pipeline_roofline],
                         ids=lambda r: r.__name__.rsplit(".", 1)[1])
def test_reader_finds_nothing_to_read(reader):
    assert reader.read(run_of()) is None
    # the parent's spans: shards and group_size, no dbs, no tpu.range_cut
    old = [stream(8), stream(1), span("tpu.lanes.decode", 13.0, rows=25875)]
    modules = {"jit_one_shard": {"count": 2, "seconds": 0.018}}
    for run in (run_of(old), run_of(old, trace(modules))):
        run.config, run.peaks = config(), {"hbm_bytes_per_s": 819e9}
        assert reader.read(run) is None
    # spans that say it, and no recording of the device
    said = [stream(24, dbs=8)]
    assert range_pipeline_roofline.read(run_of(said)) is None
    assert range_pipeline_roofline.read(run_of(said, trace({}))) is None


def test_new_readers_are_declared_for_the_new_cell_alone():
    declared = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in (("places_per_shard", "compaction seam"),
                        ("range_cut_ms", "compaction seam"),
                        ("range_pipeline_roofline", "kernels")):
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == layer
        assert declared[name]["moves"] == "refresh_rows_per_s"
    assert CELL not in declared["compact_pipeline_roofline"]["workloads"]
    for name in ("ingest_compact_ms", "launch_fill_pct", "device_idle_pct"):
        assert declared[name]["workloads"][-1] == CELL


# -- the driver's question --------------------------------------------------


def test_prepare_asks_the_program_and_builds_nothing_where_it_says_no(
        tmp_path, monkeypatch):
    from rocksplicator_tpu.tpu import compaction_service as cs

    assert refresh_ranges.device_takes(config()) == ""
    monkeypatch.setattr(cs, "device_shard_rows_max", lambda op: 32768)
    assert "up to 32768 rows" in refresh_ranges.device_takes(config())
    assert "brings 84786" in refresh_ranges.device_takes(config())
    # the counter cell's unit is one place: such a program takes it
    small = dict(config(), rows_per_slot=20000)
    assert refresh_ranges.device_takes(small) == ""
    driver = refresh_ranges.make(None, str(tmp_path), config(), {}, 1, None)
    with pytest.raises(SystemExit) as e:
        driver.prepare()
    assert e.value.code not in (0, None) and driver.child is None
    assert "nothing was built" in str(e.value.code)
    assert os.listdir(tmp_path) == []
    # a program from before the function (the parent commit) cannot say
    monkeypatch.delattr(cs, "device_shard_rows_max")
    assert "cannot say" in refresh_ranges.device_takes(config())


# -- the cell as the driver runs it -----------------------------------------


def test_sound_rehearsal_exits_3_and_was_cut():
    assert work_model.unit_rows(dict(config(), **config()["rehearse"]))[
        0] > 32768  # every unit of the rehearsal is over one place
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELL, "--seed",
         str(2**31 + 33), "--seconds", "2", "--rehearse", "--trace", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 3, out.stderr[-2000:]
    (line,) = [x for x in out.stdout.splitlines() if "REHEARSAL ONLY" in x]
    result = json.loads(line.split("not a chip run: ", 1)[1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["window_compilations"] == 0
    assert result["compared"]["host_fallbacks"]["value"] == 0
    metrics = result["metrics"]
    assert metrics["places_per_shard"]["value"] >= 2.0
    assert metrics["range_cut_ms"]["value"] > 0
    assert metrics["launch_fill_pct"]["value"] > 0
    assert "(8, 32768)" in out.stdout  # the one launch shape of the cell


def test_a_cut_inside_a_key_group_is_not_correct(monkeypatch):
    """The placement broken underneath: rows go to places by their
    position in the runs, not by key, so a counter's increments and the
    bulk PUT that shadows them lie in two places."""
    from rocksplicator_tpu.storage import native_compaction as nc
    from rocksplicator_tpu.tpu import compaction_service as cs

    def by_rows(parts, bounds, klen, value_rows=None):
        total = sum(p["key_len"].shape[0] for p in parts)
        lanes = nc.concat_lanes(parts, total)
        edges = [i * total // (len(bounds) + 1)
                 for i in range(len(bounds) + 2)]
        return [{f: a[lo:hi] for f, a in lanes.items()}
                for lo, hi in zip(edges, edges[1:])]

    monkeypatch.setattr(cs, "slice_lanes", by_rows)
    try:
        result, ok = rehearse(CELL)
    except RuntimeError as e:  # version 0 itself did not load
        assert "did not load" in str(e)
        return
    assert not ok and result["correct"] is False
    assert result["compared"]["mismatched_answers"]["value"] > 0
