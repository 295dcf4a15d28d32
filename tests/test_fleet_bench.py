"""Fleet-density macro-bench harness (round 22): tier-1 smoke.

Two subprocess runs of ``benchmarks.fleet_bench`` at a minimal shape,
asserting the ARTIFACT SHAPES the committed fleet artifacts carry:

- the scripted timeline (baseline, hot-set shift, node SIGKILL +
  restart, live drain, cooldown — >= 4 phases including the three
  disruptive ones) with per-phase SLO gate records and a
  `/cluster_stats` snapshot per phase, zero gate failures, zero
  acked-write loss across the drain and the whole-timeline readback;
- the mux on/off A/B: both arms completed, the mux-on arm actually
  muxed (mux_pulls > 0, zero legacy fallbacks), the mux-off arm
  didn't, and the idle-window frames/parked reduction held at the
  shape-appropriate factor.

The full-size shapes (10x100 timeline, 8x64 A/B at the 5x gate) run
via ``make fleet-bench``; ``make fleet-smoke`` is the mid-size manual
smoke. This test keeps the harness itself honest in tier-1.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TIMELINE_PHASES = "baseline,hot_shift,node_kill,drain,cooldown"


def _run(tmp_path, name, argv, timeout):
    out = tmp_path / name
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.fleet_bench",
         *argv, "--out", str(out)],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=timeout)
    assert proc.returncode == 0, (
        f"fleet_bench exited {proc.returncode}\n"
        f"stdout tail: {proc.stdout[-3000:]}\n"
        f"stderr tail: {proc.stderr[-3000:]}")
    with open(out) as f:
        return json.load(f)


def test_fleet_timeline_artifact_shape(tmp_path):
    art = _run(
        tmp_path, "fleet_timeline.json",
        ["--nodes", "3", "--shards", "6", "--preload_keys", "30",
         "--rate", "100", "--duration", "1.5",
         "--phases", TIMELINE_PHASES],
        timeout=420)

    assert art["bench"] == "fleet_bench"
    assert art["topology"] == {
        "nodes": 3, "shards": 6, "replication_factor": 3,
        "placement": art["topology"]["placement"],
        "pull_mux": art["topology"]["pull_mux"],
    }
    assert art["failures"] == [], art["failures"]
    assert "host_calibration" in art

    phases = art["phases"]
    names = [p["phase"] for p in phases]
    assert names == TIMELINE_PHASES.split(",")
    assert len(names) >= 4
    for rec in phases:
        # every phase carries its SLO verdicts and a /cluster_stats
        # snapshot taken right after it
        assert "slo" in rec or "curve" in rec, rec["phase"]
        snap = rec["cluster_stats"]
        assert snap["shards_reporting"] == 6
        assert snap["endpoints"] == 3
        assert "fleet_latency_ms" in snap
        if "summary" in rec:
            assert rec["summary"]["value_mismatches"] == 0

    kill = next(p for p in phases if p["phase"] == "node_kill")
    assert kill["slo"]["recovery_sec"] > 0

    drain = next(p for p in phases if p["phase"] == "drain")
    assert drain["drain"]["shards_moved"] == 2  # node 2 led 6/3 shards
    rb = drain["slo"]["acked_readback"]
    assert rb["lost"] == 0 and rb["sampled"] > 0

    cool = next(p for p in phases if p["phase"] == "cooldown")
    assert cool["slo"]["convergence_sec"] is not None
    assert cool["slo"]["acked_readback"]["lost"] == 0

    # the final full /cluster_stats document (per-shard map included)
    final = art["final_cluster_stats"]
    assert len(final["per_shard"]) == 6
    assert final["replicas_scraped"] == 3


def test_fleet_mux_ab_artifact_shape(tmp_path):
    # 3 nodes / 6 shards: each node follows 4 shard streams from 2
    # peers solo vs 2 mux sessions -> ~2x frames/parked; gate at 1.5x.
    # p99 factor is wide: ~2s windows put 2-3 samples in the tail.
    art = _run(
        tmp_path, "fleet_mux_ab.json",
        ["--ab", "--ab_nodes", "3", "--ab_shards", "6",
         "--preload_keys", "30", "--ab_reps", "2",
         "--ab_rate", "120", "--ab_load_sec", "2",
         "--ab_idle_sec", "3", "--ab_frames_factor", "1.5",
         "--ab_parked_factor", "1.5", "--ab_p99_factor", "4"],
        timeout=420)

    assert art["bench"] == "fleet_mux_ab"
    assert art["failures"] == [], art["failures"]
    ab = art["ab"]
    assert ab["interleaved"] and ab["baseline"] == "mux_off"
    for arm in ("mux_off", "mux_on"):
        assert len(ab["samples"][arm]) == 2
        for s in ab["samples"][arm]:
            assert s["acked_loss"] == 0
            assert s["value_mismatches"] == 0
            assert s["idle_frames_per_node_sec"] > 0
    for s in ab["samples"]["mux_on"]:
        assert s["mux_pulls"] > 0 and s["mux_fallbacks"] == 0
    for s in ab["samples"]["mux_off"]:
        assert s["mux_pulls"] == 0
    # the ratio the summary carries is mux_on/mux_off of the idle
    # frames metric: < 1 means the mux reduced it
    assert ab["ratio_vs_mux_off"]["mux_on"] < 1.0
