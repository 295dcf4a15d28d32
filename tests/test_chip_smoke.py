"""chip_smoke.py itself, as far as a CPU can show it: it refuses to run
without a chip, and its dict model agrees with the served engine."""

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_no_chip_exits_nonzero_and_prints_no_result():
    """The driver runs ``python chip_smoke.py`` here first, where it MUST
    fail: nonzero exit, nothing on stdout (no result line at all), before
    any phase runs."""
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no TPU" in out.stderr


def test_dict_model_agrees_with_engine_on_256_key_shard(tmp_path):
    """One 256-key shard through the smoke's own phases — pre-load
    increments, bulk load + device compaction over the admin RPC,
    post-load rounds into a background L0→L1 compaction, reads over the
    data RPC — answers the dict model exactly, with no host fallback.
    (XLA-CPU under the explicit JAX_PLATFORMS=cpu of conftest.py.)"""
    import chip_smoke as cs
    from rocksplicator_tpu.storage.compaction import host_fallback_counts
    from rocksplicator_tpu.testing.counter_workload import bulk_key
    from rocksplicator_tpu.utils.objectstore import LocalObjectStore

    store_uri = str(tmp_path / "bucket")
    sh = cs.Shard(cs.SEGMENT, 3, seed=11, keys=256)
    cs.build_bulk_sst(LocalObjectStore(store_uri), str(tmp_path), sh)
    cluster = cs.Cluster(str(tmp_path))
    try:
        cs.preload(cluster, sh)
        cluster.ingest_all(store_uri, [sh.db_name])
        cs.note_bulk_loaded(sh)
        assert cs.check_reads(cluster, sh, scan=True) == 0
        assert len(sh.model) == 256 + 3  # bulk + live-only counters
        files = cs.sst_files(cluster, sh)
        assert files and all(planar for _n, planar, _e in files)

        rounds = cs.background_compaction(
            cluster, sh, time.monotonic() + 120)
        assert rounds == 4  # one L0 file per round, trigger at four
        assert cs.check_reads(cluster, sh, scan=True) == 0
        spans = cs.span_counts()
        assert len(spans["tpu.compact_stream"]) == 1
        assert spans["per_db_device_compactions"] == 1

        # the comparison has teeth: a model that missed one write differs
        sh.model.merge(bulk_key(sh.shard, 0), 1)  # always probed
        assert cs.check_reads(cluster, sh, scan=False) == 1
    finally:
        cluster.close()
    assert host_fallback_counts() == {}

