"""No fallback hides the device: every seam where a compaction routed to
the TPU carries on in host code keeps its guarantee (the node keeps
compacting) but is counted under ``tpu.host_fallbacks reason=<seam>`` and
logged at ERROR; seams that used to substitute another backend under the
device's name raise instead. chip_smoke.py requires every reason at zero
on the chip; these tests show each reason can become nonzero."""

import logging
import struct

import pytest

from rocksplicator_tpu.storage import DB, DBOptions
from rocksplicator_tpu.storage.compaction import (host_fallback_counts,
                                                  record_host_fallback)
from rocksplicator_tpu.storage.merge import UInt64AddOperator
from rocksplicator_tpu.tpu import TpuCompactionBackend

pack64 = struct.Struct("<q").pack


def _counter_db(path, **kw):
    return DB(str(path), DBOptions(
        merge_operator=UInt64AddOperator(),
        compaction_backend=TpuCompactionBackend(),
        level0_compaction_trigger=100, memtable_bytes=1 << 30, **kw))


def _three_runs(db):
    for r in range(3):
        for i in range(40):
            db.merge(b"ctr%013d" % i, pack64(r + i))
        db.flush()


@pytest.mark.parametrize("reason, sink", [
    ("direct_sink_error", "raise"),
    ("direct_sink_declined", "decline"),
])
def test_direct_sink_fallback_is_counted_and_files_stay_correct(
        tmp_path, monkeypatch, caplog, reason, sink):
    """An exception out of the device's direct sink (a Mosaic refusal, an
    OOM, a compile error) — or a decline — still compacts through the
    tuple path, but bumps the counter and logs at ERROR."""
    def broken(self, *a, **kw):
        if sink == "raise":
            raise RuntimeError("injected: device sink failed")
        return None

    monkeypatch.setattr(TpuCompactionBackend, "merge_runs_to_files", broken)
    with _counter_db(tmp_path / "db") as db, \
            caplog.at_level(logging.ERROR):
        _three_runs(db)
        assert host_fallback_counts() == {}
        db.compact_range()
        assert host_fallback_counts() == {reason: 1}
        for i in range(40):
            assert db.get(b"ctr%013d" % i) == pack64(3 * i + 3)
    assert any(r.levelno == logging.ERROR and reason in r.getMessage()
               for r in caplog.records)


def test_clean_device_compaction_counts_no_fallback(tmp_path):
    with _counter_db(tmp_path / "db") as db:
        _three_runs(db)
        db.compact_range()
        assert db.get(b"ctr%013d" % 7) == pack64(3 * 7 + 3)
    assert host_fallback_counts() == {}


def test_batched_launch_failure_is_counted(tmp_path, monkeypatch):
    """compact_dbs_batched keeps its guarantee when the group launch
    fails (every shard handed back for the per-db path, no plan mutex
    leaked) and counts it."""
    from rocksplicator_tpu.tpu.compaction_service import (
        TpuCompactionService, compact_dbs_batched)

    def boom(self, *a, **kw):
        raise RuntimeError("injected: launch failed")

    monkeypatch.setattr(TpuCompactionService, "compact_shard_stream", boom)
    dbs = []
    for n in range(2):
        db = _counter_db(tmp_path / f"db{n}")
        _three_runs(db)
        dbs.append((f"db{n}", db))
    try:
        handled, remaining = compact_dbs_batched(dbs)
        assert handled == [] and len(remaining) == 2
        assert host_fallback_counts() == {"batched_launch": 1}
        for _name, db in remaining:
            db.compact_range()  # the mutex came back: this cannot hang
            assert db.get(b"ctr%013d" % 5) == pack64(3 * 5 + 3)
    finally:
        for _name, db in dbs:
            db.close()


def test_tpu_backend_refuses_a_platform_nobody_asked_for(monkeypatch):
    """On a host with no chip, "the TPU backend" would be XLA-CPU under
    the TPU's name. It constructs off-chip only under an EXPLICIT
    JAX_PLATFORMS=cpu (the test suite's setting), and records the
    platform it found where /stats shows it."""
    from rocksplicator_tpu.tpu.compaction_service import TpuCompactionService
    from rocksplicator_tpu.utils.stats import Stats

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="resolved platform 'cpu'"):
        TpuCompactionBackend()
    with pytest.raises(RuntimeError, match="resolved platform 'cpu'"):
        TpuCompactionService()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert TpuCompactionBackend().platform == "cpu"
    assert "tpu.backend_platform platform=cpu" in Stats.get().dump_text()


def test_worker_tpu_backend_no_longer_degrades(monkeypatch):
    """``--backend tpu`` asked for the device: without one the worker
    fails to start instead of serving jobs on the native CPU backend."""
    from rocksplicator_tpu.compaction_remote.worker import _build_backend
    from rocksplicator_tpu.storage.native_compaction import \
        NativeCompactionBackend

    assert isinstance(_build_backend("cpu"), NativeCompactionBackend)
    assert isinstance(_build_backend("tpu"), TpuCompactionBackend)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="resolved platform"):
        _build_backend("tpu")
    monkeypatch.setenv("RSTPU_COMPACT_WORKER_BACKEND", "tpu")
    with pytest.raises(RuntimeError, match="resolved platform"):
        _build_backend(None)


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
        tmp_path, monkeypatch):
    import os

    import jax

    from rocksplicator_tpu.tpu import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert updates == []  # placed from outside: nothing set in code
    monkeypatch.delenv(compile_cache.ENV_VAR)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.configure_compile_cache() == os.path.join(
        repo, ".jax_cache")
    assert ("jax_compilation_" + "cache_dir",
            compile_cache.DEFAULT_CACHE_DIR) in updates


def test_fallback_reasons_share_one_counter_family():
    record_host_fallback("native_lib", "test")
    record_host_fallback("native_lib", "test")
    record_host_fallback("kernel_overflow", "test")
    assert host_fallback_counts() == {"native_lib": 2,
                                      "kernel_overflow": 1}
