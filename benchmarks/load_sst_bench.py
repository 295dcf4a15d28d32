#!/usr/bin/env python
"""BASELINE config #2/#3-shaped benchmark: multi-shard load_sst end-to-end.

Drives the FULL north-star path on real DBs through the admin RPC surface:
build per-shard SST sets → upload to the object store → addS3SstFilesToDB
on every shard — measuring wall-clock and GB/s for the CPU backend vs the
TPU backend.

Round-7 pipelining (ISSUE 3): shard ingest RPCs are issued CONCURRENTLY on
the ioloop through a bounded window (AckWindow-style flow control,
``--window``, default 8 in flight) instead of strictly serially; the
handler narrows its per-db admin lock so shard k+1's download overlaps
shard k's engine ingest, and post-load compactions coalesce cross-shard in
the BatchCompactor. ``--trace`` emits the slowest-shard ingest span tree
and per-phase totals (download/validate/ingest/meta/compact) from the
in-process SpanCollector.

    python -m benchmarks.load_sst_bench --shards 16 --keys_per_shard 20000
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import struct
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from rocksplicator_tpu.admin import AdminHandler
# Warm the engine's lazily-imported kernel deps (ops → jax, ~1.5 s) before
# any timed region: a serving node has them loaded; without this the first
# shard's flush pays the import inside its ingest span and every
# concurrently-admitted shard blocks on the same import lock.
import rocksplicator_tpu.ops  # noqa: F401

from rocksplicator_tpu.observability.collector import SpanCollector, render_trace
from rocksplicator_tpu.replication import Replicator
from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer
from rocksplicator_tpu.storage import OpType, WriteBatch
from rocksplicator_tpu.storage.sst import SSTWriter
from rocksplicator_tpu.utils.objectstore import LocalObjectStore
from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

pack64 = struct.Struct("<q").pack


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def build_sst_sets(store, shards, keys_per_shard, tmp, key_bytes=16):
    """Per-shard sorted SST files uploaded under sst/<shard:05d>/."""
    total_bytes = 0
    for shard in range(shards):
        path = os.path.join(tmp, f"bulk{shard:05d}.tsst")
        w = SSTWriter(path)
        for i in range(keys_per_shard):
            key = f"s{shard:03d}-key{i:08d}".encode()[:key_bytes]
            w.add(key, 0, OpType.PUT, pack64(i))
        w.finish()
        total_bytes += os.path.getsize(path)
        store.put_object(path, f"sst/{shard:05d}/bulk.tsst")
        os.remove(path)
    return total_bytes


def run_load(handler_kwargs, store_uri, shards, keys_per_shard,
             write_frac, label, rocksdb_dir, window):
    """One labeled pass. Returns a per-run result dict (elapsed, spot-check
    failures, per-phase span totals, slowest-shard trace)."""
    # fresh span ring per pass so cpu/tpu attributions don't mix
    SpanCollector.reset_for_test()
    replicator = Replicator(port=0)
    handler = AdminHandler(
        rocksdb_dir, replicator,
        executor_threads=window + 4,
        # the client honors the same window, so the admission gate never
        # rejects in-bench; real orchestrators retry on TOO_MANY_REQUESTS
        max_sst_loading_concurrency=window,
        **handler_kwargs)
    server = RpcServer(port=0, ioloop=replicator.ioloop)
    server.add_handler(handler)
    server.start()
    ioloop = IoLoop.default()
    pool = RpcClientPool()

    def call(method, **args):
        async def go():
            return await pool.call("127.0.0.1", server.port, method, args,
                                   timeout=600)

        return ioloop.run_sync(go(), timeout=610)

    try:
        for shard in range(shards):
            call("add_db", db_name=segment_to_db_name("seg", shard),
                 role="LEADER")
        # pre-load writes so the post-load compaction has overlap work
        n_writes = int(keys_per_shard * write_frac)
        for shard in range(shards):
            app_db = handler.db_manager.get_db(segment_to_db_name("seg", shard))
            for i in range(0, n_writes):
                app_db.write(WriteBatch().put(
                    f"s{shard:03d}-key{i * 7:08d}".encode()[:16], pack64(-1)))

        async def fan_out():
            # bounded concurrent shard fan-out — the serial per-shard
            # run_sync loop was the single largest orchestration cost
            sem = asyncio.Semaphore(window)

            async def one(shard):
                async with sem:
                    return await pool.call(
                        "127.0.0.1", server.port, "add_s3_sst_files_to_db",
                        {"db_name": segment_to_db_name("seg", shard),
                         "s3_bucket": store_uri,
                         "s3_path": f"sst/{shard:05d}",
                         "compact_db_after_load": True},
                        timeout=600)

            return await asyncio.gather(*(one(s) for s in range(shards)))

        t0 = time.monotonic()
        # the overall cap must scale with the shard count (each RPC keeps
        # its own 600s budget; a serial --window 1 A/B on a slow host can
        # legitimately exceed a flat 610s total)
        ioloop.run_sync(fan_out(), timeout=610 + 30 * shards)
        elapsed = time.monotonic() - t0

        # correctness spot-checks: every shard
        failures = 0
        for shard in range(shards):
            app_db = handler.db_manager.get_db(segment_to_db_name("seg", shard))
            want = pack64(keys_per_shard - 1)
            if app_db.get(
                f"s{shard:03d}-key{(keys_per_shard - 1):08d}".encode()[:16]
            ) != want:
                failures += 1
                log(f"{label}: SPOT-CHECK FAILURE shard {shard}")
        collector = SpanCollector.get()
        phases = collector.phase_totals("admin.")
        # every served RPC records its root: the ingest's trace starts there
        slowest = collector.slowest_trace(
            "rpc.server.add_s3_sst_files_to_db")
        trace_lines = None
        if slowest is not None:
            trace_lines = render_trace(
                slowest["trace"]["spans"], slowest["trace"]["start_ms"])
        return {
            "elapsed_s": round(elapsed, 3),
            "spot_check_failures": failures,
            "window": window,
            "phase_ms": phases,
            "compact_batch_sizes": list(handler._batch_compactor.batch_sizes),
            "slowest_shard_trace": trace_lines,
        }
    finally:
        server.stop()
        handler.close()
        replicator.stop()
        ioloop.run_sync(pool.close())


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--shards", type=int, default=16)
    p.add_argument("--keys_per_shard", type=int, default=20000)
    p.add_argument("--write_frac", type=float, default=0.2)
    p.add_argument("--window", type=int, default=8,
                   help="max in-flight shard ingest RPCs (flow-control "
                        "window)")
    p.add_argument("--configs", default="cpu,tpu",
                   help="comma-separated subset of cpu,tpu to run")
    p.add_argument("--trace", action="store_true",
                   help="include the slowest-shard ingest span tree in the "
                        "output JSON")
    p.add_argument("--out", default=None, help="also write the result JSON "
                                               "to this path")
    p.add_argument("--trace_out", default=None,
                   help="write a standalone trace-attribution artifact "
                        "(implies --trace)")
    args = p.parse_args(argv)
    if args.trace_out:
        args.trace = True

    tmp = tempfile.mkdtemp(prefix="loadsst-bench-")
    store_uri = os.path.join(tmp, "bucket")
    store = LocalObjectStore(store_uri)
    total_bytes = build_sst_sets(store, args.shards, args.keys_per_shard, tmp)
    log(f"built {args.shards} shard SST sets, {total_bytes / 1e6:.1f} MB")

    configs = {"cpu": {}, "tpu": {"tpu_compaction": True}}
    runs = {}
    results = {}
    labels = [c.strip() for c in args.configs.split(",") if c.strip()]
    if "tpu" in labels:
        # the tpu config's kernel compiles are identical run to run —
        # warm runs measure the pipeline, not the compiler
        from rocksplicator_tpu.tpu.compile_cache import \
            configure_compile_cache

        log(f"compile cache: {configure_compile_cache()}")
    for label in labels:
        run = run_load(
            configs[label], store_uri, args.shards, args.keys_per_shard,
            args.write_frac, label, os.path.join(tmp, f"dbs-{label}"),
            args.window,
        )
        gbps = total_bytes / run["elapsed_s"] / 1e9
        run["gbps"] = round(gbps, 4)
        runs[label] = run
        results[label] = gbps
        log(f"{label}: load_sst of {args.shards} shards in "
            f"{run['elapsed_s']:.2f}s = {gbps:.4f} GB/s "
            f"(window={args.window}, "
            f"spot_check_failures={run['spot_check_failures']}, "
            f"compact_batches={run['compact_batch_sizes']})")

    headline = results.get("tpu", results.get("cpu", 0.0))
    out = {
        "metric": "load_sst_end_to_end",
        "value": round(headline, 4),
        "unit": "GB/s",
        "vs_baseline": round(results["tpu"] / results["cpu"], 2)
        if results.get("cpu") and results.get("tpu") else 0.0,
        "shards": args.shards,
        "keys_per_shard": args.keys_per_shard,
        "total_mb": round(total_bytes / 1e6, 1),
        "window": args.window,
        "cpu_gbps": round(results.get("cpu", 0.0), 4),
        "spot_check_failures": sum(
            r["spot_check_failures"] for r in runs.values()),
        "runs": {
            label: {k: v for k, v in run.items()
                    if args.trace or k != "slowest_shard_trace"}
            for label, run in runs.items()
        },
    }
    print(json.dumps(out), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
    if args.trace_out:
        artifact = {
            "bench": "load_sst_pipelined",
            "shards": args.shards,
            "keys_per_shard": args.keys_per_shard,
            "window": args.window,
            "total_mb": round(total_bytes / 1e6, 1),
            "attribution": {
                label: {
                    "elapsed_s": run["elapsed_s"],
                    "gbps": run["gbps"],
                    "phase_ms": run["phase_ms"],
                    "compact_batch_sizes": run["compact_batch_sizes"],
                    "slowest_shard_trace": run["slowest_shard_trace"],
                }
                for label, run in runs.items()
            },
        }
        os.makedirs(
            os.path.dirname(os.path.abspath(args.trace_out)), exist_ok=True)
        with open(args.trace_out, "w") as f:
            json.dump(artifact, f, indent=1)
            f.write("\n")
    shutil.rmtree(tmp, ignore_errors=True)
    return 0 if out["spot_check_failures"] == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
