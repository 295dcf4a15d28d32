#!/usr/bin/env python
"""The main path on ONE TPU chip, in one process: bulk-load SSTs into a
sharded counter segment → post-load compaction on the device → serve
reads — plus the per-DB seam a background L0→L1 compaction takes.

    python chip_smoke.py            # one chip; from the sandbox:
                                    #   chiprun -- python chip_smoke.py
    python chip_smoke.py --chips 4  # ONLY the mesh path on four chips

Deployment (BASELINE.json configs 1-2, "1M int64 counters" /
"counter_service 64 shards, local-FS load_sst ingest + L0→L1
compaction"): 64 shards × 16,384 bulk-loaded counters, 16-byte keys,
8-byte little-endian int64 values, examples/counter_service options
(uint64-add, bits_per_key=10, background_compaction). Widths and the
shard count are never cut; a smaller ``--keys_per_shard`` is printed in
the ``reduced`` list. Data is made from ``--seed``.

Entry points are the served ones: an ``AdminHandler(tpu_compaction=True)``
behind an ``RpcServer`` driven over the wire (``add_db``,
``add_s3_sst_files_to_db`` with ``compact_db_after_load`` from a
``LocalObjectStore``, ``set_db_options``), writes and reads through the
replication plane's ``write`` / ``read`` RPCs. Every answer is compared
with a dict model fed the same operations
(rocksplicator_tpu/testing/counter_workload.py).

There is no CPU fallback: without a TPU the script exits nonzero before
anything else and prints no result. ``--rehearse`` runs every phase on
whatever platform jax has (``JAX_PLATFORMS=cpu`` at a tiny size) to find
wrong paths before chip time is spent; it ALWAYS exits nonzero and never
prints an ``ok`` line. The last line of a real run is the contract's
``{"ok": true, "device": {...}}``; everything else is on earlier lines.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib.metadata
import json
import logging
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FULL_SHARDS = 64
FULL_KEYS_PER_SHARD = 16384
WINDOW = 8           # in-flight ingest RPCs (load_sst_bench's default)
PROBES_PER_SHARD = 256
SCAN_SHARDS = 2
BURST_KEYS = 1000    # post-load increments per round (one memtable each)
WARM_SEGMENT = "warm"
SEGMENT = "seg"
RPC_TIMEOUT = 1100.0  # a cold compile rides the first ingest RPC


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Every XLA compilation of the process, from jax's own monitoring
    events: name + seconds per program, persistent-cache hits/misses, and
    (from the compiler's debug log) the argument shapes of each."""

    _BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax.monitoring

        self.programs = []  # (fun_name, seconds, arg shapes)
        self.hits = self.misses = 0
        self._shapes = {}   # fun_name -> arg shapes of its pending compile
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        pxla = logging.getLogger("jax._src.interpreters.pxla")
        pxla.setLevel(logging.DEBUG)
        pxla.propagate = False  # debug records stay out of stderr
        handler = logging.Handler(logging.DEBUG)
        handler.emit = self._record
        pxla.addHandler(handler)

    def _record(self, record) -> None:
        # logged by the compiler just before the program's compile event
        if str(record.msg).startswith("Compiling %s with global shapes"):
            self._shapes[str(record.args[0])] = str(record.args[1])

    def _duration(self, event, secs, **kw) -> None:
        if event == self._BACKEND:
            name = kw.get("fun_name", "?")
            self.programs.append(
                (name, float(secs), self._shapes.pop(name, "")))

    def _event(self, event, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def summary(self, since: int = 0, top: int = 4) -> dict:
        progs = self.programs[since:]
        return {
            "compilations": len(progs),
            "compile_seconds": round(sum(p[1] for p in progs), 3),
            "slowest": [
                {"program": n, "seconds": round(s, 3), "args": shapes[:240]}
                for n, s, shapes in sorted(progs, key=lambda p: -p[1])[:top]],
        }


class Cluster:
    """One AdminHandler node behind an RpcServer, plus the client side."""

    def __init__(self, root: str):
        from examples.counter_service.options import \
            counter_options_generator
        from rocksplicator_tpu.admin import AdminHandler
        from rocksplicator_tpu.replication import Replicator
        from rocksplicator_tpu.rpc import IoLoop, RpcClientPool, RpcServer

        self.replicator = Replicator(port=0)
        self.handler = AdminHandler(
            os.path.join(root, "dbs"), self.replicator,
            options_generator=counter_options_generator,
            executor_threads=WINDOW + 4,
            max_sst_loading_concurrency=WINDOW,
            tpu_compaction=True)
        self.server = RpcServer(port=0, ioloop=self.replicator.ioloop)
        self.server.add_handler(self.handler)
        self.server.start()
        self.ioloop = IoLoop.default()
        self.pool = RpcClientPool()

    def admin(self, method: str, **args):
        return self._call(self.server.port, method, args)

    def data(self, method: str, **args):
        return self._call(self.replicator.port, method, args)

    def _call(self, port, method, args):
        async def go():
            return await self.pool.call("127.0.0.1", port, method, args,
                                        timeout=RPC_TIMEOUT)

        return self.ioloop.run_sync(go(), timeout=RPC_TIMEOUT + 10)

    def ingest_all(self, store_uri: str, db_names) -> None:
        """Bounded concurrent fan-out of the ingest+compact RPC, exactly
        as benchmarks/load_sst_bench.py drives it."""
        async def fan_out():
            sem = asyncio.Semaphore(WINDOW)

            async def one(db_name):
                async with sem:
                    return await self.pool.call(
                        "127.0.0.1", self.server.port,
                        "add_s3_sst_files_to_db",
                        {"db_name": db_name, "s3_bucket": store_uri,
                         "s3_path": f"sst/{db_name}",
                         "compact_db_after_load": True},
                        timeout=RPC_TIMEOUT)

            return await asyncio.gather(*(one(n) for n in db_names))

        for res in self.ioloop.run_sync(
                fan_out(), timeout=RPC_TIMEOUT + 30 * len(db_names)):
            if res.get("ingested_files") != 1:
                raise RuntimeError(f"ingest answered {res}")

    def close(self) -> None:
        self.server.stop()
        self.handler.close()
        self.replicator.stop()
        self.ioloop.run_sync(self.pool.close())


class Shard:
    """One shard of the deployment: its db, its workload, its model."""

    def __init__(self, segment: str, shard: int, seed: int, keys: int):
        from rocksplicator_tpu.testing.counter_workload import CounterModel
        from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

        self.shard, self.seed, self.keys = shard, seed, keys
        self.db_name = segment_to_db_name(segment, shard)
        self.model = CounterModel()


def build_bulk_sst(store, tmp: str, sh: Shard) -> int:
    """The shard's bulk file, written with the plain row-format writer
    (not the array sink under test) and uploaded to the object store."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter
    from rocksplicator_tpu.testing import counter_workload as wl

    path = os.path.join(tmp, f"{sh.db_name}.tsst")
    w = SSTWriter(path)
    for key, value in wl.bulk_rows(sh.seed, sh.shard, sh.keys):
        w.add(key, 0, OpType.PUT, wl.encode_value(value))
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"sst/{sh.db_name}/bulk.tsst")
    os.remove(path)
    return size


def write_merges(cluster: Cluster, sh: Shard, ops, batch: int) -> None:
    """MERGE increments through the leader write RPC, ``batch`` per
    WriteBatch; the model takes each op once its batch is acknowledged."""
    from rocksplicator_tpu.storage import WriteBatch
    from rocksplicator_tpu.testing.counter_workload import encode_value

    for lo in range(0, len(ops), batch):
        chunk = ops[lo:lo + batch]
        wb = WriteBatch()
        for key, delta in chunk:
            wb.merge(key, encode_value(delta))
        cluster.data("write", db_name=sh.db_name, raw_batch=wb.encode())
        for key, delta in chunk:
            sh.model.merge(key, delta)


def preload(cluster: Cluster, sh: Shard) -> None:
    from rocksplicator_tpu.testing.counter_workload import preload_ops

    cluster.admin("add_db", db_name=sh.db_name, role="LEADER")
    write_merges(cluster, sh, preload_ops(sh.seed, sh.shard, sh.keys), 512)


def note_bulk_loaded(sh: Shard) -> None:
    from rocksplicator_tpu.testing.counter_workload import bulk_rows

    for key, value in bulk_rows(sh.seed, sh.shard, sh.keys):
        sh.model.put(key, value)


def check_reads(cluster: Cluster, sh: Shard, scan: bool) -> int:
    """Point reads (and a full scan) over the read RPC vs the model.
    Returns the number of mismatches."""
    from rocksplicator_tpu.testing.counter_workload import probe_keys

    bad = 0
    keys = probe_keys(sh.seed, sh.shard, sh.keys, PROBES_PER_SHARD)
    for lo in range(0, len(keys), 64):
        chunk = keys[lo:lo + 64]
        got = cluster.data("read", db_name=sh.db_name, op="multi_get",
                           keys=chunk)["values"]
        for key, value in zip(chunk, got):
            value = None if value is None else bytes(value)
            if value != sh.model.get(key):
                bad += 1
                if bad <= 3:
                    say(f"MISMATCH {sh.db_name} get {key!r}: "
                        f"{value!r} != {sh.model.get(key)!r}")
    if scan:
        rows, start = [], None
        while True:
            page = cluster.data("read", db_name=sh.db_name, op="scan",
                                start=start, count=4096)["values"]
            rows += [(bytes(k), bytes(v)) for k, v in page]
            if len(page) < 4096:
                break
            start = rows[-1][0] + b"\x00"
        want = sh.model.scan()
        if rows != want:
            diff = sum(1 for a, b in zip(rows, want) if a != b) \
                + abs(len(rows) - len(want))
            bad += max(1, diff)
            say(f"MISMATCH {sh.db_name} scan: {len(rows)} rows vs "
                f"{len(want)} in the model, {diff} differ")
    return bad


def background_compaction(cluster: Cluster, sh: Shard,
                          deadline: float) -> int:
    """Post-load MERGE rounds under a small memtable until a background
    L0→L1 compaction has installed an L1 file. Each round is ONE
    WriteBatch larger than the memtable, so it rotates exactly one
    memtable → one L0 file; the fourth file trips the L0 trigger."""
    from rocksplicator_tpu.testing.counter_workload import burst_ops

    db = cluster.handler.db_manager.get_db(sh.db_name).db
    burst_keys = min(BURST_KEYS, sh.keys)
    # below one round's bytes (an entry is > 24 B of key + value), so
    # every round rotates the memtable
    cluster.admin("set_db_options", db_name=sh.db_name,
                  options={"memtable_bytes": 16 * burst_keys})
    trigger = db.options.level0_compaction_trigger

    def files(level: int) -> int:
        return int(db.get_property(f"num-files-at-level{level}"))

    def wait(cond, until: float) -> bool:
        while not cond():
            if time.monotonic() > until:
                return False
            time.sleep(0.02)
        return True

    rounds = 0
    while files(1) == 0:
        l0 = files(0)
        ops = burst_ops(sh.seed, sh.shard, rounds, burst_keys)
        write_merges(cluster, sh, ops, len(ops))
        rounds += 1
        # the round's flush lands before the next round is sent (queued
        # memtables would coalesce into one L0 file); at the trigger the
        # background thread compacts, compiling on first use
        flushed = wait(lambda: files(0) > l0 or files(1) > 0, deadline)
        if flushed and files(0) >= trigger:
            flushed = wait(lambda: files(1) > 0, deadline)
        if not flushed:
            raise RuntimeError(
                f"{sh.db_name}: stuck after {rounds} rounds "
                f"(L0={files(0)} L1={files(1)})")
    return rounds


def sst_files(cluster: Cluster, sh: Shard):
    """(name, is_planar, entries) of every SST file in the shard's dir."""
    from rocksplicator_tpu.storage.sst import SSTReader

    path = os.path.join(cluster.handler.rocksdb_dir, sh.db_name)
    out = []
    for name in sorted(os.listdir(path)):
        if name.endswith(".tsst"):
            r = SSTReader(os.path.join(path, name))
            out.append((name, bool(r.props.get("planar")),
                        int(r.props.get("num_entries", 0))))
            r.close()
    return out


def span_counts() -> dict:
    from rocksplicator_tpu.observability.collector import SpanCollector

    spans = SpanCollector.get().snapshot()
    return {
        "tpu.compact_stream": [
            s["annotations"] for s in spans
            if s["name"] == "tpu.compact_stream"],
        "per_db_device_compactions": sum(
            1 for s in spans if s["name"] == "storage.compaction"
            and s["annotations"].get("backend") == "tpu"
            and s["annotations"].get("outputs", 0) >= 1),
    }


def run_main_path(args) -> bool:
    import jax

    from rocksplicator_tpu.observability.collector import SpanCollector
    from rocksplicator_tpu.storage.compaction import host_fallback_counts
    from rocksplicator_tpu.storage.native.binding import rebuild_native
    from rocksplicator_tpu.tpu.compile_cache import configure_compile_cache
    from rocksplicator_tpu.utils.objectstore import LocalObjectStore

    t_start = time.monotonic()
    reduced = []
    if args.shards != FULL_SHARDS:
        reduced.append(f"shards {FULL_SHARDS} -> {args.shards}")
    if args.keys_per_shard != FULL_KEYS_PER_SHARD:
        reduced.append(f"keys_per_shard {FULL_KEYS_PER_SHARD} -> "
                       f"{args.keys_per_shard}")
    say(f"deployment: counter_service, {args.shards} shards x "
        f"{args.keys_per_shard} bulk-loaded counters = "
        f"{args.shards * args.keys_per_shard}, 16 B keys, 8 B int64 "
        f"values, uint64-add, seed {args.seed}; reduced={reduced}")

    cache_dir = configure_compile_cache()
    warm_cache = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    say(f"compile cache: {cache_dir} "
        f"({'warm' if warm_cache else 'cold'} at start)")
    compiles = CompileLog()

    lib = rebuild_native()
    say(f"native library: rebuilt from tsst_native.cc and loaded "
        f"(merge_resolve={bool(getattr(lib, 'has_merge_resolve', False))})")

    root = tempfile.mkdtemp(prefix="chip-smoke-")
    cluster = None
    secs = {}
    try:
        store_uri = os.path.join(root, "bucket")
        store = LocalObjectStore(store_uri)
        warm = Shard(WARM_SEGMENT, 0, args.seed, args.keys_per_shard)
        shards = [Shard(SEGMENT, s, args.seed, args.keys_per_shard)
                  for s in range(args.shards)]

        t0 = time.monotonic()
        total_bytes = sum(build_bulk_sst(store, root, sh)
                          for sh in [warm] + shards)
        secs["build"] = time.monotonic() - t0
        say(f"built {len(shards) + 1} bulk SST sets, "
            f"{total_bytes / 1e6:.1f} MB, {secs['build']:.1f} s")

        cluster = Cluster(root)
        say(f"node up: admin rpc :{cluster.server.port}, data rpc "
            f":{cluster.replicator.port}")

        # -- set-up: warm every program shape through the same calls ------
        t0 = time.monotonic()
        preload(cluster, warm)
        cluster.ingest_all(store_uri, [warm.db_name])
        note_bulk_loaded(warm)
        background_compaction(cluster, warm, time.monotonic() + 600)
        bad = check_reads(cluster, warm, scan=True)
        secs["warmup"] = time.monotonic() - t0
        warm_mark = len(compiles.programs)
        say(f"warm-up (set-up): {secs['warmup']:.1f} s, "
            f"{json.dumps(compiles.summary())}")
        launches_before = len(cluster.handler._batch_compactor.batch_sizes)

        # -- load: add_db + pre-load increments through the write path ----
        t0 = time.monotonic()
        for sh in shards:
            preload(cluster, sh)
        secs["load"] = time.monotonic() - t0

        # -- ingest + post-load compaction on the device ------------------
        t0 = time.monotonic()
        cluster.ingest_all(store_uri, [sh.db_name for sh in shards])
        secs["ingest_compact"] = time.monotonic() - t0
        for sh in shards:
            note_bulk_loaded(sh)
        phases = SpanCollector.get().phase_totals("admin.ingest.")
        say("ingest rpc phases (span totals, ms): " + json.dumps(
            {k.split(".")[-1]: round(v["total_ms"])
             for k, v in sorted(phases.items())}))

        # -- reads vs the model -------------------------------------------
        t0 = time.monotonic()
        for i, sh in enumerate(shards):
            bad += check_reads(cluster, sh, scan=i < SCAN_SHARDS)
        secs["read"] = time.monotonic() - t0

        # -- the per-DB seam: background L0→L1 on the device --------------
        t0 = time.monotonic()
        rounds = background_compaction(
            cluster, shards[0], time.monotonic() + 600)
        bad += check_reads(cluster, shards[0], scan=True)
        secs["background"] = time.monotonic() - t0

        # -- what the program itself recorded -----------------------------
        spans = span_counts()
        batch_sizes = list(
            cluster.handler._batch_compactor.batch_sizes)[launches_before:]
        planar = {sh.db_name: sst_files(cluster, sh)
                  for sh in shards[1:SCAN_SHARDS + 1]}
        fallbacks = host_fallback_counts()
        after = compiles.summary(since=warm_mark, top=20)
        peak = (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
    finally:
        if cluster is not None:
            cluster.close()
        shutil.rmtree(root, ignore_errors=True)

    say("seconds per phase: " + json.dumps(
        {k: round(v, 2) for k, v in secs.items()}))
    say(f"reads: {PROBES_PER_SHARD}+ point reads on each of "
        f"{len(shards)} shards, full scans of {SCAN_SHARDS + 1} shards "
        f"(one of them again after {rounds} post-load rounds): "
        f"{bad} mismatches against the dict model")
    streams = spans["tpu.compact_stream"]
    say(f"batched device launches: {len(streams)} tpu.compact_stream "
        f"spans (warm-up included), shards per launch "
        f"{[a.get('shards') for a in streams]}, (group_size, capacity) "
        f"{sorted({(a.get('group_size'), a.get('capacity')) for a in streams})}"
        f"; batch_compactor.batch_sizes={batch_sizes}")
    say(f"per-DB device compactions (storage.compaction backend=tpu): "
        f"{spans['per_db_device_compactions']}")
    say(f"output files: {json.dumps(planar)}")
    say(f"tpu.host_fallbacks: {json.dumps(fallbacks)}")
    say(f"xla compilations, whole run: {json.dumps(compiles.summary())}; "
        f"persistent cache hits={compiles.hits} misses={compiles.misses}")
    say(f"xla compilations after warm-up (should be none): "
        f"{json.dumps(after)}")
    say(f"peak device bytes: {peak}")
    say(f"total seconds: {time.monotonic() - t_start:.1f}")

    checks = {
        "zero mismatches": bad == 0,
        # the warm-up's shard and every loaded one crossed the seam in a
        # tpu.compact_stream launch (how many launches that took is the
        # group commit's business: one per WINDOW when its linger works)
        "every shard rode a batched launch": (
            sum(a.get("shards", 0) for a in streams) >= len(shards) + 1
            and sum(batch_sizes) >= len(shards)),
        "per-DB device compactions happened":
            spans["per_db_device_compactions"] >= 2,
        "outputs are PLANAR array-sink files": all(
            files and all(is_planar for _n, is_planar, _e in files)
            for files in planar.values()),
        "every host fallback is zero": not any(fallbacks.values()),
    }
    for name, ok in checks.items():
        say(f"check {name}: {'ok' if ok else 'FAILED'}")
    return all(checks.values())


def run_mesh_path(args) -> bool:
    """``--chips 4``: the sharded compaction step on a 4-device mesh vs
    the unsharded single-chip pipeline over the same data, hashes equal
    (``__graft_entry__.dryrun_multichip``, which prints each
    input's sharding and bytes per device) — and no other phase."""
    import jax

    import __graft_entry__ as graft

    if len(jax.devices()) != args.chips:
        say(f"--chips {args.chips} needs exactly that many devices, jax "
            f"has {len(jax.devices())}")
        return False
    t0 = time.monotonic()
    graft.dryrun_multichip(args.chips,
                           entries_per_block=args.entries_per_block)
    say(f"sharded vs unsharded: hashes equal "
        f"({time.monotonic() - t0:.1f} s, compile included)")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=FULL_SHARDS)
    ap.add_argument("--keys_per_shard", type=int,
                    default=FULL_KEYS_PER_SHARD)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = ONLY the mesh path, on four chips")
    # 1024: the sharded step compiles for a described v5e:2x2 in ~50 s
    # at 1024 entries and had not finished after 20 min at 2048 (PERF.md)
    ap.add_argument("--entries_per_block", type=int, default=1024,
                    help="mesh path: entries per (shard, block) cell")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases off-chip; always exits nonzero")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.rehearse:
        print(f"chip_smoke: no TPU (jax found {device}); this script has "
              f"no CPU fallback", file=sys.stderr)
        return 2
    import rocksplicator_tpu  # noqa: F401 — alone, the script is nothing

    say(f"device: {json.dumps(device)}; jax {jax.__version__}, jaxlib "
        f"{importlib.metadata.version('jaxlib')}, libtpu "
        f"{importlib.metadata.version('libtpu')}")

    logging.basicConfig(level=logging.ERROR,
                        format="%(levelname)s %(name)s: %(message)s")
    ok = (run_mesh_path(args) if args.chips == 4
          else run_main_path(args))
    if args.rehearse:
        say(f"REHEARSAL ONLY on {device['platform']}: phases "
            f"{'agreed' if ok else 'FAILED'}; this is not a chip run")
        return 3 if ok else 1
    if not ok:
        say("FAILED")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
