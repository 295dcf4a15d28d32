#!/usr/bin/env python
"""Serving-scale macro-benchmark: YCSB-style mixed workload against a
3-replica cluster, driven through the FULL stack (RPC client → router →
replication → engine).

Every PERF.md number through round 12 is a micro/meso bench of one path
in isolation; this harness measures the serving SLO instead — p50/p99
latency per op class against a sweep of offered throughput:

- **zipfian key popularity** (YCSB ZipfianGenerator shape) over the
  preloaded keyspace;
- **tunable op mix** (``--mix get=0.75,put=0.15,multi_get=0.05,scan=0.05``);
- **open-loop (Poisson) arrival**: requests are issued on a seeded
  Poisson schedule regardless of completions, and latency is measured
  from the INTENDED arrival time — so at overload, queueing delay shows
  up in the percentiles instead of being hidden by a closed loop
  slowing its own offered rate (the YCSB "coordinated omission" fix);
- a ≥3-point offered-throughput sweep, each point reporting p50/p99 per
  op class;
- an interleaved read-policy A/B (leader_only vs follower_ok(max_lag)):
  closed-loop reader saturation, the read-scaling acceptance number.

Topology: 3 OS processes (1 leader + 2 followers, semi-sync mode 1)
spawned by this script via its own ``--serve`` child mode, plus this
driver process as the client fleet. Reads ride the round-13
bounded-staleness ``read`` RPC through ``RpcRouter.read`` read-preference
policies; writes ride the ``write`` RPC to the leader.

    python -m benchmarks.macro_bench --shards 4 --preload_keys 2000 \
        --rates 300,600,1200 --duration 5 --ab \
        --out benchmarks/results/macro_bench.json

Artifacts carry the shared ``host_calibration`` block
(benchmarks/ab_runner.py) so numbers are comparable across hosts.
"""

from __future__ import annotations

import argparse
import asyncio
import bisect
import contextlib
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.ab_runner import (emit_gated_artifact,  # noqa: E402
                                  host_calibration, run_interleaved,
                                  sched_ab_failures)

SEGMENT = "mac"
OP_CLASSES = ("get", "put", "multi_get", "scan")
DEFAULT_MIX = "get=0.75,put=0.15,multi_get=0.05,scan=0.05"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# deterministic workload generators (unit-tested: same seed ⇒ same stream)
# ---------------------------------------------------------------------------


class ZipfianGenerator:
    """Zipfian key popularity over ``[0, n)``: P(rank r) ∝ 1/(r+1)^theta
    (YCSB ZipfianGenerator shape, theta=0.99 default), drawn via a
    precomputed inverse CDF + bisect. ``spread`` scatters ranks over the
    id space deterministically so hot keys don't all land on shard 0."""

    def __init__(self, n: int, theta: float = 0.99, seed: int = 0,
                 spread: bool = True):
        if n <= 0:
            raise ValueError("n must be positive")
        self.n = n
        self.theta = theta
        self._rng = random.Random(seed)
        cum: List[float] = []
        total = 0.0
        for rank in range(n):
            total += 1.0 / ((rank + 1) ** theta)
            cum.append(total)
        self._cum = cum
        self._total = total
        # rank -> key id permutation (seeded by n, NOT by the draw seed:
        # two generators over the same keyspace agree on which ids are
        # hot, regardless of their draw streams)
        if spread:
            perm = list(range(n))
            random.Random(n * 2654435761 % (1 << 31)).shuffle(perm)
            self._perm: Optional[List[int]] = perm
        else:
            self._perm = None

    def next(self) -> int:
        r = self._rng.random() * self._total
        rank = bisect.bisect_left(self._cum, r)
        rank = min(rank, self.n - 1)
        return self._perm[rank] if self._perm is not None else rank


def poisson_arrivals(rate_per_sec: float, duration_sec: float,
                     seed: int = 0) -> List[float]:
    """Open-loop arrival offsets (seconds from phase start): exponential
    inter-arrivals at ``rate_per_sec``, deterministic under ``seed``."""
    if rate_per_sec <= 0:
        return []
    rng = random.Random(seed)
    t = 0.0
    out: List[float] = []
    while True:
        t += rng.expovariate(rate_per_sec)
        if t >= duration_sec:
            return out
        out.append(t)


def parse_mix(spec: str) -> Dict[str, float]:
    mix: Dict[str, float] = {}
    for part in spec.split(","):
        name, _, w = part.partition("=")
        name = name.strip()
        if name not in OP_CLASSES:
            raise ValueError(f"unknown op class {name!r} in mix")
        mix[name] = float(w)
    total = sum(mix.values())
    if total <= 0:
        raise ValueError("mix weights must sum > 0")
    return {k: v / total for k, v in mix.items()}


def op_stream(mix: Dict[str, float], n: int, seed: int) -> List[str]:
    """Deterministic op-class assignment for ``n`` arrivals."""
    rng = random.Random(seed)
    names = list(mix)
    weights = [mix[k] for k in names]
    return rng.choices(names, weights=weights, k=n)


def percentile(sorted_vals: List[float], pct: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(len(sorted_vals) * pct / 100.0))
    return sorted_vals[idx]


# ---------------------------------------------------------------------------
# keys & values (deterministic: spot-checkable under concurrent puts)
# ---------------------------------------------------------------------------


def key_of(gid: int) -> bytes:
    return b"k%08d" % gid


def shard_of(gid: int, shards: int) -> int:
    return gid % shards


def preload_value(gid: int, value_bytes: int) -> bytes:
    v = b"l%08d." % gid
    return (v * (value_bytes // len(v) + 1))[:value_bytes]


def put_value(gid: int, value_bytes: int) -> bytes:
    v = b"p%08d." % gid
    return (v * (value_bytes // len(v) + 1))[:value_bytes]


# ---------------------------------------------------------------------------
# --serve child: one replica process (leader preloads, followers catch up)
# ---------------------------------------------------------------------------


def serve(args) -> int:
    from rocksplicator_tpu.replication import (ReplicaRole,
                                               ReplicationFlags,
                                               Replicator,
                                               StorageDbWrapper)
    from rocksplicator_tpu.storage import DB, DBOptions, WriteBatch
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    flags = ReplicationFlags(
        server_long_poll_ms=1000,
        ack_timeout_ms=2000,
        write_window=args.write_window,
        # TTL above the long-poll period: an IDLE follower's estimate
        # refreshes on every long-poll expiry (~1s), so bounded reads in
        # a read-only phase serve without probing; the staleness window
        # a client buys is max_lag seqs + this TTL of time
        read_info_ttl_ms=args.read_info_ttl_ms,
        pull_error_delay_min_ms=50,
        pull_error_delay_max_ms=250,
    )
    # Per-shard assignment: the legacy 3-replica shape (one role, every
    # shard, one upstream) or — round 22, the fleet topology — an
    # explicit ``--topo`` JSON list of [shard, role, upstream_port]
    # giving THIS node's hosted subset (leaders and followers mixed, a
    # different upstream peer per shard).
    if args.topo:
        assign = [(int(s), ReplicaRole[r.upper()],
                   ("127.0.0.1", int(up)) if up else None)
                  for s, r, up in json.loads(args.topo)]
    else:
        role = (ReplicaRole.LEADER if args.serve == "leader"
                else ReplicaRole.FOLLOWER)
        upstream = (("127.0.0.1", args.upstream_port)
                    if args.upstream_port else None)
        assign = [(s, role, upstream) for s in range(args.shards)]
    replicator = Replicator(port=args.port, flags=flags,
                            executor_threads=args.executor_threads)
    handler = admin_server = None
    if args.db_profile == "churn":
        # compaction-pressure profile (the --sched_ab arms): small
        # memtables + low L0 triggers + small files so the write-heavy
        # mix accumulates REAL L0 debt; whether the adaptive scheduler
        # acts on it comes from the inherited RSTPU_COMPACTION_SCHED
        db_options = lambda _seg: DBOptions(  # noqa: E731
            wal_ttl_seconds=3600.0,
            background_compaction=True,
            memtable_bytes=24 * 1024,
            level0_compaction_trigger=4,
            level0_slowdown_writes_trigger=8,
            level0_stop_writes_trigger=16,
            target_file_bytes=48 * 1024,
            max_bytes_for_level_base=96 * 1024,
        )
    else:
        db_options = lambda _seg: DBOptions(  # noqa: E731
            wal_ttl_seconds=3600.0)
    if args.admin_port:
        # the live-move variant: this replica also speaks the Admin RPC
        # plane (backup/restore/pause/role-change) so a DirectShardMove
        # can relocate a shard mid-bench; restored dbs must come up in
        # the same semi-sync mode the bench registers explicitly
        from rocksplicator_tpu.admin.handler import AdminHandler
        from rocksplicator_tpu.rpc.server import RpcServer
        from rocksplicator_tpu.utils.dbconfig import DBConfigManager

        DBConfigManager.get().load_from_dict(
            {SEGMENT: {"replication_mode": 1}})
        handler = AdminHandler(args.db_dir, replicator,
                               options_generator=db_options)
        admin_server = RpcServer(port=args.admin_port,
                                 ioloop=replicator.ioloop)
        admin_server.add_handler(handler)
        admin_server.start()
    dbs = []
    for s, role, upstream in assign:
        name = segment_to_db_name(SEGMENT, s)
        db = DB(os.path.join(args.db_dir, name), db_options(SEGMENT))
        if role is ReplicaRole.LEADER and args.preload_keys:
            # preload BEFORE replication registration: engine writes go
            # straight to the WAL, followers replay them on first pull.
            # gids are dealt round-robin across the TOTAL shard count
            # (shard = gid % --shards), so each leader preloads exactly
            # its residue class
            batch = None
            for gid in range(s, args.shards * args.preload_keys,
                             args.shards):
                if batch is None:
                    batch = WriteBatch()
                batch.put(key_of(gid), preload_value(gid, args.value_bytes))
                if batch.count() >= 64:
                    db.write(batch)
                    batch = None
            if batch is not None:
                db.write(batch)
        dbs.append(db)
        if handler is not None:
            # register through the admin plane (ApplicationDB) so move
            # RPCs and the replication plane see the same instance
            from rocksplicator_tpu.admin.application_db import \
                ApplicationDB

            app_db = ApplicationDB(name, db, role, replicator=replicator,
                                   upstream_addr=upstream,
                                   replication_mode=1)
            handler.db_manager.add_db(name, app_db)
        else:
            replicator.add_db(name, StorageDbWrapper(db), role,
                              upstream_addr=upstream, replication_mode=1)
    print(f"READY role={args.serve} port={replicator.port} "
          f"shards={len(assign)}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    try:
        while not stop.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    if admin_server is not None:
        admin_server.stop()
    if handler is not None:
        handler.close()
    replicator.stop()
    for db in dbs:
        if handler is None:
            db.close()  # admin-managed dbs were closed by handler.close
    return 0


# ---------------------------------------------------------------------------
# driver: cluster orchestration
# ---------------------------------------------------------------------------


def reserve_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def build_router(ports: List[int], shards: int):
    """Router + pool over the 3-replica layout (leader = ports[0]).
    Shared by the driver and the A/B worker processes."""
    from rocksplicator_tpu.rpc.client_pool import RpcClientPool
    from rocksplicator_tpu.rpc.ioloop import IoLoop
    from rocksplicator_tpu.rpc.router import ClusterLayout, RpcRouter

    layout: Dict = {SEGMENT: {"num_shards": shards}}
    marks = {0: "M", 1: "S", 2: "S"}
    for i, port in enumerate(ports):
        layout[SEGMENT][f"127.0.0.1:{port}:az-n{i}:{port}"] = [
            f"{s:05d}:{marks[i]}" for s in range(shards)]
    pool = RpcClientPool()
    router = RpcRouter(local_az="az-n0", pool=pool)
    router.update_layout(ClusterLayout.parse(json.dumps(layout).encode()))
    return IoLoop.default(), pool, router


class Cluster:
    """1 leader + 2 followers as OS processes, plus the router/pool the
    driver issues RPCs through. With ``with_move_node`` the children
    also serve the Admin RPC plane and a 4th (initially empty) node is
    spawned — the destination a mid-bench DirectShardMove relocates a
    shard onto."""

    def __init__(self, root: str, shards: int, preload_keys: int,
                 value_bytes: int, write_window: int,
                 read_info_ttl_ms: int, transport: str,
                 executor_threads: int, with_move_node: bool = False,
                 db_profile: str = "default",
                 extra_env: Optional[Dict[str, str]] = None,
                 with_admin: bool = False):
        self.shards = shards
        self.with_move_node = with_move_node
        self._moved: Dict[int, int] = {}  # shard -> current leader idx
        self.procs: List[subprocess.Popen] = []
        n = 4 if with_move_node else 3
        self.ports = [reserve_port() for _ in range(n)]
        # with_admin: admin RPC plane on the 3 replicas WITHOUT the 4th
        # move-destination node (the --cdc mode drives
        # start_message_ingestion against the leader's admin port)
        self.admin_ports = ([reserve_port() for _ in range(n)]
                            if (with_move_node or with_admin) else [])
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   RSTPU_TRANSPORT=transport)
        env.update(extra_env or {})

        def spawn(role: str, idx: int, upstream: int,
                  node_shards: int) -> subprocess.Popen:
            port = self.ports[idx]
            cmd = [
                sys.executable, "-m", "benchmarks.macro_bench",
                "--serve", role, "--port", str(port),
                "--shards", str(node_shards),
                "--db_dir", os.path.join(root, f"{role}{port}"),
                "--preload_keys", str(preload_keys),
                "--value_bytes", str(value_bytes),
                "--write_window", str(write_window),
                "--read_info_ttl_ms", str(read_info_ttl_ms),
                "--executor_threads", str(executor_threads),
                "--db_profile", db_profile,
            ]
            if self.admin_ports:
                cmd += ["--admin_port", str(self.admin_ports[idx])]
            if upstream:
                cmd += ["--upstream_port", str(upstream)]
            return subprocess.Popen(
                cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True, env=env,
                cwd=os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))))

        self.procs.append(spawn("leader", 0, 0, shards))
        self._wait_ready(self.procs[0], "leader")
        for i in (1, 2):
            self.procs.append(spawn("follower", i, self.ports[0],
                                    shards))
        if with_move_node:
            # the move destination: admin plane up, zero shards hosted
            self.procs.append(spawn("follower", 3, self.ports[0], 0))
        for p in self.procs[1:]:
            self._wait_ready(p, "follower")

        # per-process transport policy must match the children's
        os.environ["RSTPU_TRANSPORT"] = transport
        self.ioloop, self.pool, self.router = build_router(
            self.ports[:3], shards)

    def apply_move_layout(self, shard: int, new_leader_idx: int) -> None:
        """Re-teach the driver's router after a completed shard move:
        ``shard``'s leader is now node ``new_leader_idx`` (what the
        shardmap-agent file refresh does for real clients). CUMULATIVE:
        every move applied so far stays applied — the hot-shift
        rebalancer arm relocates several shards in one run, and a
        rebuild that forgot an earlier move would route that shard back
        to its RETIRED old leader."""
        from rocksplicator_tpu.rpc.router import ClusterLayout

        self._moved[shard] = new_leader_idx
        layout: Dict = {SEGMENT: {"num_shards": self.shards}}
        marks = {0: "M", 1: "S", 2: "S", 3: None}
        for i, port in enumerate(self.ports):
            entries = []
            for s in range(self.shards):
                moved_to = self._moved.get(s)
                if moved_to is not None:
                    # moved shard: leader on its new node, the two
                    # surviving followers unchanged, old leader retired
                    if i == moved_to:
                        mark = "M"
                    elif i in (1, 2):
                        mark = "S"
                    else:
                        mark = None
                else:
                    mark = marks[i]
                if mark:
                    entries.append(f"{s:05d}:{mark}")
            if entries:
                layout[SEGMENT][
                    f"127.0.0.1:{port}:az-n{i}:{port}"] = entries
        self.router.update_layout(
            ClusterLayout.parse(json.dumps(layout).encode()))

    @staticmethod
    def _wait_ready(proc: subprocess.Popen, what: str,
                    timeout: float = 120.0) -> None:
        import select

        # select before readline: a child that hangs BEFORE printing
        # READY (stale engine lock, import deadlock) must trip the
        # deadline, not block the whole bench on a parked readline
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            ready, _, _ = select.select([proc.stdout], [], [], 1.0)
            if not ready:
                if proc.poll() is not None:
                    raise RuntimeError(f"{what} exited before READY "
                                       f"(rc={proc.poll()})")
                continue
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError(f"{what} exited before READY "
                                   f"(rc={proc.poll()})")
            if line.startswith("READY"):
                log(f"  {line.strip()}")
                return
        raise RuntimeError(f"{what} not READY within {timeout}s")

    def wait_catchup(self, total_keys: int, timeout: float = 120.0) -> None:
        """Every follower must serve a max_lag=0 read of the last
        preloaded key of EVERY shard before the timed phases start (a
        single-shard probe would let still-replaying shards bounce
        bounded reads into the first sweep point and skew it) — also
        the first exercise of the bounded read path end to end."""
        from rocksplicator_tpu.rpc.errors import RpcError
        from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

        # last preloaded gid per shard: gids are dealt round-robin
        # (shard = gid % shards), so walk back from the end
        last_gids = {}
        for gid in range(total_keys - 1, total_keys - 1 - self.shards, -1):
            if gid >= 0:
                last_gids[shard_of(gid, self.shards)] = gid

        async def probe(port: int, shard: int, gid: int):
            return await self.pool.call(
                "127.0.0.1", port, "read",
                {"db_name": segment_to_db_name(SEGMENT, shard),
                 "op": "get", "keys": [key_of(gid)], "max_lag": 0},
                timeout=5.0)

        deadline = time.monotonic() + timeout
        # replicas only — the move-phase spare node (ports[3]) hosts
        # nothing until a move lands on it
        for port in self.ports[1:3]:
            for shard, gid in sorted(last_gids.items()):
                while True:
                    try:
                        r = self.ioloop.run_sync(
                            probe(port, shard, gid), timeout=10)
                        if r["values"][0] is not None:
                            break
                    except RpcError:
                        pass
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"follower :{port} shard {shard} never "
                            f"caught up ({timeout}s)")
                    time.sleep(0.25)
        log("  followers caught up (max_lag=0 reads served on "
            f"{len(last_gids)} shards)")

    def stop(self) -> None:
        try:
            self.ioloop.run_sync(self.pool.close(), timeout=10)
        except Exception:
            pass
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# open-loop mixed-workload phase
# ---------------------------------------------------------------------------


class PhaseResult:
    def __init__(self) -> None:
        self.lat: Dict[str, List[float]] = {op: [] for op in OP_CLASSES}
        self.errors: Dict[str, int] = {op: 0 for op in OP_CLASSES}
        self.bounced = 0
        self.by_role: Dict[str, int] = {}
        self.value_mismatches = 0

    def summarize(self, offered: float, duration: float) -> Dict:
        ops = {}
        completed = 0
        for op in OP_CLASSES:
            vals = sorted(self.lat[op])
            completed += len(vals)
            if not vals and not self.errors[op]:
                continue
            ops[op] = {
                "count": len(vals),
                "errors": self.errors[op],
                "p50_ms": round(percentile(vals, 50), 3),
                "p90_ms": round(percentile(vals, 90), 3),
                "p99_ms": round(percentile(vals, 99), 3),
                "mean_ms": round(sum(vals) / len(vals), 3) if vals else None,
            }
        return {
            "offered_per_sec": offered,
            "duration_sec": duration,
            "achieved_per_sec": round(completed / duration, 1),
            "ops": ops,
            "reads_by_role": dict(self.by_role),
            "read_bounces": self.bounced,
            "value_mismatches": self.value_mismatches,
        }


async def _run_open_loop(cluster: Cluster, policy, rate: float,
                         duration: float, total_keys: int,
                         value_bytes: int, mix: Dict[str, float],
                         seed: int, max_inflight: int,
                         server_get_sink: Optional[List[float]] = None,
                         sample_log: Optional[List] = None,
                         gid_source=None,
                         acked_puts: Optional[set] = None
                         ) -> PhaseResult:
    from rocksplicator_tpu.rpc.errors import RpcError
    from rocksplicator_tpu.storage import WriteBatch

    res = PhaseResult()
    arrivals = poisson_arrivals(rate, duration, seed)
    opnames = op_stream(mix, len(arrivals), seed + 1)
    zipf = ZipfianGenerator(total_keys, seed=seed + 2)
    shards = cluster.shards
    router = cluster.router
    loop = asyncio.get_running_loop()
    base_bounces = _router_bounces(cluster)
    sem = asyncio.Semaphore(max_inflight)
    expect = {}  # gid -> allowed values, lazily built for spot checks

    def allowed(gid: int):
        vals = expect.get(gid)
        if vals is None:
            vals = expect[gid] = (preload_value(gid, value_bytes),
                                  put_value(gid, value_bytes))
        return vals

    async def one_op(intended: float, op: str, gid: int):
        async with sem:
            try:
                if op == "put":
                    batch = WriteBatch().put(
                        key_of(gid), put_value(gid, value_bytes))
                    await router.write(SEGMENT, shard_of(gid, shards),
                                       batch.encode(), timeout=15.0)
                    if acked_puts is not None:
                        # durably acked: the hot-shift gate reads every
                        # one of these back after the run — a key that
                        # lost its put across a policy-driven move is
                        # an acked-write loss
                        acked_puts.add(gid)
                else:
                    if op == "get":
                        args = {"keys": [key_of(gid)]}
                    elif op == "multi_get":
                        # step by `shards`: gids are dealt round-robin
                        # (shard = gid % shards), so only same-residue
                        # keys live on the routed shard — stepping by 1
                        # would benchmark 3/4 guaranteed misses
                        args = {"keys": [
                            key_of((gid + j * shards) % total_keys)
                            for j in range(4)]}
                    else:  # scan
                        args = {"start": key_of(gid), "count": 10}
                    r = await router.read(
                        SEGMENT, shard_of(gid, shards), op=op,
                        policy=policy, timeout=15.0, **args)
                    role = r.get("source_role") or "?"
                    res.by_role[role] = res.by_role.get(role, 0) + 1
                    if op == "get" and server_get_sink is not None \
                            and r.get("serve_ms") is not None:
                        # server-reported serve time: the exact samples
                        # the fleet histogram buckets — the p99
                        # agreement check's bench side
                        server_get_sink.append(float(r["serve_ms"]))
                    if op == "get":
                        got = r["values"][0]
                        got = bytes(got) if got is not None else None
                        if got not in allowed(gid):
                            res.value_mismatches += 1
            except RpcError:
                res.errors[op] += 1
                if sample_log is not None:
                    sample_log.append((loop.time(), op, None))
                return
            # OPEN-LOOP latency: completion minus INTENDED arrival, so
            # dispatcher/queue delay counts against the server, not the
            # next request's budget
            lat_ms = (loop.time() - intended) * 1000.0
            res.lat[op].append(lat_ms)
            if sample_log is not None:
                # (completion time, op, latency) — the move phase
                # windows samples into before/during/after the flip
                sample_log.append((loop.time(), op, lat_ms))

    next_gid = gid_source or zipf.next
    t0 = loop.time()
    tasks = []
    for off, op in zip(arrivals, opnames):
        delay = (t0 + off) - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            one_op(t0 + off, op, next_gid())))
    if tasks:
        await asyncio.wait(tasks)
    res.bounced = int(_router_bounces(cluster) - base_bounces)
    return res


def _router_bounces(cluster) -> float:
    from rocksplicator_tpu.rpc.router import _READ_BOUNCE_CODES
    from rocksplicator_tpu.utils.stats import Stats

    total = 0.0
    stats = Stats.get()
    for code in _READ_BOUNCE_CODES:  # derived: can't drift from router
        total += stats.get_counter(
            f"router.read_bounces code={code.lower()}")
    return total


def run_phase(cluster: Cluster, policy, rate: float, duration: float,
              total_keys: int, value_bytes: int, mix: Dict[str, float],
              seed: int, max_inflight: int,
              server_get_sink: Optional[List[float]] = None) -> Dict:
    res = cluster.ioloop.run_sync(
        _run_open_loop(cluster, policy, rate, duration, total_keys,
                       value_bytes, mix, seed, max_inflight,
                       server_get_sink=server_get_sink),
        timeout=duration + 120)
    return res.summarize(rate, duration)


def run_move_phase(cluster: Cluster, root: str, policy, rate: float,
                   duration: float, total_keys: int, value_bytes: int,
                   mix: Dict[str, float], seed: int,
                   max_inflight: int) -> Dict:
    """One long open-loop phase (3 windows of ``duration``) with a LIVE
    leader move of shard 0 onto the spare node launched at the 1/3
    mark: snapshot → bulk-ingest → WAL-tail catch-up → paused cutover →
    epoch-stamped promote (DirectShardMove). Samples are windowed into
    before/during/after the move so the artifact records what a live
    move costs the serving p99 — the acceptance number for this
    scenario. Reads keep serving throughout (bounded-staleness reads
    bounce off the moving replica to its peers); writes see a brief
    WRITE_PAUSED/repoint window, counted as errors, then resume on the
    new leader."""
    from rocksplicator_tpu.cluster.shard_move import (DirectMovePlan,
                                                      DirectNode,
                                                      DirectShardMove,
                                                      MoveFlags)
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    sample_log: List = []
    move_info: Dict = {}

    def node(i: int) -> DirectNode:
        return DirectNode("127.0.0.1", cluster.admin_ports[i],
                          cluster.ports[i])

    def mover():
        time.sleep(duration)
        move_info["t_start"] = time.monotonic()
        try:
            plan = DirectMovePlan(
                db_name=segment_to_db_name(SEGMENT, 0),
                source=node(0), target=node(3), leader=node(0),
                followers=[node(1), node(2)],
                store_uri=os.path.join(root, "move-bucket"))
            timings = DirectShardMove(plan, flags=MoveFlags(
                catchup_lag_threshold=32, catchup_timeout=60.0,
                cutover_pause_ms=3000.0, poll_interval=0.05)).run()
            move_info.update(ok=True, timings_ms=timings)
        except Exception as e:
            move_info.update(ok=False, error=repr(e))
        move_info["t_end"] = time.monotonic()
        if move_info.get("ok"):
            # what the shardmap-agent file refresh does for real
            # clients: shard 0's leader is the spare node now
            cluster.apply_move_layout(0, 3)

    th = threading.Thread(target=mover, name="bench-mover", daemon=True)
    th.start()
    res = cluster.ioloop.run_sync(
        _run_open_loop(cluster, policy, rate, duration * 3, total_keys,
                       value_bytes, mix, seed, max_inflight,
                       sample_log=sample_log),
        timeout=duration * 3 + 180)
    th.join(timeout=120)
    t_start = move_info.get("t_start")
    t_end = move_info.get("t_end")
    inf = float("inf")
    windows: Dict[str, Dict] = {}
    for name, lo, hi in (("before", -inf, t_start or inf),
                         ("during", t_start or inf, t_end or inf),
                         ("after", t_end or inf, inf)):
        gets = sorted(lat for ts, op, lat in sample_log
                      if op == "get" and lat is not None
                      and lo <= ts < hi)
        windows[name] = {
            "get_count": len(gets),
            "get_errors": sum(1 for ts, op, lat in sample_log
                              if op == "get" and lat is None
                              and lo <= ts < hi),
            "get_p50_ms": round(percentile(gets, 50), 3) if gets else None,
            "get_p99_ms": round(percentile(gets, 99), 3) if gets else None,
            "put_count": sum(1 for ts, op, lat in sample_log
                             if op == "put" and lat is not None
                             and lo <= ts < hi),
            "put_errors": sum(1 for ts, op, lat in sample_log
                              if op == "put" and lat is None
                              and lo <= ts < hi),
        }
    return {
        "move": {k: move_info.get(k)
                 for k in ("ok", "error", "timings_ms")},
        "move_duration_ms": (round((t_end - t_start) * 1000.0, 1)
                             if t_start and t_end else None),
        "windows": windows,
        "phase": res.summarize(rate, duration * 3),
    }


# ---------------------------------------------------------------------------
# read-policy A/B (closed-loop saturation: the read-scaling number)
# ---------------------------------------------------------------------------


async def _run_read_saturation(cluster: Cluster, policy, duration: float,
                               total_keys: int, readers: int,
                               seed: int) -> Dict[str, float]:
    from rocksplicator_tpu.rpc.errors import RpcError

    zipf = ZipfianGenerator(total_keys, seed=seed)
    shards = cluster.shards
    router = cluster.router
    loop = asyncio.get_running_loop()
    lats: List[float] = []
    errors = [0]
    by_role: Dict[str, int] = {}
    stop_at = loop.time() + duration

    async def reader():
        while loop.time() < stop_at:
            gid = zipf.next()
            t1 = loop.time()
            try:
                r = await router.read(SEGMENT, shard_of(gid, shards),
                                      op="get", keys=[key_of(gid)],
                                      policy=policy, timeout=15.0)
            except RpcError:
                errors[0] += 1
                continue
            lats.append((loop.time() - t1) * 1000.0)
            role = r.get("source_role") or "?"
            by_role[role] = by_role.get(role, 0) + 1

    await asyncio.gather(*[reader() for _ in range(readers)])
    lats.sort()
    return {
        "reads_per_sec": round(len(lats) / duration, 1),
        "p50_ms": round(percentile(lats, 50), 3),
        "p99_ms": round(percentile(lats, 99), 3),
        "errors": float(errors[0]),
        "follower_share": round(
            by_role.get("FOLLOWER", 0) / max(1, len(lats)), 3),
    }


def ab_worker(args) -> int:
    """One closed-loop reader-fleet process (A/B child mode): saturates
    the cluster with gets under one read policy and prints one JSON
    line. Run as a process fleet so the CLIENT side scales past one
    Python interpreter's GIL — otherwise the A/B measures the driver,
    not the replicas."""
    from rocksplicator_tpu.rpc.router import ReadPolicy

    ports = [int(x) for x in args.ports.split(",")]
    policy = (ReadPolicy.leader_only() if args.ab_worker == "leader_only"
              else ReadPolicy.follower_ok(args.max_lag))
    ioloop, pool, _router = build_router(ports, args.shards)
    total_keys = args.shards * args.preload_keys
    cluster_view = _WorkerView(_router, args.shards, ioloop, pool)
    out = ioloop.run_sync(
        _run_read_saturation(cluster_view, policy, args.ab_duration,
                             total_keys, args.ab_readers, args.seed),
        timeout=args.ab_duration + 60)
    ioloop.run_sync(pool.close(), timeout=10)
    print(json.dumps(out), flush=True)
    return 0


class _WorkerView:
    """The slice of Cluster the saturation loop needs."""

    def __init__(self, router, shards, ioloop, pool):
        self.router = router
        self.shards = shards
        self.ioloop = ioloop
        self.pool = pool


def run_read_ab(cluster: Cluster, max_lag: int, duration: float,
                shards: int, preload_keys: int, readers: int,
                procs: int, reps: int, seed: int,
                transport: str) -> Dict:
    """Interleaved leader_only vs follower_ok saturation, each variant a
    FLEET of ``procs`` closed-loop worker processes (sum of reads/s;
    p99 reported as the worst worker's — conservative)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", RSTPU_TRANSPORT=transport)
    ports_arg = ",".join(str(p) for p in cluster.ports)

    def fleet(kind: str):
        def run():
            cmds = []
            for w in range(procs):
                cmds.append(subprocess.Popen(
                    [sys.executable, "-m", "benchmarks.macro_bench",
                     "--ab_worker", kind, "--ports", ports_arg,
                     "--shards", str(shards),
                     "--preload_keys", str(preload_keys),
                     "--max_lag", str(max_lag),
                     "--ab_duration", str(duration),
                     "--ab_readers", str(readers),
                     "--seed", str(seed + w * 7919)],
                    stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                    text=True, env=env,
                    cwd=os.path.dirname(os.path.dirname(
                        os.path.abspath(__file__)))))
            outs = []
            for p in cmds:
                stdout, _ = p.communicate(timeout=duration + 120)
                if p.returncode != 0:
                    raise RuntimeError(
                        f"ab worker rc={p.returncode}")
                outs.append(json.loads(stdout.strip().splitlines()[-1]))
            n = sum(o["reads_per_sec"] * duration for o in outs)
            return {
                "reads_per_sec": round(
                    sum(o["reads_per_sec"] for o in outs), 1),
                "p50_ms": round(sorted(
                    o["p50_ms"] for o in outs)[len(outs) // 2], 3),
                "p99_ms": round(max(o["p99_ms"] for o in outs), 3),
                "errors": sum(o["errors"] for o in outs),
                "follower_share": round(
                    sum(o["follower_share"] * o["reads_per_sec"]
                        for o in outs)
                    / max(1e-9, sum(o["reads_per_sec"] for o in outs)), 3),
                "worker_procs": procs,
                "total_reads": int(n),
            }
        return run

    return run_interleaved(
        [("leader_only", fleet("leader_only")),
         ("follower_ok", fleet("follower_ok"))],
        reps=reps, key="reads_per_sec")


# ---------------------------------------------------------------------------
# compaction-scheduler A/B (round 16: whole-cluster, serving-SLO number)
# ---------------------------------------------------------------------------


def run_sched_ab(args) -> Dict:
    """Interleaved A/B of the workload-adaptive compaction scheduler
    UNDER the macro-bench: each rep boots a FRESH 3-process cluster per
    arm — children inherit ``RSTPU_COMPACTION_SCHED`` (1 vs 0) and run
    the ``churn`` engine profile (small memtables, low L0 triggers) so
    the write-heavy mix accumulates real L0 debt — then runs one
    open-loop mixed phase at the SAME offered throughput and scrapes
    the leader's ``stats`` RPC for the scheduler counters and
    write-stall totals. Lower get p99 is better."""
    import shutil
    import tempfile

    from rocksplicator_tpu.rpc.router import ReadPolicy

    mix = parse_mix(args.sched_mix)
    total_keys = args.shards * args.preload_keys
    policy = ReadPolicy.follower_ok(args.max_lag)
    rep_no = [0]

    def arm(sched: str):
        name = "sched_on" if sched == "1" else "sched_off"

        def run() -> Dict:
            rep_no[0] += 1
            root = tempfile.mkdtemp(prefix="rstpu-macro-sched-")
            cluster = None
            try:
                log(f"sched_ab[{name}]: booting churn cluster "
                    f"(RSTPU_COMPACTION_SCHED={sched})")
                cluster = Cluster(
                    root, args.shards, args.preload_keys,
                    args.value_bytes, args.write_window,
                    args.read_info_ttl_ms, args.transport,
                    args.executor_threads, db_profile="churn",
                    extra_env={"RSTPU_COMPACTION_SCHED": sched})
                cluster.wait_catchup(total_keys)
                phase = run_phase(
                    cluster, policy, args.sched_rate,
                    args.sched_duration, total_keys, args.value_bytes,
                    mix, args.seed + 77 * rep_no[0], args.max_inflight)

                async def scrape(port: int):
                    return await cluster.pool.call(
                        "127.0.0.1", port, "stats", {}, timeout=10.0)

                # fleet totals: every replica compacts (followers apply
                # the same write stream), so stalls/picks sum across
                # all three processes
                counters: Dict[str, float] = {}
                stall_sum, stall_count = 0.0, 0
                for port in cluster.ports[:3]:
                    st = cluster.ioloop.run_sync(scrape(port), timeout=15)
                    for k, v in (st.get("counters") or {}).items():
                        counters[k] = counters.get(k, 0.0) + v["total"]
                    rec = (st.get("metrics") or {}).get(
                        "storage.write_stall_ms") or {}
                    stall_sum += float(rec.get("sum", 0.0))
                    stall_count += int(rec.get("count", 0))

                def csum(prefix: str) -> int:
                    return int(sum(v for k, v in counters.items()
                                   if k.startswith(prefix)))

                g = phase["ops"].get("get") or {}
                pw = phase["ops"].get("put") or {}
                return {
                    "get_p99_ms": g.get("p99_ms"),
                    "get_p50_ms": g.get("p50_ms"),
                    "put_p99_ms": pw.get("p99_ms"),
                    "achieved_per_sec": phase["achieved_per_sec"],
                    "get_errors": g.get("errors", 0),
                    "put_errors": pw.get("errors", 0),
                    "value_mismatches": phase["value_mismatches"],
                    "fleet_write_stall_ms": round(stall_sum, 1),
                    "fleet_write_stalls": stall_count,
                    "compaction.sched_picks": csum(
                        "compaction.sched_picks"),
                    "compaction.yields": csum("compaction.yields"),
                    "compaction.subcompactions": csum(
                        "compaction.subcompactions"),
                }
            finally:
                if cluster is not None:
                    cluster.stop()
                shutil.rmtree(root, ignore_errors=True)
        return run

    return run_interleaved(
        [("sched_off", arm("0")), ("sched_on", arm("1"))],
        reps=args.sched_reps, key="get_p99_ms", higher_is_better=False,
        log=log)


# ---------------------------------------------------------------------------
# overload A/B (round 19: tail armor — deadlines, admission, hedging)
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _bench_env(**overrides: str):
    """Set/restore env in the BENCH process: the client half of the
    tail armor (deadline stamping, hedging) reads env here, not in the
    children — an A/B that only flips the children's env would measure
    half the killswitch."""
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _scrape_counter_sums(cluster: "Cluster",
                         prefixes: Tuple[str, ...]) -> Dict[str, float]:
    """Fleet totals of every stats counter under the given prefixes."""

    async def scrape(port: int):
        return await cluster.pool.call("127.0.0.1", port, "stats", {},
                                       timeout=10.0)

    sums: Dict[str, float] = {}
    for port in cluster.ports[:3]:
        st = cluster.ioloop.run_sync(scrape(port), timeout=15)
        for k, v in (st.get("counters") or {}).items():
            if k.startswith(prefixes):
                sums[k] = sums.get(k, 0.0) + v["total"]
    return sums


async def _run_tenant_loop(cluster: "Cluster",
                           tenant_rates: Dict[str, float],
                           duration: float, total_keys: int,
                           seed: int, max_inflight: int,
                           deadline_ms: float) -> Dict:
    """Open-loop per-tenant get storm at the LEADER (one admission
    point, so "10x quota" means what it says): every op runs under
    ``request_scope`` so the client stamps the tenant tag and a
    relative deadline budget — exactly what an armored application
    client does. Typed sheds (RETRY_LATER / DEADLINE_EXCEEDED) are
    counted per tenant, NOT as errors: shedding is the armor working.
    Latency is open-loop (completion minus intended arrival), so the
    OFF arm's queue explosion lands in the percentiles."""
    from rocksplicator_tpu.rpc.deadline import (DEADLINE_EXCEEDED,
                                                RETRY_LATER, Deadline,
                                                request_scope)
    from rocksplicator_tpu.rpc.errors import RpcApplicationError, RpcError
    from rocksplicator_tpu.rpc.router import ReadPolicy

    policy = ReadPolicy.leader_only()
    arrivals: List[Tuple[float, str]] = []
    for i, (tenant, rate) in enumerate(sorted(tenant_rates.items())):
        for off in poisson_arrivals(rate, duration, seed + 31 * i):
            arrivals.append((off, tenant))
    arrivals.sort()
    zipf = ZipfianGenerator(total_keys, seed=seed + 7)
    shards = cluster.shards
    router = cluster.router
    loop = asyncio.get_running_loop()
    sem = asyncio.Semaphore(max_inflight)
    per: Dict[str, Dict] = {
        t: {"lat": [], "shed": 0, "deadline_shed": 0, "errors": 0}
        for t in tenant_rates}

    async def one_op(intended: float, tenant: str, gid: int):
        rec = per[tenant]
        async with sem:
            try:
                with request_scope(
                        deadline=Deadline.after_ms(deadline_ms),
                        tenant=tenant):
                    await router.read(SEGMENT, shard_of(gid, shards),
                                      op="get", keys=[key_of(gid)],
                                      policy=policy, timeout=15.0)
            except RpcApplicationError as e:
                if e.code == RETRY_LATER:
                    rec["shed"] += 1
                elif e.code == DEADLINE_EXCEEDED:
                    rec["deadline_shed"] += 1
                else:
                    rec["errors"] += 1
                return
            except RpcError:
                rec["errors"] += 1
                return
            rec["lat"].append((loop.time() - intended) * 1000.0)

    t0 = loop.time()
    tasks = []
    for off, tenant in arrivals:
        delay = (t0 + off) - loop.time()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.ensure_future(
            one_op(t0 + off, tenant, zipf.next())))
    if tasks:
        await asyncio.wait(tasks)

    out: Dict[str, Dict] = {}
    for tenant, rec in per.items():
        vals = sorted(rec["lat"])
        out[tenant] = {
            "offered_per_sec": tenant_rates[tenant],
            "goodput_per_sec": round(len(vals) / duration, 1),
            "shed": rec["shed"],
            "deadline_shed": rec["deadline_shed"],
            "errors": rec["errors"],
            "p50_ms": round(percentile(vals, 50), 3) if vals else None,
            "p99_ms": round(percentile(vals, 99), 3) if vals else None,
            "p999_ms": round(percentile(vals, 99.9), 3) if vals else None,
            # raw samples ride along so the caller can POOL tenants
            # before taking a p99.9 (per-tenant sample counts are too
            # small for a stable 1-in-1000 quantile); popped before the
            # artifact is written
            "_raw": vals,
        }
    return out


def run_overload_ab(args) -> Dict:
    """The round-19 acceptance bench: three interleaved A/Bs, each arm
    on a FRESH 3-process cluster (armor knobs are process-env, and the
    OFF arm's queue backlog must not leak into the next arm).

    - ``tenant_ab`` — one abusive tenant offered 10x its ops/s quota
      plus well-behaved tenants (within quota), total offered past the
      serving knee, leader-only reads. armor_on children carry
      ``RSTPU_TENANT_OPS``; armor_off children (and the bench-side
      client) run ``RSTPU_TAIL_ARMOR=0``. The gate: the well-behaved
      tenants' pooled p99.9 with armor ON is strictly better than OFF,
      their goodput holds, and the abuser is the one shedding.
    - ``hedge_ab`` — a read-only follower_ok phase against a cluster
      whose replicas have a rare fat tail injected server-side
      (``repl.read=delay_ms`` failpoint via RSTPU_FAILPOINTS, armed at
      child import). RSTPU_HEDGE=1 vs 0 in the BENCH process (hedging
      is client-side). Gates: hedged get p99 strictly better, hedge
      rate within the 5% budget, zero hedges in the off arm.
    - ``overhead_ab`` — the unarmed-overhead guard: NO overload, no
      quotas, mixed get/put at a comfortable rate, RSTPU_TAIL_ARMOR
      1 vs 0 everywhere. Armed-but-idle stamping+checking must cost
      within host noise on the write path (gated as a mean-latency
      ratio bound).
    """
    import shutil
    import tempfile

    from rocksplicator_tpu.rpc.router import ReadPolicy
    from rocksplicator_tpu.utils.stats import Stats

    total_keys = args.shards * args.preload_keys
    quota = float(args.overload_quota)
    abuser_rate = 10.0 * quota
    tenant_rates = {"abuser": abuser_rate}
    good_tenants = [f"good{i}" for i in range(args.overload_good_tenants)]
    for t in good_tenants:
        tenant_rates[t] = float(args.overload_good_rate)
    rep_no = [0]

    def fresh_cluster(root: str, extra_env: Dict[str, str],
                      executor_threads: Optional[int] = None) -> Cluster:
        cluster = Cluster(root, args.shards, args.preload_keys,
                          args.value_bytes, args.write_window,
                          args.read_info_ttl_ms, args.transport,
                          executor_threads or args.executor_threads,
                          extra_env=extra_env)
        cluster.wait_catchup(total_keys)
        return cluster

    def tenant_arm(armor: str):
        name = f"armor_{armor}"

        def run() -> Dict:
            rep_no[0] += 1
            extra_env = ({"RSTPU_TAIL_ARMOR": "1",
                          "RSTPU_TENANT_OPS": str(quota)}
                         if armor == "on"
                         else {"RSTPU_TAIL_ARMOR": "0"})
            root = tempfile.mkdtemp(prefix="rstpu-overload-")
            cluster = None
            try:
                with _bench_env(
                        RSTPU_TAIL_ARMOR="1" if armor == "on" else "0"):
                    Stats.reset_for_test()
                    log(f"overload[{name}]: booting cluster "
                        f"(quota={quota if armor == 'on' else 'none'} "
                        f"ops/s, abuser offered={abuser_rate}/s)")
                    # narrow dispatch on purpose: the overload signal
                    # must come from the abuser monopolizing the
                    # server's executor queue, not from how close the
                    # host's raw CPU knee happens to sit to the
                    # offered rate that day. With one dispatch thread
                    # the OFF arm serializes the flood (queue-wait is
                    # the damage) while the ON arm sheds the abuser
                    # BEFORE dispatch, so the A/B tests the armor.
                    cluster = fresh_cluster(
                        root, extra_env,
                        executor_threads=args.tenant_executor_threads)
                    per_tenant = cluster.ioloop.run_sync(
                        _run_tenant_loop(
                            cluster, tenant_rates,
                            args.overload_duration, total_keys,
                            args.seed + 977 * rep_no[0],
                            args.max_inflight,
                            args.overload_deadline_ms),
                        timeout=args.overload_duration + 180)
                    server = _scrape_counter_sums(
                        cluster, ("rpc.tenant_shed", "rpc.tenant_served",
                                  "rpc.deadline_shed", "rpc.retry_later"))
                good_goodput = round(sum(
                    per_tenant[t]["goodput_per_sec"]
                    for t in good_tenants), 1)
                good_shed = sum(per_tenant[t]["shed"]
                                + per_tenant[t]["deadline_shed"]
                                for t in good_tenants)
                good_pool = sorted(
                    v for t in good_tenants
                    for v in per_tenant[t]["_raw"])
                for rec in per_tenant.values():
                    rec.pop("_raw", None)
                ab = per_tenant["abuser"]
                return {
                    "per_tenant": per_tenant,
                    "good_p999_ms": (round(percentile(good_pool, 99.9), 3)
                                     if good_pool else None),
                    "good_p99_ms": (round(percentile(good_pool, 99), 3)
                                    if good_pool else None),
                    "good_goodput_per_sec": good_goodput,
                    "good_offered_per_sec": round(sum(
                        tenant_rates[t] for t in good_tenants), 1),
                    "good_shed": good_shed,
                    "abuser_offered_per_sec": abuser_rate,
                    "abuser_goodput_per_sec": ab["goodput_per_sec"],
                    "abuser_shed": ab["shed"] + ab["deadline_shed"],
                    "errors": sum(per_tenant[t]["errors"]
                                  for t in per_tenant),
                    "server_counters": server,
                }
            finally:
                if cluster is not None:
                    cluster.stop()
                shutil.rmtree(root, ignore_errors=True)
        return run

    def hedge_arm(hedge: str):
        name = f"hedge_{hedge}"
        inject = (f"repl.read=delay_ms:{args.hedge_inject_ms}:"
                  f"{args.hedge_inject_prob}@seed{args.seed}")

        def run() -> Dict:
            rep_no[0] += 1
            root = tempfile.mkdtemp(prefix="rstpu-overload-")
            cluster = None
            try:
                with _bench_env(RSTPU_TAIL_ARMOR="1",
                                RSTPU_HEDGE=hedge):
                    Stats.reset_for_test()
                    log(f"overload[{name}]: booting cluster "
                        f"(server tail inject {inject})")
                    cluster = fresh_cluster(
                        root, {"RSTPU_FAILPOINTS": inject})
                    phase = run_phase(
                        cluster, ReadPolicy.follower_ok(args.max_lag),
                        args.hedge_read_rate, args.overload_duration,
                        total_keys, args.value_bytes, {"get": 1.0},
                        args.seed + 977 * rep_no[0], args.max_inflight)
                    stats = Stats.get()
                    stats.flush()
                    hedges = stats.get_counter("router.hedges op=get")
                    wins = stats.get_counter("router.hedge_wins op=get")
                    denied = stats.get_counter(
                        "router.hedge_budget_denied op=get")
                g = phase["ops"].get("get") or {}
                reads = g.get("count", 0) + g.get("errors", 0)
                return {
                    "get_p99_ms": g.get("p99_ms"),
                    "get_p50_ms": g.get("p50_ms"),
                    "get_count": g.get("count", 0),
                    "get_errors": g.get("errors", 0),
                    "value_mismatches": phase["value_mismatches"],
                    "hedges": int(hedges),
                    "hedge_wins": int(wins),
                    "hedge_budget_denied": int(denied),
                    "hedge_rate": round(hedges / max(1, reads), 4),
                }
            finally:
                if cluster is not None:
                    cluster.stop()
                shutil.rmtree(root, ignore_errors=True)
        return run

    def overhead_arm(armor: str):
        name = f"armor_{armor}"

        def run() -> Dict:
            rep_no[0] += 1
            root = tempfile.mkdtemp(prefix="rstpu-overload-")
            cluster = None
            try:
                with _bench_env(
                        RSTPU_TAIL_ARMOR="1" if armor == "on" else "0"):
                    Stats.reset_for_test()
                    log(f"overload[overhead {name}]: booting cluster")
                    cluster = fresh_cluster(
                        root,
                        {"RSTPU_TAIL_ARMOR":
                         "1" if armor == "on" else "0"})
                    phase = run_phase(
                        cluster, ReadPolicy.follower_ok(args.max_lag),
                        args.overhead_rate, args.overload_duration,
                        total_keys, args.value_bytes,
                        {"get": 0.5, "put": 0.5},
                        args.seed + 977 * rep_no[0], args.max_inflight)
                g = phase["ops"].get("get") or {}
                pw = phase["ops"].get("put") or {}
                return {
                    "put_mean_ms": pw.get("mean_ms"),
                    "put_p99_ms": pw.get("p99_ms"),
                    "get_mean_ms": g.get("mean_ms"),
                    "get_p99_ms": g.get("p99_ms"),
                    "put_errors": pw.get("errors", 0),
                    "get_errors": g.get("errors", 0),
                    "value_mismatches": phase["value_mismatches"],
                    "achieved_per_sec": phase["achieved_per_sec"],
                }
            finally:
                if cluster is not None:
                    cluster.stop()
                shutil.rmtree(root, ignore_errors=True)
        return run

    return {
        "tenant_ab": run_interleaved(
            [("armor_off", tenant_arm("off")),
             ("armor_on", tenant_arm("on"))],
            reps=args.overload_reps, key="good_p999_ms",
            higher_is_better=False, log=log),
        "hedge_ab": run_interleaved(
            [("hedge_off", hedge_arm("0")), ("hedge_on", hedge_arm("1"))],
            reps=args.overload_reps, key="get_p99_ms",
            higher_is_better=False, log=log),
        "overhead_ab": run_interleaved(
            [("armor_off", overhead_arm("off")),
             ("armor_on", overhead_arm("on"))],
            reps=args.overload_reps, key="put_mean_ms",
            higher_is_better=False, log=log),
    }


def _median_field(samples: List[Dict], field: str) -> Optional[float]:
    from statistics import median

    vals = [s[field] for s in samples or [] if s.get(field) is not None]
    return median(vals) if vals else None


def overload_failures(result: Dict,
                      mechanical_only: bool = False) -> List[str]:
    """The round-19 acceptance gates over the three A/B sections —
    medians across interleaved reps (the ab_runner discipline: per-rep
    comparisons on a drifting host gate the host, not the change).

    ``mechanical_only`` (the smoke's mode) keeps every deterministic
    gate — killswitch arms may not leak typed sheds or hedges, the
    quota must actually bite the abuser, hedges must fire inside their
    5% budget, zero value mismatches, and the armed good-tenant p99
    stays inside a deadline-derived absolute bound — but drops the
    latency-median A/B comparisons: on a 1-rep micro run the serving
    knee itself drifts run to run, so a strict p99.9 comparison gates
    the host, not the armor. The full ``make overload-bench`` runs
    every gate."""
    failures: List[str] = []
    oab = result.get("overload_ab") or {}

    t = oab.get("tenant_ab") or {}
    ts = t.get("samples") or {}
    on_p999 = _median_field(ts.get("armor_on"), "good_p999_ms")
    off_p999 = _median_field(ts.get("armor_off"), "good_p999_ms")
    if on_p999 is None or off_p999 is None:
        failures.append("tenant_ab: missing good-tenant p99.9 in an arm")
    elif not mechanical_only and not on_p999 < off_p999:
        failures.append(
            f"tenant_ab: good p99.9 armor_on {on_p999}ms not strictly "
            f"better than armor_off {off_p999}ms")
    on_good = _median_field(ts.get("armor_on"), "good_goodput_per_sec")
    off_good = _median_field(ts.get("armor_off"), "good_goodput_per_sec")
    if not mechanical_only and on_good is not None \
            and off_good is not None and on_good < 0.8 * off_good:
        failures.append(
            f"tenant_ab: good-tenant goodput collapsed under armor "
            f"({on_good}/s vs {off_good}/s off) — not graceful")
    # deadline enforcement bounds a SUCCESSFUL armed op's latency:
    # anything slower becomes a typed DEADLINE_EXCEEDED instead of a
    # latency sample. 2x the budget leaves room for the open-loop
    # intended-arrival anchor (client dispatch lag precedes the
    # deadline scope), but a p99 past that means the armor isn't
    # converting overload into typed sheds at all.
    budget_ms = (result.get("config") or {}).get("deadline_budget_ms")
    if budget_ms:
        for s in ts.get("armor_on") or []:
            p99 = s.get("good_p99_ms")
            if p99 is not None and p99 > 2.0 * float(budget_ms):
                failures.append(
                    f"tenant_ab: armed good-tenant p99 {p99}ms over "
                    f"the 2x deadline-budget bound "
                    f"({2.0 * float(budget_ms)}ms)")
    for s in ts.get("armor_on") or []:
        if s["abuser_shed"] <= 0:
            failures.append("tenant_ab: armor_on rep shed nothing "
                            "from the abuser")
        if s["abuser_goodput_per_sec"] > 0.35 * s["abuser_offered_per_sec"]:
            failures.append(
                f"tenant_ab: abuser goodput "
                f"{s['abuser_goodput_per_sec']}/s not held near its "
                f"quota (offered {s['abuser_offered_per_sec']}/s)")
    for s in ts.get("armor_off") or []:
        if s["abuser_shed"] + s["good_shed"] > 0:
            failures.append("tenant_ab: armor_off rep shed typed "
                            "errors (killswitch leak)")

    h = oab.get("hedge_ab") or {}
    hs = h.get("samples") or {}
    on_p99 = _median_field(hs.get("hedge_on"), "get_p99_ms")
    off_p99 = _median_field(hs.get("hedge_off"), "get_p99_ms")
    if on_p99 is None or off_p99 is None:
        failures.append("hedge_ab: missing get p99 in an arm")
    elif not mechanical_only and not on_p99 < off_p99:
        failures.append(
            f"hedge_ab: hedged get p99 {on_p99}ms not strictly better "
            f"than unhedged {off_p99}ms")
    for s in hs.get("hedge_on") or []:
        if s["hedges"] <= 0:
            failures.append("hedge_ab: hedge_on rep fired zero hedges")
        # 5% accrual + the small starting-credit transient
        if s["hedge_rate"] > 0.055:
            failures.append(
                f"hedge_ab: hedge rate {s['hedge_rate']} over the "
                f"5% budget")
        if s["value_mismatches"]:
            failures.append("hedge_ab: value mismatches under hedging")
    for s in hs.get("hedge_off") or []:
        if s["hedges"] > 0:
            failures.append("hedge_ab: hedge_off rep fired hedges "
                            "(killswitch leak)")

    o = oab.get("overhead_ab") or {}
    os_ = o.get("samples") or {}
    on_mean = _median_field(os_.get("armor_on"), "put_mean_ms")
    off_mean = _median_field(os_.get("armor_off"), "put_mean_ms")
    if on_mean is None or off_mean is None:
        failures.append("overhead_ab: missing put mean in an arm")
    elif not mechanical_only and off_mean > 0 and on_mean / off_mean > 1.5:
        failures.append(
            f"overhead_ab: armed write-path mean {on_mean}ms vs "
            f"unarmed {off_mean}ms — over the 1.5x host-noise bound")
    for mode, reps_data in os_.items():
        for s in reps_data:
            if s["value_mismatches"]:
                failures.append(f"overhead_ab {mode}: value mismatches")
    return failures


# ---------------------------------------------------------------------------
# hot-shift rebalancer A/B (round 20: the autonomy acceptance number)
# ---------------------------------------------------------------------------


def run_hot_shift_phase(cluster: Cluster, root: str, policy,
                        rebalance_on: bool, args, total_keys: int,
                        seed: int, mix: Dict[str, float]) -> Dict:
    """One 3-window open-loop phase whose zipfian hot set SHIFTS shards
    at the 1/3 mark: ``--hot_frac`` of ops target one hot shard
    (zipfian key popularity WITHIN it), the rest spread uniformly; at
    ``t_shift`` the hot shard flips from 0 to ``shards // 2``. All four
    shard leaders start crammed on node 0 (the macro-bench's static
    layout), so the hot shard rides the most-loaded dispatch queue in
    both arms — until the ON arm's driver notices.

    The ON arm runs the PRODUCTION policy (RebalancerPolicy: EWMA +
    hysteresis + sustain) fed with per-shard dispatched-op rates, and
    actuates each decision with DirectShardMove onto the spare node —
    the same sense→decide→act loop the coordinator-mode Rebalancer
    runs, minus the coordinator. The OFF arm runs no driver. Samples
    are windowed before/settle/after the shift; the A/B gate compares
    the AFTER window's get p99 — the number that says whether the
    policy re-detected and re-homed the NEW hot shard autonomously.

    Correctness rides along: every acked put is read back at the end
    (leader_only) and must return its exact put value — an acked write
    lost across a policy-initiated cutover fails the run, as does any
    mid-run get outside the deterministic preload/put value set."""
    from rocksplicator_tpu.cluster.rebalancer import (RebalancerFlags,
                                                      RebalancerPolicy)
    from rocksplicator_tpu.cluster.shard_move import (DirectMovePlan,
                                                      DirectNode,
                                                      DirectShardMove,
                                                      MoveFlags)
    from rocksplicator_tpu.rpc.errors import RpcError
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    shards = cluster.shards
    duration = float(args.hot_duration)
    keys_per_shard = total_keys // shards
    hot_ref = [0]                # flipped by the shifter mid-run
    h1 = shards // 2             # the post-shift hot shard (≠ 0)
    counts = [0] * shards        # dispatched ops per shard (policy feed)
    rng = random.Random(seed ^ 0x517F7)
    zipf = ZipfianGenerator(keys_per_shard, seed=seed + 2)
    info: Dict = {}

    def gid_source() -> int:
        # hot ops: zipfian rank within the hot shard's keyspace; cold
        # ops: uniform over all shards. gid = k*shards + s keeps the
        # round-robin dealing (shard_of == gid % shards) intact.
        if rng.random() < args.hot_frac:
            s = hot_ref[0]
            k = zipf.next()
        else:
            s = rng.randrange(shards)
            k = rng.randrange(keys_per_shard)
        counts[s] += 1
        return k * shards + s

    def shifter():
        time.sleep(duration)
        info["t_shift"] = time.monotonic()
        hot_ref[0] = h1

    moves: List[Dict] = []
    stop = threading.Event()
    leaders = {s: 0 for s in range(shards)}
    db_to_shard = {segment_to_db_name(SEGMENT, s): s
                   for s in range(shards)}

    def node(i: int) -> DirectNode:
        return DirectNode("127.0.0.1", cluster.admin_ports[i],
                          cluster.ports[i])

    def driver():
        # bench-sized policy knobs: fast EWMA, 2-tick sustain, and a
        # hot_factor low enough that one shard carrying ~hot_frac of a
        # 4-shard fleet clears it; split_factor effectively off (direct
        # mode has no coordinator to host a range split — moves only)
        rp = RebalancerPolicy(RebalancerFlags(
            ewma_alpha=0.5, hot_factor=1.6, cool_factor=1.2, sustain=2,
            max_concurrent=1, split_factor=1e9, min_rate=10.0))
        info["policy"] = rp
        prev = list(counts)
        t_prev = time.monotonic()
        while not stop.wait(0.4):
            cur = list(counts)
            now = time.monotonic()
            dt = max(1e-3, now - t_prev)
            rates = {db: (cur[s] - prev[s]) / dt
                     for db, s in db_to_shard.items()}
            prev, t_prev = cur, now
            for d in rp.observe(rates):
                s = db_to_shard[d.db_name]
                if leaders[s] != 0:
                    # already re-homed; only the spare can take leaders
                    rp.forget(d.db_name)
                    continue
                rec = {"shard": s, "kind": d.kind,
                       "ewma": round(d.ewma, 1),
                       "fleet_mean": round(d.fleet_mean, 1),
                       "after_shift": "t_shift" in info,
                       "t_sec": round(now - info["t0"], 2)}
                try:
                    plan = DirectMovePlan(
                        db_name=d.db_name, source=node(0),
                        target=node(3), leader=node(0),
                        followers=[node(1), node(2)],
                        store_uri=os.path.join(root, "hotshift-bucket"))
                    timings = DirectShardMove(plan, flags=MoveFlags(
                        catchup_lag_threshold=32, catchup_timeout=60.0,
                        cutover_pause_ms=3000.0,
                        poll_interval=0.05)).run()
                except Exception as e:
                    rec.update(ok=False, error=repr(e))
                    moves.append(rec)
                    rp.forget(d.db_name)
                    continue
                leaders[s] = 3
                cluster.apply_move_layout(s, 3)
                rec.update(ok=True, timings_ms=timings)
                moves.append(rec)
                rp.forget(d.db_name)

    sample_log: List = []
    acked_puts: set = set()
    info["t0"] = time.monotonic()
    threads = [threading.Thread(target=shifter, name="hot-shifter",
                                daemon=True)]
    if rebalance_on:
        threads.append(threading.Thread(target=driver,
                                        name="hot-rebalancer",
                                        daemon=True))
    for th in threads:
        th.start()
    res = cluster.ioloop.run_sync(
        _run_open_loop(cluster, policy, args.hot_rate, duration * 3,
                       total_keys, args.value_bytes, mix, seed,
                       args.max_inflight, sample_log=sample_log,
                       gid_source=gid_source, acked_puts=acked_puts),
        timeout=duration * 3 + 240)
    stop.set()
    for th in threads:
        th.join(timeout=150)

    # the acked-write-loss sweep: every key this phase acked a put for
    # must read back its exact put value from the CURRENT leader —
    # wherever the policy moved it
    async def verify_acked() -> List[int]:
        sem = asyncio.Semaphore(64)
        lost: List[int] = []

        async def check(gid: int):
            async with sem:
                for attempt in range(3):
                    try:
                        r = await cluster.router.read(
                            SEGMENT, shard_of(gid, shards), op="get",
                            keys=[key_of(gid)], policy=policy,
                            timeout=15.0)
                    except RpcError:
                        await asyncio.sleep(0.2 * (attempt + 1))
                        continue
                    got = r["values"][0]
                    got = bytes(got) if got is not None else None
                    if got != put_value(gid, args.value_bytes):
                        lost.append(gid)
                    return
                lost.append(gid)  # unreadable counts as lost

        await asyncio.gather(*[check(g) for g in sorted(acked_puts)])
        return sorted(lost)

    lost = cluster.ioloop.run_sync(verify_acked(),
                                   timeout=30 + len(acked_puts))

    t_shift = info.get("t_shift")
    inf = float("inf")
    windows: Dict[str, Dict] = {}
    for name, lo, hi in (
            ("before", -inf, t_shift or inf),
            ("settle", t_shift or inf,
             (t_shift + duration) if t_shift else inf),
            ("after", (t_shift + duration) if t_shift else inf, inf)):
        gets = sorted(lat for ts, op, lat in sample_log
                      if op == "get" and lat is not None and lo <= ts < hi)
        windows[name] = {
            "get_count": len(gets),
            "get_errors": sum(1 for ts, op, lat in sample_log
                              if op == "get" and lat is None
                              and lo <= ts < hi),
            "get_p50_ms": round(percentile(gets, 50), 3) if gets else None,
            "get_p99_ms": round(percentile(gets, 99), 3) if gets else None,
            "put_errors": sum(1 for ts, op, lat in sample_log
                              if op == "put" and lat is None
                              and lo <= ts < hi),
        }
    policy_obj = info.get("policy")
    return {
        "after_get_p99_ms": windows["after"]["get_p99_ms"],
        "after_get_p50_ms": windows["after"]["get_p50_ms"],
        "windows": windows,
        "moves": moves,
        "moves_ok": sum(1 for m in moves if m.get("ok")),
        "moves_after_shift": sum(1 for m in moves
                                 if m.get("ok") and m.get("after_shift")),
        "acked_puts": len(acked_puts),
        "acked_write_losses": len(lost),
        "lost_gids": lost[:20],
        "value_mismatches": res.value_mismatches,
        "achieved_per_sec": res.summarize(
            args.hot_rate, duration * 3)["achieved_per_sec"],
        "policy_snapshot": (policy_obj.snapshot()
                            if policy_obj is not None else None),
    }


def run_hot_shift_ab(args) -> Dict:
    """Interleaved rebalancer-ON vs OFF over the hot-shift workload:
    fresh 4-node cluster (3 replicas + spare, admin plane on) per arm
    per rep — the ON arm's moves rewrite placement, so arms can never
    share a cluster. Lower after-window get p99 wins."""
    import shutil
    import tempfile

    from rocksplicator_tpu.rpc.router import ReadPolicy

    mix = parse_mix(args.hot_mix)
    total_keys = args.shards * args.preload_keys
    # leader_only on purpose: every op for a shard rides its leader's
    # dispatch queue, so placement IS the latency story the A/B tells
    policy = ReadPolicy.leader_only()
    rep_no = [0]

    def arm(on: bool):
        name = "rebalance_on" if on else "rebalance_off"

        def run() -> Dict:
            rep_no[0] += 1
            root = tempfile.mkdtemp(prefix="rstpu-hotshift-")
            cluster = None
            try:
                log(f"hot_shift[{name}]: booting 4-node cluster "
                    f"({args.shards} shards, all leaders on node 0, "
                    f"read stall {args.hot_inject_ms}ms)")
                # symmetric per-read executor stall in BOTH arms: the
                # serving knee is the same everywhere; only WHERE the
                # hot shard's queue lives differs between arms
                extra_env = ({"RSTPU_FAILPOINTS":
                              f"repl.read.serve=delay_ms:"
                              f"{args.hot_inject_ms}"}
                             if args.hot_inject_ms > 0 else {})
                cluster = Cluster(root, args.shards, args.preload_keys,
                                  args.value_bytes, args.write_window,
                                  args.read_info_ttl_ms, args.transport,
                                  args.hot_executor_threads,
                                  with_move_node=True,
                                  extra_env=extra_env)
                cluster.wait_catchup(total_keys)
                return run_hot_shift_phase(
                    cluster, root, policy, on, args, total_keys,
                    args.seed + 271 * rep_no[0], mix)
            finally:
                if cluster is not None:
                    cluster.stop()
                shutil.rmtree(root, ignore_errors=True)
        return name, run

    return run_interleaved([arm(False), arm(True)], reps=args.hot_reps,
                           key="after_get_p99_ms",
                           higher_is_better=False, log=log)


def hot_shift_failures(ab: Dict) -> List[str]:
    """The round-20 autonomy acceptance gates: final-window fleet get
    p99 strictly better with the rebalancer ON (median across
    interleaved reps), zero value mismatches, zero acked-write losses,
    the ON arm demonstrably re-detected the post-shift hot shard (≥1
    successful move AFTER t_shift), and the OFF arm moved nothing."""
    failures: List[str] = []
    samples = ab.get("samples") or {}
    for name in ("rebalance_off", "rebalance_on"):
        if not samples.get(name):
            failures.append(f"no completed {name} rep")
        for s in samples.get(name) or []:
            if s["value_mismatches"]:
                failures.append(
                    f"{name}: {s['value_mismatches']} value mismatches")
            if s["acked_write_losses"]:
                failures.append(
                    f"{name}: {s['acked_write_losses']} acked put(s) "
                    f"did not read back their value after the run "
                    f"(gids {s['lost_gids']})")
            if s["after_get_p99_ms"] is None:
                failures.append(
                    f"{name}: no gets completed in the after window")
    for s in samples.get("rebalance_on") or []:
        if not s["moves_after_shift"]:
            failures.append(
                "rebalance_on rep dispatched no successful move AFTER "
                "the hot-set shift (policy failed to re-detect)")
        for m in s["moves"]:
            if not m.get("ok"):
                failures.append(
                    f"rebalance_on move of shard {m['shard']} failed: "
                    f"{m.get('error')}")
    for s in samples.get("rebalance_off") or []:
        if s["moves"]:
            failures.append("rebalance_off arm executed moves "
                            "(killswitch leak)")
    ratio = (ab.get("ratio_vs_rebalance_off") or {}).get("rebalance_on")
    if ratio is None:
        if not failures:
            failures.append("no ON/OFF after-window p99 ratio computed")
    elif ratio >= 1.0:
        failures.append(
            f"after-window get p99 ON/OFF ratio {ratio} >= 1.0 — the "
            f"rebalancer did not improve the post-shift tail")
    return failures


# ---------------------------------------------------------------------------
# cluster-wide stats scrape (round 14: the spectator-aggregation path)
# ---------------------------------------------------------------------------


def collect_cluster_stats(cluster: Cluster) -> Dict:
    """One spectator-style scrape+merge over the 3 replica processes:
    per-shard read/write rates + max lag, fleet per-op-class p50/p99
    from the exact log-bucket histogram merge."""
    from rocksplicator_tpu.cluster.stats_aggregator import \
        ClusterStatsAggregator

    agg = ClusterStatsAggregator(pool=cluster.pool, ioloop=cluster.ioloop)
    endpoints = [("127.0.0.1", p) for p in cluster.ports]
    return agg.scrape_and_aggregate(endpoints)


def _fleet_p99(cluster_stats: Dict, op: str) -> Optional[float]:
    fam = (cluster_stats.get("fleet_latency_ms") or {}).get(
        "reads.latency_ms") or {}
    rec = fam.get(op)
    return rec.get("p99_ms") if rec else None


def p99_agreement(result: Dict, server_get_ms: List[float]) -> Dict:
    """The acceptance check: the fleet-merged get p99 must AGREE with a
    bench-measured p99 within histogram bucket resolution.

    The apples-to-apples comparison is against the bench's pooled
    SERVER-REPORTED serve times (each read response carries
    ``serve_ms`` — the exact quantity the per-replica
    ``reads.latency_ms`` histograms bucket). The merged value is a
    bucket UPPER edge, so exact agreement means
    fleet_p99 ∈ [bench_p99, bench_p99 * 2^(1/8)]; the gate allows one
    extra bucket step each way for the catch-up probe reads that are in
    the fleet histogram but predate the sweep. The client-side p99
    (intended-arrival → completion) is recorded alongside for the
    queueing-delta picture but only bounds from above."""
    sweep = result.get("sweep") or []
    fleet = _fleet_p99(result.get("cluster_stats") or {}, "get")
    if not sweep or fleet is None or not server_get_ms:
        return {"checked": False}
    bench_server = percentile(sorted(server_get_ms), 99)
    lowest = min(sweep, key=lambda p: p["offered_per_sec"])
    bench_client = (lowest["ops"].get("get") or {}).get("p99_ms")
    bucket_step = 2 ** 0.125  # 8 sub-buckets per octave (~9%)
    tol = bucket_step * bucket_step * 1.01  # two bucket steps + epsilon
    within = (bench_server / tol - 0.05 <= fleet
              <= bench_server * tol + 0.05)
    return {
        "checked": True,
        "bench_server_get_p99_ms": round(bench_server, 3),
        "bench_server_samples": len(server_get_ms),
        "bench_client_get_p99_ms": bench_client,
        "fleet_get_p99_ms": fleet,
        "bucket_step": round(bucket_step, 4),
        "within": within,
        "note": ("fleet p99 is an exact log-bucket merge of the same "
                 "server-side samples (upper-edge convention); client "
                 "p99 adds RTT + open-loop queueing on top"),
    }


# ---------------------------------------------------------------------------
# CDC streaming ingest phase (round 19: kafka wire -> exactly-once
# follower apply with WAL-riding checkpoints + pacing backpressure)
# ---------------------------------------------------------------------------


def _cdc_value(i: int, nbytes: int) -> bytes:
    seed = b"c%d." % i
    return (seed * (nbytes // len(seed) + 1))[:nbytes]


def run_cdc_phase(args, root: str) -> Dict:
    """CDC streaming ingest under serving load, serving-shaped numbers:

    - boots the 3-process churn-profile cluster WITH the admin plane,
      plus a networked BrokerServer in the driver;
    - phase 1 (baseline): the open-loop mixed workload alone;
    - phase 2 (cdc): the same workload while a producer streams CDC
      records into the broker and the leader's IngestionWatchers (one
      per shard, started via the startMessageIngestion admin RPC,
      ``broker://`` transport) apply them through the grouped-commit
      write path — watermark checkpoints riding every batch;
    - a freshness sampler produces marker records and polls a FOLLOWER
      until each is readable: produce -> replicated-readable wall time,
      the end-to-end freshness the artifact reports as p50/p99;
    - after the producer stops, the drain must converge to EXACTLY the
      produced count (``kafka.cdc.records_applied`` delta == produced,
      zero ``dup_skipped``) — the exactly-once invariant, serving-shaped;
    - backpressure must demonstrably engage: the churn engine profile
      builds real flush/L0 debt, so ``kafka.cdc.paced_sleeps``/
      ``paced_ms`` (the delayed-write-controller-derived fetch pacing)
      must be nonzero.
    """
    from rocksplicator_tpu.kafka.network import BrokerServer
    from rocksplicator_tpu.rpc.router import ReadPolicy
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    mix = parse_mix(args.cdc_mix)
    total_keys = args.shards * args.preload_keys
    policy = ReadPolicy.follower_ok(args.max_lag)
    topic = "cdc_bench"
    out: Dict = {}

    cluster = Cluster(
        root, args.shards, args.preload_keys, args.value_bytes,
        args.write_window, args.read_info_ttl_ms, args.transport,
        args.executor_threads, db_profile="churn", with_admin=True)
    broker = None
    try:
        cluster.wait_catchup(total_keys)
        log(f"cdc: baseline phase (no CDC) {args.cdc_serve_rate}/s "
            f"x {args.cdc_duration}s")
        out["baseline"] = run_phase(
            cluster, policy, args.cdc_serve_rate, args.cdc_duration,
            total_keys, args.value_bytes, mix, args.seed, args.max_inflight)

        broker = BrokerServer(
            data_dir=os.path.join(root, "broker")).start()
        bport = broker.port

        async def bcall(method: str, **a):
            return await cluster.pool.call("127.0.0.1", bport, method, a,
                                           timeout=15.0)

        cluster.ioloop.run_sync(
            bcall("broker_create_topic", topic=topic,
                  num_partitions=args.shards), timeout=20)
        for s in range(args.shards):
            db_name = segment_to_db_name(SEGMENT, s)

            async def start(db=db_name):
                return await cluster.pool.call(
                    "127.0.0.1", cluster.admin_ports[0],
                    "start_message_ingestion",
                    {"db_name": db, "topic_name": topic,
                     "kafka_broker_serverset_path":
                         f"broker://127.0.0.1:{bport}"},
                    timeout=30.0)

            cluster.ioloop.run_sync(start(), timeout=35)
        log(f"cdc: {args.shards} IngestionWatchers consuming "
            f"broker://127.0.0.1:{bport} topic={topic}")

        before = _scrape_counter_sums(cluster, ("kafka.cdc.",))
        produced = [0]       # records (producer + markers)
        produced_bytes = [0]
        stop_producing = threading.Event()
        freshness_ms: List[float] = []
        probe_timeouts = [0]

        def producer():
            """Open-loop CDC stream at cdc_rate across all partitions,
            bursts dispatched as one gather per tick (the per-record
            sync-RPC round trip would cap the rate well below target)."""
            i = 0
            t0 = time.monotonic()
            while not stop_producing.is_set():
                due = int((time.monotonic() - t0) * args.cdc_rate)
                burst = min(due - i, 64)
                if burst <= 0:
                    time.sleep(0.005)
                    continue
                msgs = []
                for _ in range(burst):
                    key = b"cdc%08d" % i
                    val = _cdc_value(i, args.cdc_value_bytes)
                    msgs.append((i % args.shards, key, val))
                    produced_bytes[0] += len(key) + len(val)
                    i += 1

                async def send():
                    await asyncio.gather(*[
                        bcall("broker_produce", topic=topic, partition=p,
                              key=k, value=v,
                              timestamp_ms=int(time.time() * 1000))
                        for (p, k, v) in msgs])

                cluster.ioloop.run_sync(send(), timeout=30)
                produced[0] += burst
            # markers ride the same stream: fold them into the total

        def sampler():
            """Produce a marker, poll a FOLLOWER until readable: the
            produce -> replicated-readable freshness distribution."""
            m = 0
            while not stop_producing.is_set():
                shard = m % args.shards
                key = b"cdcmark%06d" % m
                val = _cdc_value(10_000_000 + m, args.cdc_value_bytes)
                t_prod = time.monotonic()
                cluster.ioloop.run_sync(
                    bcall("broker_produce", topic=topic, partition=shard,
                          key=key, value=val,
                          timestamp_ms=int(time.time() * 1000)),
                    timeout=30)
                produced[0] += 1
                produced_bytes[0] += len(key) + len(val)

                async def read():
                    r = await cluster.pool.call(
                        "127.0.0.1", cluster.ports[1], "read",
                        {"db_name": segment_to_db_name(SEGMENT, shard),
                         "op": "get", "keys": [key],
                         "max_lag": 1 << 30}, timeout=5.0)
                    return r["values"][0]

                deadline = time.monotonic() + args.cdc_probe_timeout
                seen = False
                while time.monotonic() < deadline:
                    try:
                        if cluster.ioloop.run_sync(read(), timeout=10) \
                                == val:
                            seen = True
                            break
                    except Exception:
                        pass
                    time.sleep(0.003)
                if seen:
                    freshness_ms.append(
                        (time.monotonic() - t_prod) * 1000.0)
                else:
                    probe_timeouts[0] += 1
                m += 1
                time.sleep(0.1)

        threads = [threading.Thread(target=producer, daemon=True),
                   threading.Thread(target=sampler, daemon=True)]
        t_start = time.monotonic()
        for t in threads:
            t.start()
        log(f"cdc: CDC phase — {args.cdc_rate} rec/s x "
            f"{args.cdc_value_bytes}B CDC stream + {args.cdc_serve_rate}/s"
            f" mixed serving load x {args.cdc_duration}s")
        out["with_cdc"] = run_phase(
            cluster, policy, args.cdc_serve_rate, args.cdc_duration,
            total_keys, args.value_bytes, mix, args.seed + 31,
            args.max_inflight)
        stop_producing.set()
        for t in threads:
            t.join(timeout=30)
        produce_window = time.monotonic() - t_start

        # drain: applied must converge to EXACTLY the produced count
        def applied_delta() -> Dict[str, float]:
            now = _scrape_counter_sums(cluster, ("kafka.cdc.",))
            return {k: now.get(k, 0.0) - before.get(k, 0.0)
                    for k in set(now) | set(before)}

        deadline = time.monotonic() + args.cdc_drain_timeout
        delta = applied_delta()
        while time.monotonic() < deadline and (
                delta.get("kafka.cdc.records_applied", 0) < produced[0]):
            time.sleep(0.25)
            delta = applied_delta()
        drain_sec = time.monotonic() - t_start - produce_window

        for s in range(args.shards):
            db_name = segment_to_db_name(SEGMENT, s)

            async def stop_ing(db=db_name):
                return await cluster.pool.call(
                    "127.0.0.1", cluster.admin_ports[0],
                    "stop_message_ingestion", {"db_name": db},
                    timeout=30.0)

            try:
                cluster.ioloop.run_sync(stop_ing(), timeout=35)
            except Exception:
                pass

        freshness_ms.sort()
        applied = int(delta.get("kafka.cdc.records_applied", 0))
        bytes_applied = delta.get("kafka.cdc.bytes_applied", 0.0)
        out["cdc"] = {
            "produced_records": produced[0],
            "produced_mb": round(produced_bytes[0] / 1e6, 3),
            "applied_records": applied,
            "dup_skipped": int(delta.get("kafka.cdc.dup_skipped", 0)),
            "consumer_errors": int(
                delta.get("kafka.cdc.consumer_errors", 0)),
            "retry_later": int(delta.get("kafka.cdc.retry_later", 0)),
            "apply_batches": int(delta.get("kafka.cdc.batches", 0)),
            "consume_mb_per_sec": round(
                bytes_applied / 1e6 / max(0.001, produce_window + max(
                    0.0, drain_sec)), 3),
            "produce_window_sec": round(produce_window, 2),
            "drain_sec": round(max(0.0, drain_sec), 2),
            "paced_sleeps": int(delta.get("kafka.cdc.paced_sleeps", 0)),
            "paced_ms": round(delta.get("kafka.cdc.paced_ms", 0.0), 1),
            "freshness_samples": len(freshness_ms),
            "freshness_probe_timeouts": probe_timeouts[0],
            "freshness_p50_ms": percentile(freshness_ms, 50.0),
            "freshness_p99_ms": percentile(freshness_ms, 99.0),
        }
        g0 = out["baseline"]["ops"].get("get") or {}
        g1 = out["with_cdc"]["ops"].get("get") or {}
        log(f"cdc: applied {applied}/{produced[0]} "
            f"({out['cdc']['consume_mb_per_sec']} MB/s), freshness "
            f"p99={out['cdc']['freshness_p99_ms']}ms "
            f"({len(freshness_ms)} samples), paced_sleeps="
            f"{out['cdc']['paced_sleeps']}, get p99 "
            f"{g0.get('p99_ms')} -> {g1.get('p99_ms')}ms under CDC")
        return out
    finally:
        if broker is not None:
            broker.stop()
        cluster.stop()


def cdc_failures(result: Dict) -> List[str]:
    """Loud gates for the --cdc artifact (the smoke relies on these)."""
    failures: List[str] = []
    cdc = result.get("cdc_phase", {}).get("cdc") or {}
    if not cdc:
        return ["cdc phase produced no summary"]
    if cdc["applied_records"] != cdc["produced_records"]:
        failures.append(
            f"exactly-once violated: applied {cdc['applied_records']} != "
            f"produced {cdc['produced_records']} after drain")
    if cdc["dup_skipped"]:
        failures.append(
            f"{cdc['dup_skipped']} duplicate offsets skipped in a "
            f"crash-free run (consumer re-fetched acked records)")
    if not cdc["paced_sleeps"]:
        failures.append(
            "backpressure never engaged (kafka.cdc.paced_sleeps == 0 "
            "under the churn profile)")
    if not cdc["freshness_samples"]:
        failures.append("no freshness samples completed")
    if cdc["freshness_probe_timeouts"] > cdc["freshness_samples"]:
        failures.append(
            f"freshness probes mostly timed out "
            f"({cdc['freshness_probe_timeouts']} timeouts vs "
            f"{cdc['freshness_samples']} samples)")
    base = result.get("cdc_phase", {}).get("baseline", {})
    with_cdc = result.get("cdc_phase", {}).get("with_cdc", {})
    for name, phase in (("baseline", base), ("with_cdc", with_cdc)):
        g = (phase.get("ops") or {}).get("get") or {}
        if not g.get("count"):
            failures.append(f"no reads completed in the {name} phase")
    return failures


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    # child modes
    p.add_argument("--serve", choices=["leader", "follower", "topo"])
    p.add_argument("--topo",
                   help="serve: JSON [[shard, role, upstream_port], ...] "
                        "— this node's hosted subset of the fleet "
                        "topology (fleet_bench spawns these)")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--upstream_port", type=int, default=0)
    p.add_argument("--admin_port", type=int, default=0,
                   help="serve: also run the Admin RPC plane on this "
                        "port (required for mid-bench shard moves)")
    p.add_argument("--db_dir")
    p.add_argument("--ab_worker", choices=["leader_only", "follower_ok"])
    p.add_argument("--ports", help="ab_worker: leader,f1,f2 ports")
    p.add_argument("--db_profile", default="default",
                   choices=["default", "churn"],
                   help="serve: engine options profile (churn = small "
                        "memtables + low L0 triggers for compaction-"
                        "pressure benches)")
    # shared topology / workload knobs
    p.add_argument("--shards", type=int, default=4)
    p.add_argument("--preload_keys", type=int, default=2000,
                   help="keys preloaded PER SHARD before the timed phases")
    p.add_argument("--value_bytes", type=int, default=128)
    p.add_argument("--write_window", type=int, default=64)
    p.add_argument("--read_info_ttl_ms", type=int, default=1500)
    p.add_argument("--executor_threads", type=int, default=4)
    # driver knobs
    p.add_argument("--rates", default="300,600,1200",
                   help="offered-throughput sweep points (ops/sec)")
    p.add_argument("--duration", type=float, default=5.0,
                   help="seconds per sweep point")
    p.add_argument("--mix", default=DEFAULT_MIX)
    p.add_argument("--read_policy", default="follower_ok",
                   choices=["leader_only", "follower_ok", "nearest"])
    p.add_argument("--max_lag", type=int, default=128,
                   help="staleness bound (seqs) for follower_ok/nearest")
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--max_inflight", type=int, default=512)
    p.add_argument("--transport", default="tcp", choices=["tcp", "uds"])
    p.add_argument("--ab", action="store_true",
                   help="run the leader_only vs follower_ok read A/B")
    p.add_argument("--ab_duration", type=float, default=5.0)
    p.add_argument("--ab_readers", type=int, default=8,
                   help="concurrent reader coroutines per worker process")
    p.add_argument("--ab_procs", type=int, default=0,
                   help="A/B client fleet size (worker PROCESSES per "
                        "variant; 0 = derive from cpu count)")
    p.add_argument("--ab_reps", type=int, default=3)
    p.add_argument("--move_mid_bench", action="store_true",
                   help="spawn a 4th (spare) node and run one LIVE "
                        "shard move (shard 0's leader onto it) in the "
                        "middle of a 3-window phase, recording get p99 "
                        "before/during/after the flip")
    p.add_argument("--move_rate", type=float, default=0.0,
                   help="offered ops/s for the move phase (0 = first "
                        "sweep rate)")
    p.add_argument("--sched_ab", action="store_true",
                   help="standalone mode: interleaved A/B of the "
                        "workload-adaptive compaction scheduler "
                        "(RSTPU_COMPACTION_SCHED=1 vs 0) over fresh "
                        "churn-profile clusters under a write-heavy mix")
    p.add_argument("--sched_rate", type=float, default=900.0)
    p.add_argument("--sched_duration", type=float, default=8.0)
    p.add_argument("--sched_reps", type=int, default=2)
    p.add_argument("--sched_mix", default="get=0.5,put=0.5")
    p.add_argument("--overload_ab", action="store_true",
                   help="standalone mode: the round-19 tail-armor "
                        "acceptance A/Bs (per-tenant admission under "
                        "an abusive tenant, hedged follower reads "
                        "against an injected server tail, and the "
                        "unarmed-overhead guard), fresh cluster per arm")
    p.add_argument("--overload_quota", type=float, default=200.0,
                   help="per-tenant ops/s quota (RSTPU_TENANT_OPS) in "
                        "the armor_on arm; the abuser offers 10x this")
    p.add_argument("--overload_good_rate", type=float, default=130.0,
                   help="offered ops/s per well-behaved tenant "
                        "(must sit under the quota)")
    p.add_argument("--overload_good_tenants", type=int, default=3)
    p.add_argument("--tenant_executor_threads", type=int, default=1,
                   help="executor threads per server in the tenant "
                        "A/B only (default 1: the abuser flood must "
                        "monopolize an explicit dispatch queue, not "
                        "race the host's raw CPU knee — the armor "
                        "sheds BEFORE dispatch, so the contrast is "
                        "structural, not host-dependent)")
    p.add_argument("--overload_duration", type=float, default=6.0,
                   help="seconds per overload/hedge/overhead phase")
    p.add_argument("--overload_reps", type=int, default=3)
    p.add_argument("--overload_deadline_ms", type=float, default=2000.0,
                   help="client deadline budget stamped on every "
                        "tenant-phase op (armor_on arm)")
    p.add_argument("--hedge_read_rate", type=float, default=400.0,
                   help="offered get/s for the hedge A/B phase")
    p.add_argument("--hedge_inject_ms", type=int, default=80,
                   help="server-side injected read delay (the fat "
                        "tail hedging should cut)")
    p.add_argument("--hedge_inject_prob", type=float, default=0.025,
                   help="probability of the injected delay per read "
                        "(rare: the p95-derived hedge delay must stay "
                        "UNDER the injected tail, or hedges fire too "
                        "late to rescue it)")
    p.add_argument("--overhead_rate", type=float, default=500.0,
                   help="offered ops/s for the unarmed-overhead A/B "
                        "(comfortably under the knee)")
    p.add_argument("--hot_shift", action="store_true",
                   help="standalone mode: interleaved rebalancer-ON vs "
                        "OFF A/B over a workload whose zipfian hot set "
                        "SHIFTS shards mid-run; the ON arm drives the "
                        "production RebalancerPolicy with "
                        "DirectShardMove as actuator; gates: final-"
                        "window get p99 ON < OFF, zero value "
                        "mismatches, zero acked-write loss")
    p.add_argument("--hot_rate", type=float, default=520.0,
                   help="offered ops/s for the hot-shift phase: with "
                        "the default 3ms read stall the all-on-node-0 "
                        "arm offers ~390 gets/s against a ~300/s "
                        "single-executor knee (overloaded), while the "
                        "rebalanced end-state's hottest node sits at "
                        "~260 gets/s (under it)")
    p.add_argument("--hot_frac", type=float, default=0.55,
                   help="fraction of ops targeting the hot shard")
    p.add_argument("--hot_duration", type=float, default=6.0,
                   help="seconds per hot-shift window (3 windows: "
                        "before/settle/after; shift at the 1/3 mark)")
    p.add_argument("--hot_reps", type=int, default=2)
    p.add_argument("--hot_mix", default="get=0.75,put=0.25",
                   help="op mix for the hot-shift phase")
    p.add_argument("--hot_executor_threads", type=int, default=1,
                   help="executor threads per server in the hot-shift "
                        "A/B (default 1: the hot shard must monopolize "
                        "an explicit dispatch queue — the same "
                        "structural-knee discipline as the tenant A/B)")
    p.add_argument("--hot_inject_ms", type=int, default=3,
                   help="server-side executor-occupancy stall per read "
                        "(repl.read.serve failpoint, BOTH arms): makes "
                        "the per-process serving knee rate-derived "
                        "(~1000/ms gets/s) instead of host-derived, so "
                        "the A/B contrast is placement, even on a "
                        "1-core host where CPU is zero-sum across "
                        "server processes")
    p.add_argument("--cdc", action="store_true",
                   help="CDC streaming-ingest phase: baseline mixed "
                        "phase, then the same load while a producer "
                        "streams into a networked broker and the "
                        "leader's IngestionWatchers apply exactly-once "
                        "with WAL-riding checkpoints; artifact gates on "
                        "applied==produced, backpressure engaging, and "
                        "follower-readable freshness samples")
    p.add_argument("--cdc_rate", type=float, default=600.0,
                   help="CDC records/s offered to the broker")
    p.add_argument("--cdc_value_bytes", type=int, default=256)
    p.add_argument("--cdc_duration", type=float, default=8.0,
                   help="seconds per phase (baseline and with-CDC)")
    p.add_argument("--cdc_serve_rate", type=float, default=400.0,
                   help="foreground mixed ops/s during both phases")
    p.add_argument("--cdc_mix", default="get=0.7,put=0.3")
    p.add_argument("--cdc_probe_timeout", type=float, default=15.0,
                   help="per-marker freshness probe deadline (s)")
    p.add_argument("--cdc_drain_timeout", type=float, default=90.0,
                   help="post-produce drain deadline (s)")
    p.add_argument("--overload_gates", choices=("full", "mechanical"),
                   default="full",
                   help="'full' (default) gates the latency medians "
                        "too; 'mechanical' (the smoke) keeps only the "
                        "deterministic gates — killswitch leaks, quota "
                        "bite, hedge budget, value mismatches — since "
                        "a 1-rep micro run's serving knee drifts too "
                        "much for a strict p99.9 comparison")
    p.add_argument("--out", help="write the artifact JSON here")
    args = p.parse_args(argv)

    if args.serve:
        if not args.db_dir:
            p.error("--serve requires --db_dir")
        return serve(args)
    if args.ab_worker:
        if not args.ports:
            p.error("--ab_worker requires --ports")
        return ab_worker(args)
    if args.ab_procs <= 0:
        # enough client fleet that the SERVERS saturate first: the 3
        # replica processes want ~3 cores + headroom, the fleet gets the
        # rest. On a small (2-4 core) CI host this bottoms out at 2 and
        # the client side caps the measured ratio — the roofline caveat
        # PERF.md round 13 documents.
        args.ab_procs = max(2, min(16, (os.cpu_count() or 4) - 8))

    import shutil
    import tempfile

    from rocksplicator_tpu.rpc.router import ReadPolicy

    mix = parse_mix(args.mix)
    rates = [float(r) for r in args.rates.split(",") if r]
    total_keys = args.shards * args.preload_keys
    policy = {
        "leader_only": ReadPolicy.leader_only(),
        "follower_ok": ReadPolicy.follower_ok(args.max_lag),
        "nearest": ReadPolicy.nearest(args.max_lag),
    }[args.read_policy]

    root = tempfile.mkdtemp(prefix="rstpu-macro-")
    t0 = time.monotonic()
    if args.cdc:
        # standalone mode: the churn cluster + admin plane + broker
        # belong to the CDC phase runner
        result = {
            "bench": "macro_bench_cdc",
            "config": {
                "shards": args.shards,
                "preload_keys_per_shard": args.preload_keys,
                "value_bytes": args.value_bytes,
                "mix": parse_mix(args.cdc_mix),
                "serve_rate": args.cdc_serve_rate,
                "cdc_rate": args.cdc_rate,
                "cdc_value_bytes": args.cdc_value_bytes,
                "duration": args.cdc_duration,
                "max_lag": args.max_lag,
                "transport": args.transport,
                "seed": args.seed,
                "db_profile": "churn",
                "topology": ("1 leader + 2 followers (mode 1), 3 OS "
                             "processes + driver-hosted BrokerServer; "
                             "IngestionWatcher per shard on the leader "
                             "via startMessageIngestion"),
            },
            "host_calibration": host_calibration(root),
        }
        try:
            result["cdc_phase"] = run_cdc_phase(args, root)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        result["elapsed_sec"] = round(time.monotonic() - t0, 1)
        result["failures"] = cdc_failures(result)
        return emit_gated_artifact(result, args.out, "macro_bench", log)
    if args.sched_ab:
        # standalone mode: each arm boots its own cluster (the
        # scheduler switch is a process-env knob), so the normal
        # shared-cluster flow below does not apply
        result = {
            "bench": "macro_bench_sched_ab",
            "config": {
                "shards": args.shards,
                "preload_keys_per_shard": args.preload_keys,
                "value_bytes": args.value_bytes,
                "mix": parse_mix(args.sched_mix),
                "rate": args.sched_rate,
                "duration": args.sched_duration,
                "reps": args.sched_reps,
                "transport": args.transport,
                "seed": args.seed,
                "db_profile": "churn",
                "topology": ("1 leader + 2 followers (mode 1), "
                             "3 OS processes, fresh cluster per arm"),
            },
            "host_calibration": host_calibration(root),
        }
        try:
            result["sched_ab"] = run_sched_ab(args)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        result["elapsed_sec"] = round(time.monotonic() - t0, 1)
        result["failures"] = sched_ab_failures(
            result["sched_ab"]["samples"],
            picks_of=lambda s: s["compaction.sched_picks"])
        return emit_gated_artifact(result, args.out, "macro_bench", log)
    if args.hot_shift:
        # standalone mode: fresh 4-node cluster per arm per rep (the
        # ON arm's policy-driven moves rewrite placement)
        result = {
            "bench": "macro_bench_hot_shift",
            "config": {
                "shards": args.shards,
                "preload_keys_per_shard": args.preload_keys,
                "value_bytes": args.value_bytes,
                "mix": parse_mix(args.hot_mix),
                "rate": args.hot_rate,
                "hot_frac": args.hot_frac,
                "window_duration": args.hot_duration,
                "shift_at": "t0 + window_duration (hot shard 0 -> "
                            f"{args.shards // 2})",
                "reps": args.hot_reps,
                "executor_threads": args.hot_executor_threads,
                "read_stall_ms": args.hot_inject_ms,
                "read_policy": "leader_only",
                "transport": args.transport,
                "seed": args.seed,
                "topology": ("1 leader + 2 followers + spare "
                             "(mode 1), 4 OS processes, fresh cluster "
                             "per arm"),
            },
            "host_calibration": host_calibration(root),
        }
        try:
            result["hot_shift_ab"] = run_hot_shift_ab(args)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        result["elapsed_sec"] = round(time.monotonic() - t0, 1)
        result["failures"] = hot_shift_failures(result["hot_shift_ab"])
        return emit_gated_artifact(result, args.out, "macro_bench", log)
    if args.overload_ab:
        # standalone mode: every arm boots its own cluster (the armor
        # switches are process-env knobs on BOTH sides of the wire)
        result = {
            "bench": "macro_bench_overload_ab",
            "config": {
                "shards": args.shards,
                "preload_keys_per_shard": args.preload_keys,
                "value_bytes": args.value_bytes,
                "tenant_quota_ops": args.overload_quota,
                "abuser_offered_per_sec": 10.0 * args.overload_quota,
                "good_tenants": args.overload_good_tenants,
                "good_rate_per_tenant": args.overload_good_rate,
                "tenant_executor_threads": args.tenant_executor_threads,
                "deadline_budget_ms": args.overload_deadline_ms,
                "hedge_read_rate": args.hedge_read_rate,
                "hedge_inject": (f"{args.hedge_inject_ms}ms @ "
                                 f"p={args.hedge_inject_prob}"),
                "overhead_rate": args.overhead_rate,
                "duration": args.overload_duration,
                "reps": args.overload_reps,
                "max_lag": args.max_lag,
                "transport": args.transport,
                "seed": args.seed,
                "gates": args.overload_gates,
                "topology": ("1 leader + 2 followers (mode 1), "
                             "3 OS processes, fresh cluster per arm"),
            },
            "host_calibration": host_calibration(root),
        }
        try:
            result["overload_ab"] = run_overload_ab(args)
        finally:
            shutil.rmtree(root, ignore_errors=True)
        result["elapsed_sec"] = round(time.monotonic() - t0, 1)
        result["failures"] = overload_failures(
            result, mechanical_only=args.overload_gates == "mechanical")
        return emit_gated_artifact(result, args.out, "macro_bench", log)
    result: Dict = {
        "bench": "macro_bench",
        "config": {
            "shards": args.shards,
            "preload_keys_per_shard": args.preload_keys,
            "total_keys": total_keys,
            "value_bytes": args.value_bytes,
            "mix": mix,
            "read_policy": args.read_policy,
            "max_lag": args.max_lag,
            "transport": args.transport,
            "seed": args.seed,
            "topology": "1 leader + 2 followers (mode 1), 3 OS processes",
        },
    }
    cluster = None
    try:
        log(f"macro_bench: spawning 3-replica cluster "
            f"({args.shards} shards, {total_keys} keys)")
        cluster = Cluster(root, args.shards, args.preload_keys,
                          args.value_bytes, args.write_window,
                          args.read_info_ttl_ms, args.transport,
                          args.executor_threads,
                          with_move_node=args.move_mid_bench)
        cluster.wait_catchup(total_keys)
        result["host_calibration"] = host_calibration(root)
        sweep = []
        server_get_ms: List[float] = []
        for i, rate in enumerate(rates):
            log(f"macro_bench: sweep {i + 1}/{len(rates)} "
                f"offered={rate}/s x {args.duration}s "
                f"policy={args.read_policy}")
            point = run_phase(cluster, policy, rate, args.duration,
                              total_keys, args.value_bytes, mix,
                              args.seed + i * 101, args.max_inflight,
                              server_get_sink=server_get_ms)
            sweep.append(point)
            g = point["ops"].get("get") or {}
            log(f"  achieved={point['achieved_per_sec']}/s "
                f"get p50={g.get('p50_ms')}ms p99={g.get('p99_ms')}ms "
                f"roles={point['reads_by_role']}")
        result["sweep"] = sweep
        # round 14: the cluster-wide metrics plane's view of the same
        # run — scrape every replica's `stats` RPC through the SAME
        # aggregator the spectator's scrape loop uses and merge exactly
        # (log-bucket histograms add losslessly). Taken right after the
        # sweep so the A/B's saturation reads don't swamp the op-class
        # histograms the agreement check compares.
        result["cluster_stats"] = collect_cluster_stats(cluster)
        result["p99_agreement"] = p99_agreement(result, server_get_ms)
        log(f"  cluster_stats: {result['cluster_stats']['replicas_scraped']}"
            f" replicas, max_lag="
            f"{result['cluster_stats']['max_replication_lag']}, "
            f"fleet get p99="
            f"{_fleet_p99(result['cluster_stats'], 'get')}ms vs bench "
            f"server-side "
            f"{result['p99_agreement'].get('bench_server_get_p99_ms')}ms "
            f"(within={result['p99_agreement'].get('within')})")
        if args.move_mid_bench:
            move_rate = args.move_rate or rates[0]
            log(f"macro_bench: LIVE shard move mid-bench (shard 0 "
                f"leader -> spare node) under {move_rate}/s mixed load")
            result["shard_move"] = run_move_phase(
                cluster, root, policy, move_rate, args.duration,
                total_keys, args.value_bytes, mix, args.seed + 9001,
                args.max_inflight)
            result["config"]["move_mid_bench"] = True
            mv = result["shard_move"]
            w = mv["windows"]
            log(f"  move ok={mv['move'].get('ok')} "
                f"phases={mv['move'].get('timings_ms')} — get p99 "
                f"before/during/after = {w['before']['get_p99_ms']}/"
                f"{w['during']['get_p99_ms']}/{w['after']['get_p99_ms']}"
                f" ms (put errors during: {w['during']['put_errors']})")
        if args.ab:
            log(f"macro_bench: read A/B leader_only vs follower_ok"
                f"(max_lag={args.max_lag}) x {args.ab_reps} reps, "
                f"{args.ab_procs} worker procs x {args.ab_readers} readers")
            result["read_ab"] = run_read_ab(
                cluster, args.max_lag, args.ab_duration, args.shards,
                args.preload_keys, args.ab_readers, args.ab_procs,
                args.ab_reps, args.seed, args.transport)
            result["config"]["ab_procs"] = args.ab_procs
            result["config"]["ab_readers"] = args.ab_readers
    finally:
        if cluster is not None:
            cluster.stop()
        shutil.rmtree(root, ignore_errors=True)
    result["elapsed_sec"] = round(time.monotonic() - t0, 1)

    # loud failure gates (the smoke target relies on these)
    failures: List[str] = []
    for point in result.get("sweep", []):
        if point["value_mismatches"]:
            failures.append(
                f"{point['value_mismatches']} get(s) returned a value "
                f"outside the deterministic preload/put set at "
                f"offered={point['offered_per_sec']}")
    if not result.get("sweep"):
        failures.append("empty sweep")
    total_reads = sum(
        sum(p["ops"].get(op, {}).get("count", 0)
            for op in ("get", "multi_get", "scan"))
        for p in result.get("sweep", []))
    if total_reads == 0:
        failures.append("no reads completed in any sweep point")
    if (args.read_policy == "follower_ok"
            and not any(p["reads_by_role"].get("FOLLOWER")
                        for p in result.get("sweep", []))):
        failures.append("follower_ok policy but zero follower-served reads")
    cs = result.get("cluster_stats") or {}
    if not cs.get("per_shard"):
        failures.append("cluster_stats scrape returned no per-shard series")
    elif cs.get("replicas_scraped", 0) < 3:
        failures.append(
            f"cluster_stats scraped only {cs.get('replicas_scraped')}/3 "
            f"replicas")
    if args.move_mid_bench:
        mv = result.get("shard_move") or {}
        if not (mv.get("move") or {}).get("ok"):
            failures.append(
                f"mid-bench shard move failed: "
                f"{(mv.get('move') or {}).get('error')}")
        else:
            w = mv["windows"]
            if not w["during"]["get_count"]:
                failures.append("no reads served DURING the live move")
            if not w["after"]["get_count"] or not w["after"]["put_count"]:
                failures.append(
                    "reads/writes did not resume after the move flip")
    agr = result.get("p99_agreement") or {}
    if agr.get("checked") and not agr.get("within"):
        failures.append(
            f"fleet-merged get p99 {agr['fleet_get_p99_ms']}ms disagrees "
            f"with bench-measured server-side "
            f"{agr['bench_server_get_p99_ms']}ms beyond histogram bucket "
            f"resolution")
    result["failures"] = failures

    out_json = json.dumps(result, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out_json + "\n")
        log(f"macro_bench: artifact -> {args.out}")
    print(out_json)
    if failures:
        for msg in failures:
            log(f"macro_bench: FAILURE: {msg}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
