#!/usr/bin/env python
"""Fleet-density macro-bench (round 22): an N-node / S-shard serving
fleet driven through a SCRIPTED timeline, plus the mux A/B.

The 3-process macro-bench measures one replica set; real density is a
fleet where every node is simultaneously a leader for some shards and
a follower for others. This harness spawns N ``macro_bench --serve
topo`` children hosting S shards at replication factor 3 (leader of
shard s = node ``s % N``, followers ``(s+1) % N`` and ``(s+2) % N`` —
the interleaved-ring layout, so each node follows shards from exactly
two upstream peers) and drives a scripted timeline of serving weather:

- **baseline** — steady mixed workload, the SLO reference point;
- **diurnal** — a stepped rate curve (trough → ramp → peak → settle);
- **hot_shift** — the zipfian hot set is CONCENTRATED on ~20% of the
  shards, then jumps to a different shard subset mid-phase;
- **node_kill** — SIGKILL one node mid-phase (degraded serving gates),
  then restart it and time recovery;
- **drain** — live-drain a node under load: per shard it leads, pause
  writes → wait replicas equal → promote the next replica (epoch+1) →
  repoint the third → demote the old leader to follower; zero
  acked-write loss is gated by reading every acked put back;
- **cdc_burst** — a CDC ingest burst through the broker into a subset
  of shards while serving, gated on EXACTLY-once drain;
- **cooldown** — return to baseline rate, then require full fleet
  convergence (every replica of every shard at the same seq).

Every phase records its own SLO gate verdicts AND a `/cluster_stats`
snapshot (the spectator aggregation over the live fleet). Failures
land in the artifact's ``failures`` and the exit code.

``--ab`` runs the round-22 acceptance A/B instead: interleaved
``RSTPU_PULL_MUX=1`` vs ``0`` over fresh fleets (≥8 procs / ≥64 shards
at the default shape), measuring replication-plane frames/sec and
parked long-polls per node over an IDLE window (driver traffic would
dilute the mux's frame savings), plus applied put throughput, get p99
and acked-put readback over a load window. Gates: frames/sec and
parked long-polls reduced ≥5x, equal applied throughput, p99 no
worse, zero acked-write loss.

    python -m benchmarks.fleet_bench --nodes 10 --shards 100 \
        --out benchmarks/results/fleet_bench.json
    python -m benchmarks.fleet_bench --ab \
        --out benchmarks/results/fleet_mux_ab.json

Artifacts carry the shared ``host_calibration`` block.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.ab_runner import (emit_gated_artifact,  # noqa: E402
                                  host_calibration, run_interleaved)
from benchmarks.macro_bench import (SEGMENT, Cluster,  # noqa: E402
                                    _bench_env, _cdc_value,
                                    _run_open_loop, key_of, log, parse_mix,
                                    percentile, put_value, reserve_port,
                                    shard_of)

REPLICATION_FACTOR = 3


def db_name_of(shard: int) -> str:
    from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

    return segment_to_db_name(SEGMENT, shard)


# ---------------------------------------------------------------------------
# fleet cluster: N topo children, interleaved-ring replica placement
# ---------------------------------------------------------------------------


class FleetCluster:
    """N ``--serve topo`` children hosting S shards at RF=3 on the
    interleaved ring (leader of s = node s % N, followers the next two
    ring nodes), plus the driver's router/pool. Duck-types the subset
    of ``macro_bench.Cluster`` the open-loop driver uses (``shards``,
    ``router``, ``ioloop``, ``pool``)."""

    def __init__(self, root: str, nodes: int, shards: int,
                 preload_keys: int, value_bytes: int, write_window: int,
                 read_info_ttl_ms: int, transport: str,
                 executor_threads: int, with_admin: bool = True,
                 extra_env: Optional[Dict[str, str]] = None):
        if nodes < REPLICATION_FACTOR:
            raise ValueError(f"fleet needs >= {REPLICATION_FACTOR} nodes")
        self.root = root
        self.nodes = nodes
        self.shards = shards
        self.preload_keys = preload_keys
        self.value_bytes = value_bytes
        self.write_window = write_window
        self.read_info_ttl_ms = read_info_ttl_ms
        self.transport = transport
        self.executor_threads = executor_threads
        self.with_admin = with_admin
        self.leader_of: Dict[int, int] = {s: s % nodes
                                          for s in range(shards)}
        self.epochs: Dict[int, int] = {s: 0 for s in range(shards)}
        self.ports = [reserve_port() for _ in range(nodes)]
        self.admin_ports = ([reserve_port() for _ in range(nodes)]
                            if with_admin else [])
        self.alive = [False] * nodes
        self.procs: List[Optional[subprocess.Popen]] = [None] * nodes
        self._env = dict(os.environ, JAX_PLATFORMS="cpu",
                         RSTPU_TRANSPORT=transport)
        self._env.update(extra_env or {})

        # spawn the whole fleet at once: every node is leader for some
        # shards and follower for others, so there is no "leaders
        # first" order — followers whose upstream peer is not yet
        # listening ride the fast-first-connect retry tier
        for i in range(nodes):
            self.procs[i] = self._spawn(i, preload=True)
        for i in range(nodes):
            Cluster._wait_ready(self.procs[i], f"node{i}")
            self.alive[i] = True

        os.environ["RSTPU_TRANSPORT"] = transport
        from rocksplicator_tpu.rpc.client_pool import RpcClientPool
        from rocksplicator_tpu.rpc.router import RpcRouter

        self.pool = RpcClientPool()
        self.router = RpcRouter(local_az="az-n0", pool=self.pool)
        from rocksplicator_tpu.rpc.ioloop import IoLoop

        self.ioloop = IoLoop.default()
        self.update_router()

    # -- placement ---------------------------------------------------------

    def replica_nodes(self, shard: int) -> List[int]:
        return [(shard + k) % self.nodes
                for k in range(REPLICATION_FACTOR)]

    def leaders_on(self, node: int) -> List[int]:
        return [s for s, n in sorted(self.leader_of.items()) if n == node]

    def _topo_json(self, node: int) -> str:
        topo = []
        for s in range(self.shards):
            if node not in self.replica_nodes(s):
                continue
            if self.leader_of[s] == node:
                topo.append([s, "leader", 0])
            else:
                topo.append([s, "follower",
                             self.ports[self.leader_of[s]]])
        return json.dumps(topo)

    def _spawn(self, node: int, preload: bool) -> subprocess.Popen:
        cmd = [
            sys.executable, "-m", "benchmarks.macro_bench",
            "--serve", "topo", "--topo", self._topo_json(node),
            "--port", str(self.ports[node]),
            "--shards", str(self.shards),
            "--db_dir", os.path.join(self.root, f"n{node}"),
            # restarts reopen the surviving storage: re-preloading
            # would append duplicate writes past the followers' seqs
            "--preload_keys", str(self.preload_keys if preload else 0),
            "--value_bytes", str(self.value_bytes),
            "--write_window", str(self.write_window),
            "--read_info_ttl_ms", str(self.read_info_ttl_ms),
            "--executor_threads", str(self.executor_threads),
        ]
        if self.admin_ports:
            cmd += ["--admin_port", str(self.admin_ports[node])]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True, env=self._env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    # -- routing -----------------------------------------------------------

    def update_router(self) -> None:
        """Re-teach the driver's router the CURRENT leader map (what
        the shardmap-agent refresh does for real clients); called after
        every drain handoff."""
        from rocksplicator_tpu.rpc.router import ClusterLayout

        layout: Dict = {SEGMENT: {"num_shards": self.shards}}
        for i, port in enumerate(self.ports):
            entries = []
            for s in range(self.shards):
                if i not in self.replica_nodes(s):
                    continue
                mark = "M" if self.leader_of[s] == i else "S"
                entries.append(f"{s:05d}:{mark}")
            if entries:
                layout[SEGMENT][
                    f"127.0.0.1:{port}:az-n{i}:{port}"] = entries
        self.router.update_layout(
            ClusterLayout.parse(json.dumps(layout).encode()))

    # -- readiness ---------------------------------------------------------

    def wait_catchup(self, total_keys: int, timeout: float = 180.0) -> None:
        """Every follower replica of every shard must serve a max_lag=0
        read of that shard's last preloaded key before the timed
        phases start."""
        from rocksplicator_tpu.rpc.errors import RpcError

        deadline = time.monotonic() + timeout
        for s in range(self.shards):
            gid = total_keys - self.shards + s
            if gid < 0:
                continue
            for node in self.replica_nodes(s):
                if node == self.leader_of[s]:
                    continue

                async def probe(port=self.ports[node], shard=s, g=gid):
                    return await self.pool.call(
                        "127.0.0.1", port, "read",
                        {"db_name": db_name_of(shard), "op": "get",
                         "keys": [key_of(g)], "max_lag": 0},
                        timeout=5.0)

                while True:
                    try:
                        r = self.ioloop.run_sync(probe(), timeout=10)
                        if r["values"][0] is not None:
                            break
                    except RpcError:
                        pass
                    if time.monotonic() > deadline:
                        raise RuntimeError(
                            f"node {node} shard {s} never caught up "
                            f"({timeout}s)")
                    time.sleep(0.1)
        log(f"  fleet caught up ({self.shards} shards x "
            f"{REPLICATION_FACTOR - 1} followers at max_lag=0)")

    # -- admin plane -------------------------------------------------------

    def admin(self, node: int, method: str, timeout: float = 15.0,
              **args):
        async def call():
            return await self.pool.call(
                "127.0.0.1", self.admin_ports[node], method, args,
                timeout=timeout)

        return self.ioloop.run_sync(call(), timeout=timeout + 5)

    def shard_seqs(self, shard: int) -> List[int]:
        return [int(self.admin(n, "get_sequence_number",
                               db_name=db_name_of(shard))["seq_num"])
                for n in self.replica_nodes(shard)]

    def wait_converged(self, shards: Optional[List[int]] = None,
                       timeout: float = 60.0) -> float:
        """Block until every replica of every given shard reports the
        same seq (quiesced fleet only). Returns the wait in seconds."""
        t0 = time.monotonic()
        deadline = t0 + timeout
        for s in (shards if shards is not None else range(self.shards)):
            while True:
                seqs = self.shard_seqs(s)
                if len(set(seqs)) == 1:
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"shard {s} never converged: seqs={seqs}")
                time.sleep(0.1)
        return time.monotonic() - t0

    # -- fault / maintenance actuators ------------------------------------

    def kill_node(self, node: int) -> None:
        p = self.procs[node]
        p.kill()
        p.wait(timeout=10)
        self.alive[node] = False
        log(f"  node{node} SIGKILLed "
            f"(led {len(self.leaders_on(node))} shards)")

    def restart_node(self, node: int) -> None:
        self.procs[node] = self._spawn(node, preload=False)
        Cluster._wait_ready(self.procs[node], f"node{node} (restart)")
        self.alive[node] = True

    def drain_node(self, node: int,
                   pause_ms: float = 20000.0,
                   catchup_timeout: float = 30.0) -> Dict:
        """Live-drain every shard ``node`` leads, one at a time: pause
        writes on the old leader (auto-expiring, so a dead drainer
        can't wedge the shard) → wait until all three replicas report
        the same seq (mode-1 acks only guarantee ONE follower has a
        write, so promotion before full catch-up could lose acked
        writes) → promote the next ring replica at epoch+1 → repoint
        the third replica → demote the old leader to a follower of the
        new one → re-teach the router. Writes to the shard error
        between pause and the router update; the phase's error budget
        absorbs that window."""
        moved = []
        t0 = time.monotonic()
        for s in list(self.leaders_on(node)):
            db = db_name_of(s)
            replicas = self.replica_nodes(s)
            new_leader = next(r for r in replicas
                              if r != node and self.alive[r])
            third = [r for r in replicas if r not in (node, new_leader)]
            self.admin(node, "pause_db_writes", db_name=db,
                       duration_ms=pause_ms)
            deadline = time.monotonic() + catchup_timeout
            while True:
                seqs = self.shard_seqs(s)
                if len(set(seqs)) == 1:
                    break
                if time.monotonic() > deadline:
                    raise RuntimeError(
                        f"drain: shard {s} replicas never leveled: "
                        f"{seqs}")
                time.sleep(0.05)
            epoch = self.epochs[s] + 1
            self.epochs[s] = epoch
            self.admin(new_leader, "change_db_role_and_upstream",
                       db_name=db, new_role="LEADER", epoch=epoch,
                       timeout=30.0)
            self.admin(node, "change_db_role_and_upstream",
                       db_name=db, new_role="FOLLOWER",
                       upstream_ip="127.0.0.1",
                       upstream_port=self.ports[new_leader],
                       epoch=epoch, timeout=30.0)
            for r in third:
                self.admin(r, "change_db_role_and_upstream",
                           db_name=db, new_role="FOLLOWER",
                           upstream_ip="127.0.0.1",
                           upstream_port=self.ports[new_leader],
                           epoch=epoch, timeout=30.0)
            self.leader_of[s] = new_leader
            self.update_router()
            moved.append({"shard": s, "from": node, "to": new_leader,
                          "epoch": epoch})
        return {"shards_moved": len(moved), "moves": moved,
                "drain_sec": round(time.monotonic() - t0, 2)}

    # -- observability -----------------------------------------------------

    def scrape_node(self, node: int) -> Dict:
        async def call():
            return await self.pool.call(
                "127.0.0.1", self.ports[node], "stats", {},
                timeout=10.0)

        return self.ioloop.run_sync(call(), timeout=15)

    def counter_sums(self, prefixes: Tuple[str, ...]) -> Dict[str, float]:
        sums: Dict[str, float] = {}
        for i in range(self.nodes):
            if not self.alive[i]:
                continue
            st = self.scrape_node(i)
            for k, v in (st.get("counters") or {}).items():
                if k.startswith(prefixes):
                    sums[k] = sums.get(k, 0.0) + v["total"]
        return sums

    def cluster_stats(self) -> Dict:
        from rocksplicator_tpu.cluster.stats_aggregator import \
            ClusterStatsAggregator

        agg = ClusterStatsAggregator(pool=self.pool, ioloop=self.ioloop)
        endpoints = [("127.0.0.1", p)
                     for i, p in enumerate(self.ports) if self.alive[i]]
        return agg.scrape_and_aggregate(endpoints)

    def stop(self) -> None:
        try:
            self.ioloop.run_sync(self.pool.close(), timeout=10)
        except Exception:
            pass
        for p in self.procs:
            if p is not None and p.poll() is None:
                p.terminate()
        for p in self.procs:
            if p is not None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()


# ---------------------------------------------------------------------------
# per-phase SLO gates + /cluster_stats snapshots
# ---------------------------------------------------------------------------


def _err_counts(summary: Dict) -> Tuple[int, int]:
    completed = sum(op["count"] for op in summary["ops"].values())
    errors = sum(op["errors"] for op in summary["ops"].values())
    return completed, errors


def slo_gate(phase: str, summary: Dict, spec: Dict,
             baseline: Optional[Dict] = None) -> Tuple[Dict, List[str]]:
    """Evaluate one phase summary against its gate spec. Returns the
    recorded gate block and the failure strings (phase-prefixed)."""
    completed, errors = _err_counts(summary)
    # the open-loop driver awaits every dispatched op, so availability
    # is exactly 1 - error_rate (there is no silent-drop channel);
    # achieved_per_sec vs the nominal rate only measures the Poisson
    # arrival draw and is recorded in the summary, not gated
    err_rate = errors / max(1, completed + errors)
    get_p99 = (summary["ops"].get("get") or {}).get("p99_ms")
    gates = {
        "spec": spec,
        "error_rate": round(err_rate, 4),
        "availability": round(1.0 - err_rate, 4),
        "value_mismatches": summary["value_mismatches"],
        "get_p99_ms": get_p99,
    }
    fails: List[str] = []
    if summary["value_mismatches"]:
        fails.append(f"{phase}: {summary['value_mismatches']} value "
                     "mismatches")
    if err_rate > spec["max_error_rate"]:
        fails.append(f"{phase}: error rate {err_rate:.3f} > "
                     f"{spec['max_error_rate']}")
    factor = spec.get("p99_factor")
    if factor and baseline is not None:
        base_p99 = (baseline["ops"].get("get") or {}).get("p99_ms")
        if base_p99 is not None and get_p99 is not None:
            bound = base_p99 * factor + spec.get("p99_slack_ms", 2.0)
            gates["get_p99_bound_ms"] = round(bound, 3)
            if get_p99 > bound:
                fails.append(
                    f"{phase}: get p99 {get_p99}ms > {bound:.1f}ms "
                    f"({factor}x baseline {base_p99}ms)")
    return gates, fails


def snapshot(cluster: FleetCluster) -> Dict:
    """A compact `/cluster_stats` snapshot for the per-phase record:
    the fleet latency merge + fleet scalars, not the full per-shard
    map (the final full snapshot is recorded once, at the end)."""
    cs = cluster.cluster_stats()
    shards = cs.get("per_shard") or {}
    counters = cs.get("counters_total") or {}
    keep = ("replicator.mux_", "replicator.pull_requests",
            "replicator.parked", "rpc.frames_", "router.")
    return {
        "endpoints": sum(1 for a in cluster.alive if a),
        "shards_reporting": len(shards),
        "max_replication_lag": cs.get("max_replication_lag"),
        "fleet_latency_ms": cs.get("fleet_latency_ms"),
        "counters": {k: v for k, v in sorted(counters.items())
                     if k.startswith(keep)},
        "scrape_errors_total": cs.get("scrape_errors_total"),
    }


def run_fleet_phase(cluster: FleetCluster, policy, rate: float,
                    duration: float, total_keys: int, value_bytes: int,
                    mix: Dict[str, float], seed: int, max_inflight: int,
                    gid_source=None,
                    acked: Optional[set] = None) -> Dict:
    res = cluster.ioloop.run_sync(
        _run_open_loop(cluster, policy, rate, duration, total_keys,
                       value_bytes, mix, seed, max_inflight,
                       gid_source=gid_source, acked_puts=acked),
        timeout=duration + 240)
    return res.summarize(rate, duration)


def readback_acked(cluster: FleetCluster, acked: set, value_bytes: int,
                   sample_cap: int = 1500) -> Dict:
    """Read a sample of acked put gids back at the CURRENT leaders with
    max_lag=0: any miss or wrong value is an acked-write loss."""
    from rocksplicator_tpu.rpc.router import ReadPolicy

    gids = sorted(acked)
    if len(gids) > sample_cap:
        step = len(gids) / sample_cap
        gids = [gids[int(i * step)] for i in range(sample_cap)]
    lost: List[int] = []

    async def check(gid: int):
        r = await cluster.router.read(
            SEGMENT, shard_of(gid, cluster.shards), op="get",
            policy=ReadPolicy.leader_only(),
            keys=[key_of(gid)], timeout=15.0)
        got = r["values"][0]
        got = bytes(got) if got is not None else None
        if got != put_value(gid, value_bytes):
            lost.append(gid)

    async def run_all():
        sem = asyncio.Semaphore(64)

        async def one(g):
            async with sem:
                await check(g)

        await asyncio.gather(*[one(g) for g in gids])

    cluster.ioloop.run_sync(run_all(), timeout=120)
    return {"acked_total": len(acked), "sampled": len(gids),
            "lost": len(lost), "lost_gids": lost[:20]}


# ---------------------------------------------------------------------------
# scripted timeline phases
# ---------------------------------------------------------------------------


def phase_baseline(cluster, args, policy, total_keys, mix, acked) -> Dict:
    log(f"phase baseline: {args.rate}/s x {args.duration}s")
    summary = run_fleet_phase(
        cluster, policy, args.rate, args.duration, total_keys,
        args.value_bytes, mix, args.seed, args.max_inflight, acked=acked)
    spec = {"max_error_rate": 0.01}
    gates, fails = slo_gate("baseline", summary, spec)
    return {"phase": "baseline", "summary": summary, "slo": gates,
            "failures": fails}


def phase_diurnal(cluster, args, policy, total_keys, mix, acked,
                  baseline) -> Dict:
    """Stepped diurnal rate curve: trough → ramp → peak (2x, open-loop
    overload by design) → settle. The p99 gate bites on the SETTLE
    step — the fleet must come back down once the peak passes."""
    steps = [("trough", 0.5), ("ramp", 1.25), ("peak", 2.0),
             ("settle", 1.0)]
    step_dur = max(1.0, args.duration / len(steps))
    curve = []
    fails: List[str] = []
    for k, (name, factor) in enumerate(steps):
        rate = args.rate * factor
        log(f"phase diurnal/{name}: {rate:.0f}/s x {step_dur:.1f}s")
        s = run_fleet_phase(
            cluster, policy, rate, step_dur, total_keys,
            args.value_bytes, mix, args.seed + 100 + k,
            args.max_inflight, acked=acked)
        spec = {"max_error_rate": 0.05}
        if name == "settle":
            spec.update({"max_error_rate": 0.02, "p99_factor": 4.0})
        g, f = slo_gate(f"diurnal/{name}", s, spec, baseline)
        curve.append({"step": name, "rate_factor": factor,
                      "summary": s, "slo": g})
        fails.extend(f)
    return {"phase": "diurnal", "curve": curve, "failures": fails}


def phase_hot_shift(cluster, args, policy, total_keys, mix, acked,
                    baseline) -> Dict:
    """Hot-SHARD skew: 90% of ops target ~20% of the shards (a
    contiguous ring arc, i.e. a specific subset of leader nodes);
    mid-phase the arc jumps to the opposite side of the ring."""
    import random as _random

    rng = _random.Random(args.seed + 17)
    arc = max(1, cluster.shards // 5)
    hot_a = list(range(0, arc))
    hot_b = [(s + cluster.shards // 2) % cluster.shards
             for s in range(arc)]
    hot = {"cur": hot_a}
    per_shard = max(1, total_keys // cluster.shards)

    def gid_source() -> int:
        if rng.random() < 0.9:
            s = rng.choice(hot["cur"])
        else:
            s = rng.randrange(cluster.shards)
        return s + cluster.shards * rng.randrange(per_shard)

    def shifter():
        time.sleep(args.duration / 2)
        hot["cur"] = hot_b
        log("  hot set SHIFTED to the opposite ring arc")

    t = threading.Thread(target=shifter, daemon=True)
    log(f"phase hot_shift: {args.rate}/s x {args.duration}s, hot arc "
        f"{arc}/{cluster.shards} shards, shift at t+{args.duration / 2:.1f}s")
    t.start()
    summary = run_fleet_phase(
        cluster, policy, args.rate, args.duration, total_keys,
        args.value_bytes, mix, args.seed + 7, args.max_inflight,
        gid_source=gid_source, acked=acked)
    t.join(timeout=5)
    spec = {"max_error_rate": 0.03, "p99_factor": 4.0}
    gates, fails = slo_gate("hot_shift", summary, spec, baseline)
    return {"phase": "hot_shift", "hot_arc_shards": arc,
            "summary": summary, "slo": gates, "failures": fails}


def phase_node_kill(cluster, args, policy, total_keys, mix, acked,
                    baseline) -> Dict:
    """SIGKILL a node mid-phase, keep serving, then restart it and
    time recovery. Reads fail over to surviving replicas (the router
    skips dead candidates); writes to the dead node's led shards error
    until it returns — the availability gate budgets exactly that."""
    victim = args.kill_node % cluster.nodes
    led_share = len(cluster.leaders_on(victim)) / cluster.shards
    put_share = mix.get("put", 0.0)
    kill_at = args.duration * 0.3

    killer = threading.Timer(kill_at, cluster.kill_node, args=(victim,))
    log(f"phase node_kill: {args.rate}/s x {args.duration}s, SIGKILL "
        f"node{victim} at t+{kill_at:.1f}s (leads "
        f"{led_share:.0%} of shards)")
    killer.start()
    summary = run_fleet_phase(
        cluster, policy, args.rate, args.duration, total_keys,
        args.value_bytes, mix, args.seed + 11, args.max_inflight)
    killer.cancel()

    t0 = time.monotonic()
    cluster.restart_node(victim)
    affected = [s for s in range(cluster.shards)
                if victim in cluster.replica_nodes(s)]
    cluster.wait_converged(affected, timeout=90.0)
    recovery_sec = time.monotonic() - t0

    # budget: writes to the victim's led shards are gone for ~70% of
    # the phase; reads mostly fail over. 3x slack on the write share
    # covers in-flight losses at the kill edge + failover latency.
    # p99 slack is ABSOLUTE: the failover tail is a detection floor
    # (in-flight ops at the kill edge ride out a connect/read timeout
    # before the router retargets) that doesn't scale with baseline
    # latency — a factor-only bound gets arbitrarily tight when the
    # unloaded baseline is fast.
    budget = min(0.5, 3.0 * led_share * put_share + 0.05)
    spec = {"max_error_rate": round(budget, 3), "p99_factor": 6.0,
            "p99_slack_ms": 250.0}
    gates, fails = slo_gate("node_kill", summary, spec, baseline)
    gates["killed_node"] = victim
    gates["led_share"] = round(led_share, 3)
    gates["recovery_sec"] = round(recovery_sec, 2)
    log(f"  node{victim} restarted; {len(affected)} shards reconverged "
        f"in {recovery_sec:.1f}s")
    return {"phase": "node_kill", "summary": summary, "slo": gates,
            "failures": fails}


def phase_drain(cluster, args, policy, total_keys, mix, acked,
                baseline) -> Dict:
    """Live-drain a node's led shards under load (pause → level →
    promote(epoch+1) → repoint → demote per shard), then read every
    acked put back: zero acked-write loss."""
    victim = args.drain_node % cluster.nodes
    n_led = len(cluster.leaders_on(victim))
    drain_result: Dict = {}
    drain_err: List[str] = []

    def drainer():
        time.sleep(args.duration * 0.2)
        try:
            drain_result.update(cluster.drain_node(victim))
        except Exception as e:
            drain_err.append(f"drain: {type(e).__name__}: {e}")

    t = threading.Thread(target=drainer, daemon=True)
    log(f"phase drain: {args.rate}/s x {args.duration}s, draining "
        f"node{victim} ({n_led} led shards) under load")
    t.start()
    phase_acked: set = set()
    summary = run_fleet_phase(
        cluster, policy, args.rate, args.duration, total_keys,
        args.value_bytes, mix, args.seed + 13, args.max_inflight,
        acked=phase_acked)
    t.join(timeout=120)
    acked |= phase_acked
    rb = readback_acked(cluster, phase_acked, args.value_bytes)

    # same absolute slack rationale as node_kill: gets racing a
    # shard's promote/re-teach window ride one failover hop
    spec = {"max_error_rate": 0.15, "p99_factor": 6.0,
            "p99_slack_ms": 250.0}
    gates, fails = slo_gate("drain", summary, spec, baseline)
    fails.extend(drain_err)
    if t.is_alive():
        fails.append("drain: drainer still running after the phase")
    if not drain_err and drain_result.get("shards_moved", 0) != n_led:
        fails.append(f"drain: moved {drain_result.get('shards_moved')} "
                     f"of {n_led} led shards")
    if cluster.leaders_on(victim):
        fails.append(f"drain: node{victim} still leads "
                     f"{cluster.leaders_on(victim)}")
    if rb["lost"]:
        fails.append(f"drain: {rb['lost']} acked puts lost "
                     f"(of {rb['sampled']} sampled)")
    gates["drained_node"] = victim
    gates["acked_readback"] = rb
    drain_result.pop("moves", None)  # artifact size: counts only
    return {"phase": "drain", "summary": summary, "drain": drain_result,
            "slo": gates, "failures": fails}


def phase_cdc_burst(cluster, args, policy, total_keys, mix, acked,
                    baseline, root) -> Dict:
    """A CDC ingest burst through the broker into a shard subset while
    serving: exactly-once drain (applied == produced, zero dup_skipped)
    against the CURRENT leaders (drain may have moved them)."""
    from rocksplicator_tpu.kafka.network import BrokerServer

    burst_shards = list(range(min(cluster.shards, 2 * cluster.nodes)))
    topic = "fleet_cdc"
    broker = BrokerServer(
        data_dir=os.path.join(root, "fleet_broker")).start()
    fails: List[str] = []
    try:
        bport = broker.port

        async def bcall(method: str, **a):
            return await cluster.pool.call(
                "127.0.0.1", bport, method, a, timeout=15.0)

        cluster.ioloop.run_sync(
            bcall("broker_create_topic", topic=topic,
                  num_partitions=cluster.shards), timeout=20)
        for s in burst_shards:
            cluster.admin(
                cluster.leader_of[s], "start_message_ingestion",
                db_name=db_name_of(s), topic_name=topic,
                kafka_broker_serverset_path=f"broker://127.0.0.1:{bport}",
                timeout=30.0)

        before = cluster.counter_sums(("kafka.cdc.",))
        produced = [0]
        stop = threading.Event()

        def producer():
            i = 0
            target = args.cdc_records * len(burst_shards)
            while i < target and not stop.is_set():
                burst = min(64, target - i)
                msgs = []
                for _ in range(burst):
                    s = burst_shards[i % len(burst_shards)]
                    msgs.append((s, b"fcdc%08d" % i,
                                 _cdc_value(i, args.cdc_value_bytes)))
                    i += 1

                async def send():
                    await asyncio.gather(*[
                        bcall("broker_produce", topic=topic, partition=p,
                              key=k, value=v,
                              timestamp_ms=int(time.time() * 1000))
                        for (p, k, v) in msgs])

                cluster.ioloop.run_sync(send(), timeout=30)
                produced[0] += burst

        t = threading.Thread(target=producer, daemon=True)
        log(f"phase cdc_burst: {args.cdc_records} rec x "
            f"{len(burst_shards)} shards through the broker + "
            f"{args.rate}/s serving x {args.duration}s")
        t.start()
        summary = run_fleet_phase(
            cluster, policy, args.rate, args.duration, total_keys,
            args.value_bytes, mix, args.seed + 19, args.max_inflight,
            acked=acked)
        t.join(timeout=60)
        stop.set()
        if t.is_alive():
            fails.append("cdc_burst: producer wedged")

        deadline = time.monotonic() + args.cdc_drain_timeout
        while time.monotonic() < deadline:
            delta = cluster.counter_sums(("kafka.cdc.",))
            applied = (delta.get("kafka.cdc.records_applied", 0)
                       - before.get("kafka.cdc.records_applied", 0))
            if applied >= produced[0]:
                break
            time.sleep(0.25)
        delta = cluster.counter_sums(("kafka.cdc.",))
        applied = int(delta.get("kafka.cdc.records_applied", 0)
                      - before.get("kafka.cdc.records_applied", 0))
        dups = int(delta.get("kafka.cdc.dup_skipped", 0)
                   - before.get("kafka.cdc.dup_skipped", 0))
        for s in burst_shards:
            with contextlib.suppress(Exception):
                cluster.admin(cluster.leader_of[s],
                              "stop_message_ingestion",
                              db_name=db_name_of(s), timeout=30.0)

        if applied != produced[0]:
            fails.append(f"cdc_burst: applied {applied} != produced "
                         f"{produced[0]} (exactly-once drain)")
        if dups:
            fails.append(f"cdc_burst: {dups} dup_skipped (should be 0)")
        # the CDC ingest shares the grouped-commit write path with the
        # serving load, so p99 gets a wide berth — the exactly-once
        # drain above is this phase's real gate
        spec = {"max_error_rate": 0.03, "p99_factor": 8.0}
        gates, f = slo_gate("cdc_burst", summary, spec, baseline)
        fails.extend(f)
        gates["cdc"] = {"produced": produced[0], "applied": applied,
                        "dup_skipped": dups,
                        "burst_shards": len(burst_shards)}
        return {"phase": "cdc_burst", "summary": summary, "slo": gates,
                "failures": fails}
    finally:
        broker.stop()


def phase_cooldown(cluster, args, policy, total_keys, mix, acked,
                   baseline) -> Dict:
    """Return to half the baseline rate, then require FULL fleet
    convergence (every replica of every shard at one seq) and a clean
    readback of every acked put across the whole timeline."""
    rate = args.rate * 0.5
    log(f"phase cooldown: {rate:.0f}/s x {args.duration}s + fleet "
        "convergence")
    summary = run_fleet_phase(
        cluster, policy, rate, args.duration, total_keys,
        args.value_bytes, mix, args.seed + 23, args.max_inflight,
        acked=acked)
    spec = {"max_error_rate": 0.01, "p99_factor": 3.0}
    gates, fails = slo_gate("cooldown", summary, spec, baseline)
    try:
        gates["convergence_sec"] = round(
            cluster.wait_converged(timeout=90.0), 2)
    except RuntimeError as e:
        fails.append(f"cooldown: {e}")
    rb = readback_acked(cluster, acked, args.value_bytes)
    gates["acked_readback"] = rb
    if rb["lost"]:
        fails.append(f"cooldown: {rb['lost']} acked puts lost across "
                     f"the timeline (of {rb['sampled']} sampled)")
    return {"phase": "cooldown", "summary": summary, "slo": gates,
            "failures": fails}


def run_timeline(args, root: str) -> Dict:
    from rocksplicator_tpu.rpc.router import ReadPolicy

    mix = parse_mix(args.mix)
    total_keys = args.shards * args.preload_keys
    policy = ReadPolicy.follower_ok(args.max_lag)
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    acked: set = set()

    log(f"fleet: {args.nodes} nodes x {args.shards} shards (RF="
        f"{REPLICATION_FACTOR}), {total_keys} keys, phases: "
        + ",".join(phases))
    cluster = FleetCluster(
        root, args.nodes, args.shards, args.preload_keys,
        args.value_bytes, args.write_window, args.read_info_ttl_ms,
        args.transport, args.executor_threads, with_admin=True)
    try:
        cluster.wait_catchup(total_keys)
        baseline: Optional[Dict] = None
        timeline: List[Dict] = []
        failures: List[str] = []
        for name in phases:
            if name == "baseline":
                rec = phase_baseline(cluster, args, policy, total_keys,
                                     mix, acked)
                baseline = rec["summary"]
            elif name == "diurnal":
                rec = phase_diurnal(cluster, args, policy, total_keys,
                                    mix, acked, baseline)
            elif name == "hot_shift":
                rec = phase_hot_shift(cluster, args, policy, total_keys,
                                      mix, acked, baseline)
            elif name == "node_kill":
                rec = phase_node_kill(cluster, args, policy, total_keys,
                                      mix, acked, baseline)
            elif name == "drain":
                rec = phase_drain(cluster, args, policy, total_keys,
                                  mix, acked, baseline)
            elif name == "cdc_burst":
                rec = phase_cdc_burst(cluster, args, policy, total_keys,
                                      mix, acked, baseline, root)
            elif name == "cooldown":
                rec = phase_cooldown(cluster, args, policy, total_keys,
                                     mix, acked, baseline)
            else:
                raise ValueError(f"unknown phase {name!r}")
            rec["cluster_stats"] = snapshot(cluster)
            failures.extend(rec.pop("failures"))
            timeline.append(rec)
        return {
            "bench": "fleet_bench",
            "topology": {
                "nodes": args.nodes, "shards": args.shards,
                "replication_factor": REPLICATION_FACTOR,
                "placement": "interleaved ring: leader of s = s % N, "
                             "followers the next two ring nodes",
                "pull_mux": os.environ.get("RSTPU_PULL_MUX", ""),
            },
            "config": {
                "rate": args.rate, "phase_duration": args.duration,
                "mix": args.mix, "preload_keys": args.preload_keys,
                "value_bytes": args.value_bytes,
                "max_lag": args.max_lag, "seed": args.seed,
            },
            "phases": timeline,
            "final_cluster_stats": cluster.cluster_stats(),
            "failures": failures,
        }
    finally:
        cluster.stop()


# ---------------------------------------------------------------------------
# mux A/B: RSTPU_PULL_MUX=1 vs 0 over fresh fleets, idle-window frames
# ---------------------------------------------------------------------------


def _frames_and_parked(cluster: FleetCluster) -> Tuple[float, float]:
    """One scrape pass: fleet frames total (sent+received) and parked
    long-polls summed over the per-node gauges. The parked gauge rides
    the same scrape as the frame counters, so the idle window pays
    only the bracketing scrapes' own frames (~2/node)."""
    frames = 0.0
    parked = 0.0
    for i in range(cluster.nodes):
        st = cluster.scrape_node(i)
        for k, v in (st.get("counters") or {}).items():
            if k.startswith(("rpc.frames_sent", "rpc.frames_received")):
                frames += v["total"]
        for k, v in (st.get("gauges") or {}).items():
            if k.startswith("replicator.parked_longpolls"):
                parked += float(v)
    return frames, parked


def run_mux_ab(args, root: str) -> Dict:
    """Interleaved mux-on vs mux-off over fresh fleets: the load
    window measures applied put throughput + get p99 + acked readback;
    the IDLE window (driver silent) measures the replication plane's
    own steady-state cost — frames/sec and parked long-polls per node,
    the two quantities the mux collapses."""
    from rocksplicator_tpu.rpc.router import ReadPolicy

    mix = parse_mix("get=0.5,put=0.5")
    total_keys = args.ab_shards * args.preload_keys
    rep_n = [0]

    def arm(mux: str):
        def thunk() -> Dict:
            rep_n[0] += 1
            workdir = os.path.join(root, f"ab_{mux}_{rep_n[0]}")
            os.makedirs(workdir, exist_ok=True)
            env = {"RSTPU_PULL_MUX": "1" if mux == "mux_on" else "0"}
            with _bench_env(**env):
                cluster = FleetCluster(
                    workdir, args.ab_nodes, args.ab_shards,
                    args.preload_keys, args.value_bytes,
                    args.write_window, args.read_info_ttl_ms,
                    args.transport, args.executor_threads,
                    with_admin=False, extra_env=env)
                try:
                    cluster.wait_catchup(total_keys)
                    acked: set = set()
                    res = cluster.ioloop.run_sync(
                        _run_open_loop(
                            cluster, ReadPolicy.follower_ok(args.max_lag),
                            args.ab_rate, args.ab_load_sec, total_keys,
                            args.value_bytes, mix, args.seed + rep_n[0],
                            args.max_inflight, acked_puts=acked),
                        timeout=args.ab_load_sec + 240)
                    summary = res.summarize(args.ab_rate,
                                            args.ab_load_sec)
                    time.sleep(1.0)  # drain the replication tail
                    f0, p0 = _frames_and_parked(cluster)
                    t0 = time.monotonic()
                    time.sleep(args.ab_idle_sec)
                    f1, p1 = _frames_and_parked(cluster)
                    idle = time.monotonic() - t0
                    rb = readback_acked(cluster, acked,
                                        args.value_bytes)
                    mc = cluster.counter_sums(("replicator.mux_",))
                    put = summary["ops"].get("put") or {}
                    return {
                        "idle_frames_per_node_sec": round(
                            (f1 - f0) / idle / cluster.nodes, 2),
                        "parked_per_node": round(
                            (p0 + p1) / 2 / cluster.nodes, 2),
                        "applied_puts_per_sec": round(
                            put.get("count", 0) / args.ab_load_sec, 1),
                        "get_p99_ms": (summary["ops"].get("get")
                                       or {}).get("p99_ms"),
                        "acked_loss": rb["lost"],
                        "acked_sampled": rb["sampled"],
                        "value_mismatches": summary["value_mismatches"],
                        "mux_pulls": mc.get("replicator.mux_pulls", 0.0),
                        "mux_fallbacks": mc.get(
                            "replicator.mux_fallbacks", 0.0),
                    }
                finally:
                    cluster.stop()

        return thunk

    log(f"mux A/B: {args.ab_nodes} nodes x {args.ab_shards} shards, "
        f"{args.ab_reps} reps, load {args.ab_rate}/s x "
        f"{args.ab_load_sec}s, idle window {args.ab_idle_sec}s")
    ab = run_interleaved(
        [("mux_off", arm("mux_off")), ("mux_on", arm("mux_on"))],
        reps=args.ab_reps, key="idle_frames_per_node_sec",
        baseline="mux_off", higher_is_better=False, log=log)
    return {
        "bench": "fleet_mux_ab",
        "topology": {"nodes": args.ab_nodes, "shards": args.ab_shards,
                     "replication_factor": REPLICATION_FACTOR},
        "config": {"rate": args.ab_rate, "load_sec": args.ab_load_sec,
                   "idle_sec": args.ab_idle_sec,
                   "frames_factor": args.ab_frames_factor,
                   "parked_factor": args.ab_parked_factor},
        "ab": ab,
        "failures": mux_ab_failures(ab, args.ab_frames_factor,
                                    args.ab_parked_factor,
                                    args.ab_p99_factor),
    }


def _median(vals: List[float]) -> Optional[float]:
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    return percentile(vals, 50.0)


def mux_ab_failures(ab: Dict, frames_factor: float,
                    parked_factor: float,
                    p99_factor: float = 1.5) -> List[str]:
    fails: List[str] = []
    samples = ab.get("samples") or {}
    for armname in ("mux_off", "mux_on"):
        if not samples.get(armname):
            fails.append(f"no completed {armname} rep")
    for armname, reps in samples.items():
        for s in reps:
            if s["acked_loss"]:
                fails.append(f"{armname}: {s['acked_loss']} acked puts "
                             f"lost (of {s['acked_sampled']})")
            if s["value_mismatches"]:
                fails.append(f"{armname}: {s['value_mismatches']} "
                             "value mismatches")
    for s in samples.get("mux_on") or []:
        if s["mux_pulls"] <= 0:
            fails.append("mux_on arm recorded zero mux pulls")
        if s["mux_fallbacks"] > 0:
            fails.append(f"mux_on arm fell back per-shard "
                         f"{int(s['mux_fallbacks'])}x")
    for s in samples.get("mux_off") or []:
        if s["mux_pulls"] > 0:
            fails.append("mux_off arm recorded mux pulls")
    if fails:
        return fails

    def med(armname, field):
        return _median([s[field] for s in samples[armname]])

    off_f, on_f = med("mux_off", "idle_frames_per_node_sec"), \
        med("mux_on", "idle_frames_per_node_sec")
    if on_f is None or off_f is None or on_f <= 0:
        fails.append("frame medians missing/zero")
    elif off_f / on_f < frames_factor:
        fails.append(f"idle frames/node only {off_f / on_f:.1f}x lower "
                     f"with mux ({off_f} -> {on_f}), need >= "
                     f"{frames_factor}x")
    off_p, on_p = med("mux_off", "parked_per_node"), \
        med("mux_on", "parked_per_node")
    if on_p is None or off_p is None or on_p <= 0:
        fails.append("parked-longpoll medians missing/zero")
    elif off_p / on_p < parked_factor:
        fails.append(f"parked long-polls/node only {off_p / on_p:.1f}x "
                     f"lower with mux ({off_p} -> {on_p}), need >= "
                     f"{parked_factor}x")
    off_a, on_a = med("mux_off", "applied_puts_per_sec"), \
        med("mux_on", "applied_puts_per_sec")
    if off_a and on_a and (on_a < 0.75 * off_a or off_a < 0.75 * on_a):
        fails.append(f"applied put throughput not equal: off {off_a}/s "
                     f"vs on {on_a}/s")
    off_p99, on_p99 = med("mux_off", "get_p99_ms"), \
        med("mux_on", "get_p99_ms")
    if off_p99 is not None and on_p99 is not None \
            and on_p99 > off_p99 * p99_factor + 1.0:
        fails.append(f"get p99 worse with mux: {off_p99}ms -> "
                     f"{on_p99}ms")
    return fails


# ---------------------------------------------------------------------------
# entrypoint
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--nodes", type=int, default=10)
    p.add_argument("--shards", type=int, default=100)
    p.add_argument("--preload_keys", type=int, default=100,
                   help="keys preloaded PER SHARD")
    p.add_argument("--value_bytes", type=int, default=128)
    p.add_argument("--write_window", type=int, default=64)
    p.add_argument("--read_info_ttl_ms", type=int, default=1500)
    p.add_argument("--executor_threads", type=int, default=2)
    p.add_argument("--transport", default="tcp", choices=["tcp", "uds"])
    p.add_argument("--rate", type=float, default=600.0)
    p.add_argument("--duration", type=float, default=5.0,
                   help="seconds per timeline phase")
    p.add_argument("--mix", default="get=0.75,put=0.15,"
                                    "multi_get=0.05,scan=0.05")
    p.add_argument("--max_lag", type=int, default=4096)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--max_inflight", type=int, default=384)
    p.add_argument("--phases",
                   default="baseline,diurnal,hot_shift,node_kill,"
                           "drain,cdc_burst,cooldown")
    p.add_argument("--kill_node", type=int, default=1)
    p.add_argument("--drain_node", type=int, default=2)
    p.add_argument("--cdc_records", type=int, default=150,
                   help="CDC records per burst shard")
    p.add_argument("--cdc_value_bytes", type=int, default=200)
    p.add_argument("--cdc_drain_timeout", type=float, default=60.0)
    p.add_argument("--ab", action="store_true",
                   help="run the mux on/off A/B instead of the timeline")
    p.add_argument("--ab_nodes", type=int, default=8)
    p.add_argument("--ab_shards", type=int, default=64)
    p.add_argument("--ab_reps", type=int, default=2)
    p.add_argument("--ab_rate", type=float, default=400.0)
    p.add_argument("--ab_load_sec", type=float, default=6.0)
    p.add_argument("--ab_idle_sec", type=float, default=6.0)
    p.add_argument("--ab_frames_factor", type=float, default=5.0,
                   help="required idle frames/node reduction (mux off "
                        "/ mux on); the ring layout predicts ~S/N")
    p.add_argument("--ab_parked_factor", type=float, default=5.0)
    p.add_argument("--ab_p99_factor", type=float, default=1.5,
                   help="get p99 with mux may be at most this factor "
                        "of the mux-off median (+1ms slack); smokes "
                        "with short windows and few reps relax it")
    p.add_argument("--out")
    args = p.parse_args(argv)

    with tempfile.TemporaryDirectory(prefix="fleet_bench_") as root:
        if args.ab:
            result = run_mux_ab(args, root)
        else:
            result = run_timeline(args, root)
        result["host_calibration"] = host_calibration(root)
        return emit_gated_artifact(
            result, args.out, result["bench"], log=log)


if __name__ == "__main__":
    sys.exit(main())
