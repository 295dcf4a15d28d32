#!/usr/bin/env python
"""BASELINE config #4-shaped benchmark: 3-replica semi-sync WAL tail.

Orchestrates a leader (replication mode 1: every write acks only after
a follower pulled it) and two followers tailing the leader's WAL over
the replication plane, on a selectable RPC byte layer:

- ``--transport tcp`` (default) — three OS processes over loopback TCP,
  the seed topology;
- ``--transport uds``  — the same three processes over the per-port
  unix-domain sockets (vectored sendmsg frame coalescing);
- ``--transport loopback`` — leader + followers COLOCATED in one
  process (``performance.py --role cluster``) over the in-process
  zero-copy loopback transport: the syscall-free ceiling.

``--transports tcp,uds,loopback --reps N`` runs the variants
INTERLEAVED (benchmarks/ab_runner.py) so same-host drift lands on every
byte layer equally, and reports median-to-median ratios vs the first.

Reports writes/s, MB/s, follower convergence, and acked-write loss.
(The config's "Kafka WAL-tail" consumer role is the CDC observer path,
covered by tests/test_admin.py + tests/test_kafka.py; this bench
measures the 3-replica semi-sync replication fabric itself.)

    python -m benchmarks.replication_3replica_bench \
        --shards 50 --keys 200 --value_bytes 1024 --transport uds

Reference harness shape: rocksdb_replicator/performance.cpp:57-207 (the
two-process original); config #4 in BASELINE.json adds the 3-replica +
WAL-tail consumer topology measured here.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.ab_runner import host_calibration, run_interleaved  # noqa: E402

TRANSPORTS = ("tcp", "uds", "loopback")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def _spawn(role, port, db_dir, shards, keys, threads, value_bytes,
           upstream_port=0, mode=1, linger=60, trace=False,
           write_window=64, executor_threads=2, transport="tcp"):
    cmd = [
        sys.executable, "-m", "rocksplicator_tpu.replication.performance",
        "--role", role, "--port", str(port), "--db_dir", db_dir,
        "--num_shards", str(shards),
        "--num_write_threads", str(threads),
        "--num_keys_per_shard_thread", str(keys),
        "--value_size", str(value_bytes),
        "--replication_mode", str(mode),
        "--linger_sec", str(linger),
        "--write_window", str(write_window),
        # this bench targets small (2-4 core) CI hosts: a lean executor
        # avoids pure GIL thrash (serve is inline on the loop; executor
        # work is cold WAL scans and follower applies)
        "--executor_threads", str(executor_threads),
    ]
    if trace:
        cmd += ["--trace"]
    if upstream_port:
        cmd += ["--upstream_port", str(upstream_port)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # explicit per-run policy: children (and their servers'
               # derived fast-path listeners) all agree by construction
               RSTPU_TRANSPORT=transport)
    return subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )


def host_roofline(tmp: str, value_bytes: int, n_writes: int = 2000) -> dict:
    """Same-host capability context (VERDICT r4 #5: the absolute
    writes/s is only interpretable against what THIS host can do).
    Measures (a) raw fsync rate — the floor under any durable ack —
    and (b) single-process engine write throughput with no replication,
    so the semi-sync number reads as a fraction of host capability
    rather than a bare absolute."""
    import tempfile as _tf

    from rocksplicator_tpu.storage.engine import DB, DBOptions

    # (a) fsync rate: append-and-fsync a small record repeatedly
    fd = os.open(os.path.join(tmp, "fsync_probe"),
                 os.O_CREAT | os.O_WRONLY | os.O_APPEND, 0o644)
    try:
        buf = b"x" * 4096
        n_fsync = 200
        t0 = time.monotonic()
        for _ in range(n_fsync):
            os.write(fd, buf)
            os.fsync(fd)
        fsync_per_sec = n_fsync / (time.monotonic() - t0)
    finally:
        os.close(fd)
    # (b) raw engine writes (no replication, async WAL)
    d = _tf.mkdtemp(dir=tmp)
    db = DB(os.path.join(d, "db"), DBOptions())
    val = b"v" * value_bytes
    t0 = time.monotonic()
    for i in range(n_writes):
        db.put(f"k{i:08d}".encode(), val)
    raw_elapsed = time.monotonic() - t0
    db.close()
    return {
        "fsync_per_sec": round(fsync_per_sec, 1),
        "engine_writes_per_sec_no_replication": round(
            n_writes / raw_elapsed, 1),
        "engine_mb_per_sec_no_replication": round(
            n_writes * value_bytes / raw_elapsed / 1e6, 2),
    }


class _LeaderReport:
    """Parsed leader stdout: throughput, acked count, trace block."""

    def __init__(self):
        self.mb = None
        self.elapsed = None
        self.acked = None
        self.total = None
        self.ack_window = None
        self.trace_lines = []
        self._in_trace = False

    def feed(self, line: str) -> bool:
        """Returns True once the throughput line landed (parse done)."""
        if line.startswith("TRACE-SLOWEST-WRITE-BEGIN"):
            self._in_trace = True
        if self._in_trace:
            self.trace_lines.append(line.rstrip("\n"))
            if line.startswith("TRACE-SLOWEST-WRITE-END"):
                self._in_trace = False
            return False
        m = re.search(
            r"TRACE-ACK-WINDOW sampled_ack_waits=(\d+) "
            r"max_overlapping=(\d+) max_window_depth=(\d+)", line)
        if m:
            self.ack_window = (int(m.group(1)), int(m.group(2)),
                               int(m.group(3)))
            return False
        m = re.search(r"leader acked (\d+)/(\d+) writes", line)
        if m:
            self.acked, self.total = int(m.group(1)), int(m.group(2))
            return False
        m = re.search(r"wrote ~([\d.]+) MB in ([\d.]+)s", line)
        if m:
            self.mb, self.elapsed = float(m.group(1)), float(m.group(2))
            return True
        return False


def run_once(args, transport: str, trace: bool = False) -> dict:
    """One full bench run on one transport; returns the results dict."""
    tmp = tempfile.mkdtemp(prefix=f"repl3-{transport}-")
    procs = []
    try:
        report = _LeaderReport()
        total_writes = args.keys * args.shards
        want = total_writes
        seqs = {0: 0, 1: 0}
        if transport == "loopback":
            # in-process colocation: ONE cluster process (the loopback
            # transport cannot cross OS processes — that's the point)
            t0 = time.monotonic()
            leader = _spawn("cluster", args.leader_port, tmp, args.shards,
                            args.keys, args.threads, args.value_bytes,
                            linger=120, trace=trace,
                            write_window=args.write_window,
                            transport=transport)
            procs.append(leader)
            for line in leader.stdout:
                log(f"[cluster] {line.rstrip()}")
                if report.feed(line):
                    break
            assert report.mb is not None, (
                "cluster leader never reported its write phase")
            deadline = time.monotonic() + 120
            for line in leader.stdout:
                m = re.search(r"follower(\d+) total seq: (\d+)", line)
                if m:
                    seqs[int(m.group(1))] = int(m.group(2))
                if "cluster converged" in line:
                    break
                if time.monotonic() > deadline:
                    break
            converge_sec = time.monotonic() - t0
        else:
            f1 = _spawn("follower", args.leader_port + 1,
                        os.path.join(tmp, "f1"), args.shards, args.keys,
                        args.threads, args.value_bytes,
                        upstream_port=args.leader_port, transport=transport)
            f2 = _spawn("follower", args.leader_port + 2,
                        os.path.join(tmp, "f2"), args.shards, args.keys,
                        args.threads, args.value_bytes,
                        upstream_port=args.leader_port, transport=transport)
            followers = [f1, f2]
            procs.extend(followers)
            time.sleep(2.0)
            t0 = time.monotonic()
            leader = _spawn("leader", args.leader_port,
                            os.path.join(tmp, "l"), args.shards, args.keys,
                            args.threads, args.value_bytes, linger=90,
                            trace=trace, write_window=args.write_window,
                            transport=transport)
            procs.append(leader)
            for line in leader.stdout:
                log(f"[leader] {line.rstrip()}")
                if report.feed(line):
                    break
            assert report.mb is not None, (
                "leader never reported its write phase")
            # watch follower convergence via their periodic seq dumps
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline and (
                    seqs[0] < want or seqs[1] < want):
                for idx, f in enumerate(followers):
                    line = f.stdout.readline()
                    if line:
                        m = re.search(r"follower total seq: (\d+)", line)
                        if m:
                            seqs[idx] = int(m.group(1))
                time.sleep(0.1)
            converge_sec = time.monotonic() - t0
        # the leader prints elapsed at 0.1s resolution: floor it so a
        # smoke-sized run can't divide by zero
        mb, elapsed = report.mb, max(report.elapsed, 0.05)
        acked = report.acked if report.acked is not None else total_writes
        results = {
            "transport": transport,
            "writes_acked": acked,
            "writes_total": total_writes,
            "leader_mb": mb,
            "leader_elapsed_s": elapsed,
            "writes_per_sec": round(total_writes / elapsed, 1),
            "acked_writes_per_sec": round(acked / elapsed, 1),
            "write_window": args.write_window,
            "mb_per_sec": round(mb / elapsed, 2),
            "follower_seqs": [seqs[0], seqs[1]],
            "both_followers_converged": bool(
                seqs[0] >= want and seqs[1] >= want),
            "convergence_sec_from_leader_start": round(converge_sec, 1),
            "acked_write_loss": max(0, want - min(seqs.values())),
        }
        if report.ack_window:
            results["ack_window_trace"] = {
                "sampled_ack_waits": report.ack_window[0],
                "max_overlapping_ack_waits": report.ack_window[1],
                "max_window_depth": report.ack_window[2],
            }
        if trace and report.trace_lines:
            results["slowest_write_trace"] = report.trace_lines
        return results
    finally:
        for p in procs:
            try:
                p.terminate()
                p.wait(timeout=10)
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=50)
    ap.add_argument("--keys", type=int, default=200)
    ap.add_argument("--threads", type=int, default=2)
    ap.add_argument("--value_bytes", type=int, default=1024)
    ap.add_argument("--write_window", type=int, default=64,
                    help="leader max in-flight (unacked) writes per shard; "
                         "1 = the old serial blocking write path")
    ap.add_argument("--leader_port", type=int, default=29391)
    ap.add_argument("--transport", choices=TRANSPORTS, default="tcp",
                    help="RPC byte layer: tcp (3 processes, seed "
                         "topology), uds (3 processes, vectored unix "
                         "sockets), loopback (colocated single process, "
                         "in-process zero-copy)")
    ap.add_argument("--transports",
                    help="comma list, e.g. tcp,uds,loopback: run an "
                         "INTERLEAVED A/B across byte layers (ratios vs "
                         "the first) instead of a single run")
    ap.add_argument("--reps", type=int, default=3,
                    help="interleaved reps for --transports mode")
    ap.add_argument("--trace", action="store_true",
                    help="sample per-write traces in the leader and report "
                         "the slowest sampled write's span tree (per-phase "
                         "attribution: wal fsync vs follower-ack wait)")
    ap.add_argument("--out",
                    default="benchmarks/results/replication_3replica.json")
    args = ap.parse_args()

    tmp = tempfile.mkdtemp(prefix="repl3-bench-")
    try:
        config = {
            "shards": args.shards, "writer_threads": args.threads,
            "keys_per_shard_thread": args.keys,
            "value_bytes": args.value_bytes,
            "write_window": args.write_window,
        }
        if args.transports:
            names = [t.strip() for t in args.transports.split(",") if t.strip()]
            for t in names:
                if t not in TRANSPORTS:
                    ap.error(f"unknown transport {t!r} "
                             f"(expected {'|'.join(TRANSPORTS)})")
            ab = run_interleaved(
                [(t, (lambda t=t: run_once(args, t, trace=args.trace)))
                 for t in names],
                reps=args.reps, key="acked_writes_per_sec", log=log)
            result = {
                "bench": "replication_3replica_semisync_transport_ab",
                "timestamp": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                "config": dict(config, transports=names,
                               topology="tcp/uds: 3 OS processes; "
                                        "loopback: colocated 1 process"),
                "ab": ab,
            }
            summary = {n: s.get("median") for n, s in
                       ab.get("summary", {}).items()}
            print(json.dumps({"acked_writes_per_sec_median": summary,
                              **{k: v for k, v in ab.items()
                                 if k.startswith("ratio_vs_")}}))
        else:
            results = run_once(args, args.transport, trace=args.trace)
            trace_lines = results.pop("slowest_write_trace", None)
            result = {
                "bench": "replication_3replica_semisync",
                "timestamp": time.strftime(
                    "%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                "config": dict(
                    config,
                    transport=args.transport,
                    topology=("leader + 2 followers colocated in ONE "
                              "process, in-process loopback transport, "
                              "replication mode 1 (semi-sync)"
                              if args.transport == "loopback" else
                              f"leader + 2 followers, 3 OS processes, "
                              f"{args.transport} loopback, replication "
                              f"mode 1 (semi-sync)"),
                ),
                "results": results,
            }
            if trace_lines:
                result["slowest_write_trace"] = trace_lines
            print(json.dumps(result["results"]))
        roof = host_roofline(tmp, args.value_bytes)
        raw_wps = roof["engine_writes_per_sec_no_replication"]
        result["host_roofline"] = roof
        if not args.transports:
            result["host_roofline"][
                "semisync_fraction_of_raw_engine"] = round(
                result["results"]["writes_per_sec"] / raw_wps, 3
            ) if raw_wps else None
        result["host_roofline"]["note"] = (
            "correctness-shaped bench on a small host: the absolute "
            "writes/s reads against the same-host raw-engine and fsync "
            "rates above, not against the reference's 32-core design "
            "point"
        )
        result["host_calibration"] = host_calibration(tmp)
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
        # a smoke gate, not just a recorder: acked loss or missed
        # convergence fails the run loudly (transport-bench-smoke
        # depends on this exit code)
        bad = []
        if args.transports:
            for name, ss in result["ab"].get("samples", {}).items():
                for s in ss:
                    if not isinstance(s, dict):
                        continue
                    if (s.get("acked_write_loss", 0)
                            or not s.get("both_followers_converged", True)):
                        bad.append(
                            f"{name}: loss={s.get('acked_write_loss')} "
                            f"converged="
                            f"{s.get('both_followers_converged')}")
        else:
            r = result["results"]
            if (r.get("acked_write_loss", 0)
                    or not r.get("both_followers_converged", True)):
                bad.append(
                    f"{args.transport}: loss={r.get('acked_write_loss')} "
                    f"converged={r.get('both_followers_converged')}")
        if bad:
            log("FAIL: " + "; ".join(bad))
            return 1
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
