"""Traffic driver ``refresh``: rolling refresh of a segment, served
meanwhile (rocksplicator's signature use: a segment's shards are
re-loaded, version after version, from batch-built SSTs while the current
version serves reads).

A *unit* is one slot of one version, driven over the wire:

1. admin ``clear_db`` of the slot's version before last (the
   configuration's ``versions_kept``);
2. admin ``add_db`` ``seg-v<v>`` shard ``s`` (LEADER);
3. where the configuration has live counters: the slot's pre-load (MERGE
   increments and base PUTs) as ``WriteBatch``es through the data
   ``write`` RPC;
4. admin ``add_s3_sst_files_to_db(s3_bucket=<the slot's own bucket>,
   compact_db_after_load=True)``.

Bulk files and answers depend on ``(seed, s)`` only, so set-up builds one
file and one model per slot and every version re-uses them. Set-up loads
version 0 of every slot through these same calls (the warm-up). The
window runs ``in_flight`` closed-loop unit tasks taking units in order
v1s0, v1s1, … and ``readers`` closed-loop, unthrottled reader tasks that ``get`` one
record, drawn by YCSB's scrambled zipfian over every record of the
segment, from the newest acknowledged version of its slot, and compare
every reply with the model inline. At the end of the window no new unit
starts; units in flight drain. Once the window has closed a sample of the
acknowledged units, drawn from the seed, is read back.

This file is the half that lives with the node, in the process that owns
the chip: it builds the bulk files, starts the client half
(``refresh_client.py``) as a child that never imports jax, and relays
the phases to it. The loop itself, and what is fixed in it, is there.
"""

from __future__ import annotations

import json
import os
import select
import subprocess
import sys
import threading
import time
from typing import List, Optional

from .. import cluster as cl
from . import refresh_client as rc


class Op:
    """One RPC as the client saw it (monotonic seconds)."""

    __slots__ = ("kind", "sent", "done", "ok")

    def __init__(self, kind: str, sent: float, done: float, ok: bool):
        self.kind, self.sent, self.done, self.ok = kind, sent, done, ok


class Refresh:
    def __init__(self, cluster: cl.Cluster, root: str, config: dict,
                 traffic: dict, seed: int, control: Optional[str] = None):
        self.cluster, self.root = cluster, root
        self.config, self.traffic = config, traffic
        self.seed, self.control = seed, control
        self.buckets = os.path.join(root, "buckets")
        self.child: Optional[subprocess.Popen] = None
        self._buf = b""
        self.ops: List[Op] = []       # since the window opened
        self.units: List[dict] = []   # acknowledged since the window opened
        self.mismatches = 0
        self.rpc_failures = 0
        self.first_mismatches: List[str] = []
        self.read_back_units = 0

    # -- the child ---------------------------------------------------------

    def _send(self, obj: dict) -> None:
        self.child.stdin.write((json.dumps(obj) + "\n").encode())
        self.child.stdin.flush()

    def _expect(self, key: str, timeout: float):
        """The child's next answer, which has to hold ``key``."""
        fd, deadline = self.child.stdout.fileno(), time.monotonic() + timeout
        while b"\n" not in self._buf:
            left = deadline - time.monotonic()
            if left <= 0 or not select.select([fd], [], [], left)[0]:
                raise RuntimeError(f"the client said nothing of {key!r} in "
                                   f"{timeout:.0f} s")
            chunk = os.read(fd, 1 << 20)
            if not chunk:
                raise RuntimeError(
                    f"the client ended (code {self.child.wait()}) before "
                    f"it said {key!r}")
            self._buf += chunk
        line, self._buf = self._buf.split(b"\n", 1)
        answer = json.loads(line)
        if key not in answer:
            raise RuntimeError(f"the client said {answer}, not {key!r}")
        return answer

    # -- the phases --------------------------------------------------------

    def prepare(self) -> None:
        """Start the client (it makes its models from the seed meanwhile)
        and build one bulk file per slot, each in the slot's own bucket."""
        from rocksplicator_tpu.utils.objectstore import LocalObjectStore

        env = dict(os.environ, JAX_PLATFORMS="cpu")  # it never needs a chip
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(rc.__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self._send({"hello": {
            "admin_port": self.cluster.server.port,
            "data_port": self.cluster.replicator.port,
            "buckets": self.buckets, "config": self.config,
            "traffic": self.traffic, "seed": self.seed,
            "control": self.control}})
        for s in range(int(self.config["slots"])):
            cl.build_bulk_sst(
                LocalObjectStore(rc.slot_bucket(self.buckets, s)),
                self.root, self.seed, s, int(self.config["rows_per_slot"]),
                rc.SST_PATH)
        self._expect("ready", 300.0)

    def warm(self) -> None:
        self._send({"cmd": "warm"})
        answer = self._expect(
            "warmed", rc.RPC_TIMEOUT + 60 * int(self.config["slots"]))
        if not answer["warmed"]:
            raise RuntimeError(f"version 0 did not load: {answer['notes']}")

    def run_window(self, seconds: float, tracer=None) -> dict:
        """The measured window, then the drain. ``tracer`` is a blocking
        function ``(t0, seconds)`` run on a thread beside the window (the
        profiler's slice). Returns the window's bounds on both clocks."""
        self._send({"cmd": "window", "seconds": seconds})
        bounds = self._expect("opened", 60.0)["opened"]
        thread = None
        if tracer is not None:
            thread = threading.Thread(target=tracer,
                                      args=(bounds["t0"], seconds))
            thread.start()
        bounds.update(self._expect("closed", seconds + rc.RPC_TIMEOUT)
                      ["closed"])
        if thread is not None:
            thread.join()
        bounds["t1"] = bounds["t0"] + seconds
        return bounds

    def verify(self) -> int:
        """The read-back, and with it the client's whole record of the
        window. Returns the number of keys read back."""
        self._send({"cmd": "verify"})
        result = self._expect("result", rc.RPC_TIMEOUT)["result"]
        self.ops = [Op(kind, sent, done, ok)
                    for kind, recs in result["ops"].items()
                    for sent, done, ok in recs]
        self.units = result["units"]
        self.mismatches = result["mismatches"]
        self.rpc_failures = result["rpc_failures"]
        self.first_mismatches = result["notes"]
        self.read_back_units = result["read_back_units"]
        return result["compared_keys"]

    def close(self) -> None:
        """Stop the client and wait until it has ended."""
        if self.child is None:
            return
        try:
            self._send({"cmd": "quit"})
            self.child.wait(timeout=30)
        except Exception:
            self.child.kill()
            self.child.wait()
        for pipe in (self.child.stdin, self.child.stdout):
            pipe.close()
        self.child = None


make = Refresh  # what run.py calls: make(cluster, root, config, traffic,
#                 seed, control)
