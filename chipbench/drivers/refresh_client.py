#!/usr/bin/env python3
"""The client half of the traffic driver ``refresh``, in a process of its
own: it never imports jax and never touches the chip, so the latencies it
reads are the served system's and not its own wait for the server's
interpreter. ``refresh.py`` (the parent, which owns the node and the chip)
starts it, and the two talk in JSON lines: commands on standard input,
answers on standard output; ``time.monotonic()`` is one clock for both.

    {"hello": ...}   first line: ports, configuration, mix, seed, control
    <- {"ready": seconds}                  data and models made from the seed
    {"cmd": "warm"}   <- {"warmed": bool, "notes": [...]}    version 0
    {"cmd": "window", "seconds": s}
    <- {"opened": {"t0", "wall0"}}   then   <- {"closed": {"drained"}}
    {"cmd": "verify"} <- {"result": {...}}                   the read-back
    {"cmd": "quit"}

Both loops are closed and unthrottled, as a refresh job's, YCSB's client
threads (no ``-target``) and upstream's ``stress_test`` are: a task sends
its next call when the last is answered. What is fixed here and not in
the mix's file, because no source states it: the size of a pre-load
``WriteBatch`` and how much the read-back reads.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import workload as wl  # noqa: E402

SEGMENT = "seg-v{version}"
RPC_TIMEOUT = 1100.0     # a cold compile rides the first ingest RPC
WRITE_BATCH_OPS = 512    # pre-load operations per write RPC
PROBES_PER_SLOT = 256    # bulk keys of a slot the read-back reads
VERIFY_UNITS = 32        # units the read-back reads
READBACK_KEYS = 64       # keys per multi_get of the read-back
STREAM_READER, STREAM_VERIFY, STREAM_SCRAMBLE = 3, 4, 5  # rng streams


def slot_bucket(buckets: str, slot: int) -> str:
    """The slot's own bucket: a listing costs one prefix, as in S3."""
    return os.path.join(buckets, f"slot{slot:05d}")


SST_PATH = "sst"  # the prefix of the slot's files inside its bucket


class Client:
    def __init__(self, hello: dict):
        from rocksplicator_tpu.rpc import IoLoop, RpcClientPool

        self.admin_port = int(hello["admin_port"])
        self.data_port = int(hello["data_port"])
        self.buckets = hello["buckets"]
        config, traffic = hello["config"], hello["traffic"]
        self.traffic = traffic
        self.seed = int(hello["seed"])
        self.control = hello.get("control")  # None, "bits32" or "fold32"
        self.slots = int(config["slots"])
        self.rows = int(config["rows_per_slot"])
        self.live = bool(config["live_counters"])
        self.kept = int(config["versions_kept"])
        self.in_flight = int(traffic["in_flight"])
        self.ioloop = IoLoop.default()
        self.pool = RpcClientPool()
        self.write_batches: List[List[bytes]] = []
        self.probes: List[List[bytes]] = []
        self.models: List[wl.SlotModel] = []
        self.controls: List[wl.SlotModel] = []
        self.newest = [-1] * self.slots      # newest acknowledged version
        self.dropped = set()                 # (version, slot) cleared since
        self.reading: Dict[tuple, int] = {}  # reads on their way to each
        self.ops: Dict[str, list] = {}       # kind -> [[sent, done, ok]]
        self.units: List[dict] = []          # acknowledged units
        self.last: list = []                 # the newest RPC's record
        self.mismatches = 0
        self.rpc_failures = 0
        self.notes: List[str] = []

    # -- data from the seed ------------------------------------------------

    def prepare(self) -> None:
        """One model, one list of encoded pre-load batches and one
        read-back sample per slot; the readers' zipfian over every record
        of the segment."""
        from rocksplicator_tpu.storage.records import WriteBatch

        for s in range(self.slots):
            ops = wl.preload_ops(self.seed, s, self.rows) if self.live else []
            raws = []
            for lo in range(0, len(ops), WRITE_BATCH_OPS):
                wb = WriteBatch()
                for kind, key, value in ops[lo:lo + WRITE_BATCH_OPS]:
                    if kind == wl.PUT:
                        wb.put(key, wl.encode_value(value))
                    else:
                        wb.merge(key, wl.encode_value(value))
                raws.append(wb.encode())
            self.write_batches.append(raws)
            self.probes.append(wl.probe_keys(
                self.seed, s, self.rows, PROBES_PER_SLOT, self.live))
            self.models.append(wl.slot_model(
                self.seed, s, self.rows, self.live))
            if self.control:
                self.controls.append(wl.slot_model(
                    self.seed, s, self.rows, self.live, self.control))
        self.per_slot = self.rows + (
            wl.live_counters(self.rows) if self.live else 0)
        n = self.slots * self.per_slot
        weights = 1.0 / np.arange(1, n + 1) ** float(
            self.traffic["read_zipf_constant"])
        self._rank_cdf = np.cumsum(weights / weights.sum())
        self._scramble = np.random.default_rng(
            [self.seed, 0, STREAM_SCRAMBLE]).permutation(n)

    def record(self, index: int):
        """(slot, key) of the segment's ``index``-th record."""
        slot, i = divmod(int(index), self.per_slot)
        return slot, (wl.bulk_key(slot, i) if i < self.rows
                      else wl.live_key(slot, i - self.rows))

    def db_name(self, version: int, slot: int) -> str:
        from rocksplicator_tpu.utils.segment_utils import segment_to_db_name

        return segment_to_db_name(SEGMENT.format(version=version), slot)

    # -- the calls ---------------------------------------------------------

    def admin(self, method: str, **args):
        return self.pool.call("127.0.0.1", self.admin_port, method, args,
                              timeout=RPC_TIMEOUT)

    def data(self, method: str, **args):
        return self.pool.call("127.0.0.1", self.data_port, method, args,
                              timeout=RPC_TIMEOUT)

    async def _rpc(self, kind: str, call) -> Optional[dict]:
        """The RPC's answer (None where it failed), timed into a record
        ``[sent, done, ok]`` of its kind; ``self.last`` is that record
        until the caller's next ``await``."""
        rec = [time.monotonic(), 0.0, True]
        self.ops.setdefault(kind, []).append(rec)
        try:
            res = await call
        except Exception as e:  # a refused or failed RPC is a failed op
            rec[1], rec[2] = time.monotonic(), False
            self.rpc_failures += 1
            self._note(f"{kind} failed: {type(e).__name__}: {e}")
            return None
        rec[1] = time.monotonic()
        self.last = rec
        return res

    def _note(self, msg: str) -> None:
        if len(self.notes) < 8:
            self.notes.append(msg[:300])

    async def unit(self, version: int, slot: int) -> bool:
        """One slot of one version: drop the version before last, add the
        new one, pre-load it through the write path, load and compact."""
        db = self.db_name(version, slot)
        started = time.monotonic()
        old = version - self.kept
        if 0 <= old < self.newest[slot]:  # never the version being read
            self.dropped.add((old, slot))
            while self.reading.get((old, slot)):  # nor one with a read on
                await asyncio.sleep(0.001)        # its way (sent earlier)
            if await self._rpc("drop", self.admin(
                    "clear_db", db_name=self.db_name(old, slot),
                    reopen_db=False)) is None:
                return False
        if await self._rpc("add_db", self.admin(
                "add_db", db_name=db, role="LEADER")) is None:
            return False
        for raw in self.write_batches[slot]:
            if await self._rpc("write", self.data(
                    "write", db_name=db, raw_batch=raw)) is None:
                return False
        res = await self._rpc("ingest", self.admin(
            "add_s3_sst_files_to_db", db_name=db,
            s3_bucket=slot_bucket(self.buckets, slot), s3_path=SST_PATH,
            compact_db_after_load=True))
        if res is None:
            return False
        rec = self.last
        if res.get("ingested_files") != 1:
            rec[2] = False
            self.rpc_failures += 1
            self._note(f"ingest of {db} answered {res}")
            return False
        self.units.append({"version": version, "slot": slot,
                           "started": started, "acked": rec[1]})
        self.newest[slot] = max(self.newest[slot], version)
        return True

    async def read(self, kind: str, op: str, version: int, slot: int,
                   keys: List[bytes]) -> None:
        """One read RPC (``get`` of one key in the window, ``multi_get``
        in the read-back) and its comparison with the model."""
        at = (version, slot)
        self.reading[at] = self.reading.get(at, 0) + 1
        try:
            res = await self._rpc(kind, self.data(
                "read", db_name=self.db_name(version, slot), op=op,
                keys=keys))
        finally:
            self.reading[at] -= 1
        if res is None:
            return
        rec, values = self.last, res["values"]
        if self.control:
            # the control: the reference in the program's place, in the
            # next narrower arithmetic
            values = [self.controls[slot].get(k) for k in keys]
        bad, model = 0, self.models[slot]
        if len(values) != len(keys):
            bad = len(keys)
        for key, value in zip(keys, values):
            value = None if value is None else bytes(value)
            if value != model.get(key):
                bad += 1
                self._note(f"MISMATCH v{version} slot {slot} {key!r}: "
                           f"{value!r} != {model.get(key)!r}")
        if bad:
            self.mismatches += bad
            rec[2] = False

    # -- the phases --------------------------------------------------------

    def warm(self) -> bool:
        """Set-up: version 0 of every slot through the window's own
        calls, at the window's own fan-out."""
        async def go():
            todo = iter(range(self.slots))

            async def worker():
                for s in todo:
                    if not await self.unit(0, s):
                        return False
                return True

            return all(await asyncio.gather(*(
                worker() for _ in range(self.in_flight))))

        return self.ioloop.run_sync(
            go(), timeout=RPC_TIMEOUT + 60 * self.slots)

    def window(self, seconds: float, say) -> None:
        """The measured window, then the drain: no unit starts after the
        close, those in flight are waited for."""
        async def go():
            self.ops, self.units = {}, []
            t0 = time.monotonic()
            say({"opened": {"t0": t0, "wall0": time.time()}})
            deadline = t0 + seconds
            units = ((v, s) for v in range(1, 1 << 30)
                     for s in range(self.slots))

            async def unit_worker():
                while time.monotonic() < deadline:
                    await self.unit(*next(units))

            async def reader(i: int):
                rng = np.random.default_rng([self.seed, i, STREAM_READER])
                while True:
                    ranks = np.searchsorted(self._rank_cdf, rng.random(256))
                    for rank in np.minimum(ranks, len(self._scramble) - 1):
                        if time.monotonic() >= deadline:
                            return
                        slot, key = self.record(self._scramble[rank])
                        await self.read("read", "get", self.newest[slot],
                                        slot, [key])

            tasks = [unit_worker() for _ in range(self.in_flight)]
            tasks += [reader(i) for i in range(int(self.traffic["readers"]))]
            await asyncio.gather(*tasks)
            say({"closed": {"drained": time.monotonic()}})

        self.ioloop.run_sync(go(), timeout=seconds + RPC_TIMEOUT)

    def verify(self) -> dict:
        """Once the window has closed: read back, in full sample, units
        drawn from the seed among those acknowledged since the window
        opened and not dropped since (the last ones, drained after the
        close, always among them)."""
        mine = [u for u in self.units
                if (u["version"], u["slot"]) not in self.dropped]
        tail = mine[-self.in_flight:]
        rest = mine[:len(mine) - len(tail)]
        rng = np.random.default_rng([self.seed, 0, STREAM_VERIFY])
        extra = max(0, min(VERIFY_UNITS, len(mine)) - len(tail))
        sample = tail + [rest[int(j)] for j in rng.choice(
            len(rest), size=min(extra, len(rest)), replace=False)]
        compared = 0

        async def go():
            nonlocal compared
            for u in sample:
                keys = self.probes[u["slot"]]
                for lo in range(0, len(keys), READBACK_KEYS):
                    chunk = keys[lo:lo + READBACK_KEYS]
                    await self.read("read_back", "multi_get", u["version"],
                                    u["slot"], chunk)
                    compared += len(chunk)

        self.ioloop.run_sync(go(), timeout=RPC_TIMEOUT)
        return {"ops": self.ops, "units": self.units,
                "mismatches": self.mismatches,
                "rpc_failures": self.rpc_failures, "notes": self.notes,
                "compared_keys": compared, "read_back_units": len(sample)}

    def close(self) -> None:
        self.ioloop.run_sync(self.pool.close(), timeout=30)


def main() -> int:
    out = os.fdopen(os.dup(sys.stdout.fileno()), "w")
    sys.stdout = sys.stderr  # nothing but answers on the pipe

    def say(obj: dict) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    t = time.monotonic()
    client = Client(json.loads(sys.stdin.readline())["hello"])
    client.prepare()
    say({"ready": time.monotonic() - t})
    for line in sys.stdin:
        cmd = json.loads(line)
        if cmd["cmd"] == "warm":
            say({"warmed": client.warm(), "notes": client.notes})
        elif cmd["cmd"] == "window":
            client.window(float(cmd["seconds"]), say)
        elif cmd["cmd"] == "verify":
            say({"result": client.verify()})
        elif cmd["cmd"] == "quit":
            break
    client.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
