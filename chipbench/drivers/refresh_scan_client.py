#!/usr/bin/env python3
"""The client half of the traffic driver ``refresh_scan``: ``refresh_rec``'s
client (``refresh_client.py``: the loops, the timing, the read-back, the
JSON lines; ``refresh_rec_client.WRITE_BATCH_OPS`` live PUTs a ``write``
RPC) under YCSB core workload E. The data is ``workload_ycsb``'s: a
unit's live writes are its inserts of new keys, the model is
``ScanModel``. A reader's call is a scan: from a record drawn by the
scrambled zipfian over every record of the segment (live inserts
included), ``count`` uniform from 1 to the mix's ``scan_length_max``,
on the data plane's ``read`` RPC with ``op="scan"``, and its reply is
compared with the model's in its keys, their order, every byte and
length of each value, and its row count. Scans are recorded as kind
``read``; the read-back after the close is the refresh client's
``multi_get``.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import workload_ycsb as wy  # noqa: E402
from chipbench.drivers import refresh_client as rc  # noqa: E402
from chipbench.drivers import refresh_rec_client as rrc  # noqa: E402

STREAM_SCAN_LENGTH = 7  # the rng stream of the scans' lengths
SCAN_LENGTHS = 1 << 16  # lengths drawn ahead, taken in turn


class ScanClient(rrc.RecClient):
    def prepare(self) -> None:
        """One model, one list of encoded insert batches and one
        read-back sample per slot; the readers' zipfian over every record
        of the segment, and the scans' lengths."""
        from rocksplicator_tpu.storage.records import WriteBatch

        self.keys = []  # per slot, its records' keys in record order
        for s in range(self.slots):
            ops = wy.insert_rows(self.seed, self.slots, self.rows, s)
            raws = []
            for lo in range(0, len(ops), rrc.WRITE_BATCH_OPS):
                wb = WriteBatch()
                for key, value in ops[lo:lo + rrc.WRITE_BATCH_OPS]:
                    wb.put(key, value)
                raws.append(wb.encode())
            self.write_batches.append(raws)
            self.keys.append(wy.keys_of(
                wy.slot_keynums(self.slots, self.rows, s)))
            self.probes.append(wy.probe_keys(
                self.slots, self.rows, s, rc.PROBES_PER_SLOT, self.seed))
            self.models.append(wy.ScanModel(
                self.seed, self.slots, self.rows, s))
            if self.control:
                self.controls.append(wy.ScanModel(
                    self.seed, self.slots, self.rows, s, self.control))
        self.per_slot = self.rows + wy.inserts(self.rows)
        n = self.slots * self.per_slot
        weights = 1.0 / np.arange(1, n + 1) ** float(
            self.traffic["read_zipf_constant"])
        self._rank_cdf = np.cumsum(weights / weights.sum())
        self._scramble = np.random.default_rng(
            [self.seed, 0, rc.STREAM_SCRAMBLE]).permutation(n)
        self._lengths = np.random.default_rng(
            [self.seed, 0, STREAM_SCAN_LENGTH]).integers(
                1, int(self.traffic["scan_length_max"]) + 1,
                SCAN_LENGTHS).tolist()
        self._scans = 0

    def record(self, index: int):
        """(slot, key) of the segment's ``index``-th record."""
        slot, i = divmod(int(index), self.per_slot)
        return slot, self.keys[slot][i]

    async def read(self, kind: str, op: str, version: int, slot: int,
                   keys) -> None:
        """A window reader's ``get`` is a scan from its key; the
        read-back's ``multi_get`` is the refresh client's."""
        if op != "get":
            return await super().read(kind, op, version, slot, keys)
        start = keys[0]
        count = self._lengths[self._scans % SCAN_LENGTHS]
        self._scans += 1
        at = (version, slot)
        self.reading[at] = self.reading.get(at, 0) + 1
        try:
            res = await self._rpc(kind, self.data(
                "read", db_name=self.db_name(version, slot), op="scan",
                start=start, count=count))
        finally:
            self.reading[at] -= 1
        if res is None:
            return
        rec = self.last
        rows = [(bytes(k), bytes(v)) for k, v in res["values"]]
        if self.control:
            # the control: the reference in the program's place, with a
            # fault this configuration could have
            rows = self.controls[slot].scan(start, count)
        want = self.models[slot].scan(start, count)
        bad = sum(1 for got, exp in zip(rows, want) if got != exp) + abs(
            len(rows) - len(want))
        if bad:
            self.mismatches += bad
            rec[2] = False
            self._note(f"MISMATCH v{version} slot {slot} scan {start!r} "
                       f"x{count}: {len(rows)} rows, {bad} wrong, model "
                       f"{len(want)}")


if __name__ == "__main__":
    # refresh_client.main() drives whatever class its module calls Client
    rc.Client = ScanClient
    sys.exit(rc.main())
