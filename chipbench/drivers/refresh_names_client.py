#!/usr/bin/env python3
"""The client half of the traffic driver ``refresh_names``: ``refresh``'s
client (``refresh_client.py``: the loops, the timing, the read-back, the
JSON lines, the size of a pre-load ``WriteBatch``) over counters named
``counter-<n>``. Only the data differs: keys, pre-load, probes and model
are ``workload_names``'s (``workload.SlotModel`` as it stands).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import workload_names as wn  # noqa: E402
from chipbench.drivers import refresh_client as rc  # noqa: E402


class NamesClient(rc.Client):
    def prepare(self) -> None:
        """One model, one list of encoded pre-load batches and one
        read-back sample per slot; the readers' zipfian over every record
        of the segment (``refresh_client.Client.prepare`` over
        ``workload_names``)."""
        from rocksplicator_tpu.storage.records import WriteBatch

        for s in range(self.slots):
            ops = wn.preload_ops(self.seed, s, self.rows) if self.live else []
            raws = []
            for lo in range(0, len(ops), rc.WRITE_BATCH_OPS):
                wb = WriteBatch()
                for kind, key, value in ops[lo:lo + rc.WRITE_BATCH_OPS]:
                    if kind == wn.PUT:
                        wb.put(key, wn.encode_value(value))
                    else:
                        wb.merge(key, wn.encode_value(value))
                raws.append(wb.encode())
            self.write_batches.append(raws)
            self.probes.append(wn.probe_keys(
                self.seed, s, self.rows, rc.PROBES_PER_SLOT, self.live))
            self.models.append(wn.slot_model(
                self.seed, s, self.rows, self.live))
            if self.control:
                self.controls.append(wn.slot_model(
                    self.seed, s, self.rows, self.live, self.control))
        self.per_slot = self.rows + (
            wn.live_counters(self.rows) if self.live else 0)
        n = self.slots * self.per_slot
        weights = 1.0 / np.arange(1, n + 1) ** float(
            self.traffic["read_zipf_constant"])
        self._rank_cdf = np.cumsum(weights / weights.sum())
        self._scramble = np.random.default_rng(
            [self.seed, 0, rc.STREAM_SCRAMBLE]).permutation(n)

    def record(self, index: int):
        """(slot, key) of the segment's ``index``-th record."""
        slot, i = divmod(int(index), self.per_slot)
        return slot, (wn.bulk_key(slot, i) if i < self.rows
                      else wn.live_key(slot, i - self.rows))


if __name__ == "__main__":
    # refresh_client.main() drives whatever class its module calls Client
    rc.Client = NamesClient
    sys.exit(rc.main())
