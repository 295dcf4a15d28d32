#!/usr/bin/env python3
"""The client half of the traffic driver ``refresh_rec``: ``refresh``'s
client (``refresh_client.py``: the loops, the timing, the read-back, the
JSON lines) over wide records. Only the data differs: the pre-load is
live PUTs of ``value_bytes`` bytes, the model is ``workload_rec.RecModel``
(a tag per key, the bytes rebuilt to compare every one of them), and a
``write`` RPC carries ``WRITE_BATCH_OPS`` operations.

What is fixed here and not in the mix's file, because no source states
it: the size of a pre-load ``WriteBatch`` (the configuration lists it
under ``assumed``).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import workload as wl  # noqa: E402
from chipbench import workload_rec as wr  # noqa: E402
from chipbench.drivers import refresh_client as rc  # noqa: E402

WRITE_BATCH_OPS = 128  # live PUTs per write RPC: 128 KB of values a frame


class RecClient(rc.Client):
    def __init__(self, hello: dict):
        super().__init__(hello)
        self.value_bytes = int(hello["config"]["value_bytes"])

    def prepare(self) -> None:
        """One model, one list of encoded pre-load batches and one
        read-back sample per slot; the readers' zipfian over every record
        of the segment."""
        from rocksplicator_tpu.storage.records import WriteBatch

        for s in range(self.slots):
            ops = wr.preload_ops(self.seed, s, self.rows) if self.live else []
            vals = wr.values(
                self.seed, s, [wr._slot_index(key) for key, _n in ops],
                [n for _key, n in ops], self.value_bytes)
            raws = []
            for lo in range(0, len(ops), WRITE_BATCH_OPS):
                wb = WriteBatch()
                for j in range(lo, min(lo + WRITE_BATCH_OPS, len(ops))):
                    wb.put(ops[j][0], vals[j].tobytes())
                raws.append(wb.encode())
            self.write_batches.append(raws)
            self.probes.append(wl.probe_keys(
                self.seed, s, self.rows, rc.PROBES_PER_SLOT, self.live))
            self.models.append(wr.slot_model(
                self.seed, s, self.rows, self.value_bytes, self.live))
            if self.control:
                self.controls.append(wr.slot_model(
                    self.seed, s, self.rows, self.value_bytes, self.live,
                    self.control))
        self.zipfian()

    def zipfian(self) -> None:
        """The readers' scrambled zipfian over every record of the
        segment, as ``refresh_client.Client.prepare`` sets it up."""
        import numpy as np

        self.per_slot = self.rows + (
            wl.live_counters(self.rows) if self.live else 0)
        n = self.slots * self.per_slot
        weights = 1.0 / np.arange(1, n + 1) ** float(
            self.traffic["read_zipf_constant"])
        self._rank_cdf = np.cumsum(weights / weights.sum())
        self._scramble = np.random.default_rng(
            [self.seed, 0, rc.STREAM_SCRAMBLE]).permutation(n)


if __name__ == "__main__":
    # refresh_client.main() drives whatever class its module calls Client
    rc.Client = RecClient
    sys.exit(rc.main())
