#!/usr/bin/env python3
"""The client half of the traffic driver ``refresh_mixgraph``:
``refresh_rec``'s client (``refresh_client.py``: the loops, the timing,
the read-back, the JSON lines; ``refresh_rec_client.WRITE_BATCH_OPS``
live PUTs a ``write`` RPC) over db_bench MixGraph's records. Only the
data differs: keys, values, pre-load, probes and model are
``workload_mixgraph``'s, and a reader draws a key range by MixGraph's
two-term exponential and then a held key uniformly inside it
(``workload_mixgraph.read_weights``) in place of YCSB's zipfian.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from chipbench import workload_mixgraph as wm  # noqa: E402
from chipbench.drivers import refresh_client as rc  # noqa: E402
from chipbench.drivers import refresh_rec_client as rrc  # noqa: E402


class MixGraphClient(rrc.RecClient):
    def __init__(self, hello: dict):
        # the configuration's value_bytes is a range ("10-1024"), not
        # RecClient's one width: the widths are workload_mixgraph's
        super(rrc.RecClient, self).__init__(hello)

    def prepare(self) -> None:
        """One model, one list of encoded pre-load batches and one
        read-back sample per slot; the readers' key-range draw over every
        held record of the segment."""
        from rocksplicator_tpu.storage.records import WriteBatch

        for s in range(self.slots):
            ops = wm.preload_ops(self.seed, s, self.rows) if self.live else []
            vals = wm.values(self.seed, s, [wm._slot_index(k) for k, _n in ops],
                             [n for _k, n in ops])
            raws = []
            for lo in range(0, len(ops), rrc.WRITE_BATCH_OPS):
                wb = WriteBatch()
                for j in range(lo, min(lo + rrc.WRITE_BATCH_OPS, len(ops))):
                    wb.put(ops[j][0], vals[j])
                raws.append(wb.encode())
            self.write_batches.append(raws)
            self.probes.append(wm.probe_keys(
                self.seed, s, self.rows, rc.PROBES_PER_SLOT, self.live))
            self.models.append(wm.slot_model(
                self.seed, s, self.rows, self.live))
            if self.control:
                self.controls.append(wm.slot_model(
                    self.seed, s, self.rows, self.live, self.control))
        self.per_slot = self.rows + (
            wm.live_counters(self.rows) if self.live else 0)
        # the window's readers draw an index by this CDF
        # (refresh_client.Client.window), in record order: no scramble
        self._rank_cdf = np.cumsum(wm.read_weights(
            self.seed, self.slots, self.rows, self.live))
        self._scramble = np.arange(self.slots * self.per_slot)

    def record(self, index: int):
        """(slot, key) of the segment's ``index``-th record."""
        slot, i = divmod(int(index), self.per_slot)
        return slot, (wm.bulk_key(slot, i) if i < self.rows
                      else wm.live_key(slot, i - self.rows))


if __name__ == "__main__":
    # refresh_client.main() drives whatever class its module calls Client
    rc.Client = MixGraphClient
    sys.exit(rc.main())
