"""Traffic driver ``refresh_names``: ``refresh``'s rolling refresh of a
segment (``refresh.py``: the unit, the window, the drain, the read-back)
over counters under the counter service's own key shape: names
``counter-<n>`` of 9 to 15 bytes, seven key lengths in every shard
(``workload_names.py``, the configuration's copy of the reference).

This half lives with the node. It differs from ``Refresh`` in set-up
only:

- ``prepare()`` first ASKS THE PROGRAM how long a key of a shard of
  DIFFERING key lengths its served device door takes
  (``tpu.compaction_service.device_mixed_key_bytes_max``), and ends the
  run, nonzero, "nothing was built", where the function is missing (a
  program from before it, as a parent commit is) or says fewer bytes
  than the configuration's longest name: such a program declines every
  shard of the cell to the host's per-entry path (``key_width``), and
  the run would measure nothing of this cell;
- the bulk files hold ``workload_names.bulk_rows`` (bytewise key order);
- the client is ``refresh_names_client.py``.
"""

from __future__ import annotations

import os
import subprocess
import sys

from .. import cluster as cl
from .. import workload_names as wn
from . import refresh_client as rc
from . import refresh_names_client as rnc
from .refresh import Refresh


def device_takes(config: dict) -> str:
    """Why the program's device path does NOT take a shard of the
    configuration's key lengths ('' where it does), asked of the program
    itself."""
    try:
        from rocksplicator_tpu.tpu.compaction_service import (
            device_mixed_key_bytes_max)
    except ImportError:
        return ("the program has no tpu.compaction_service."
                "device_mixed_key_bytes_max: it cannot say whether its "
                "device path takes a shard whose keys differ in length")
    operator = cl.options_generator(config["options"])("seg").merge_operator
    limit = int(device_mixed_key_bytes_max(operator))
    longest = wn.key_bytes_max(int(config["rows_per_slot"]),
                               bool(config["live_counters"]))
    if longest > limit:
        return (f"the program's device path takes shards of differing key "
                f"lengths up to {limit} B a key with merge_operator "
                f"{config['options']['merge_operator']!r}, the "
                f"configuration's longest name has {longest} B")
    return ""


def build_bulk_sst(store, tmp: str, seed: int, slot: int, rows: int,
                   prefix: str) -> int:
    """The slot's bulk file, written with the plain row-format writer
    (not the array sink under test) and uploaded to ``store`` under
    ``prefix``. Returns its size in bytes."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter

    path = os.path.join(tmp, f"slot{slot}.tsst")
    w = SSTWriter(path)
    for key, value in wn.bulk_rows(seed, slot, rows):
        w.add(key, 0, OpType.PUT, wn.encode_value(value))
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"{prefix}/bulk.tsst")
    os.remove(path)
    return size


class RefreshNames(Refresh):
    def prepare(self) -> None:
        """Ask the program, start the client (it makes its models and
        batches from the seed meanwhile) and build one bulk file per
        slot, each in the slot's own bucket."""
        from rocksplicator_tpu.utils.objectstore import LocalObjectStore

        why_not = device_takes(self.config)
        if why_not:
            raise SystemExit(f"chipbench: {why_not}; nothing was built")
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # it never needs a chip
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(rnc.__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self._send({"hello": {
            "admin_port": self.cluster.server.port,
            "data_port": self.cluster.replicator.port,
            "buckets": self.buckets, "config": self.config,
            "traffic": self.traffic, "seed": self.seed,
            "control": self.control}})
        for s in range(int(self.config["slots"])):
            build_bulk_sst(
                LocalObjectStore(rc.slot_bucket(self.buckets, s)),
                self.root, self.seed, s, int(self.config["rows_per_slot"]),
                rc.SST_PATH)
        self._expect("ready", 300.0)


make = RefreshNames  # what run.py calls: make(cluster, root, config,
#                      traffic, seed, control)
