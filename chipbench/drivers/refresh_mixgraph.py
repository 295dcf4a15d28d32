"""Traffic driver ``refresh_mixgraph``: ``refresh_rec``'s rolling refresh
of a segment of records (``refresh.py``: the unit, the window, the drain,
the read-back) over db_bench MixGraph's records (``workload_mixgraph``):
48-byte keys, values of 10 to 1,024 bytes of differing width in every
shard, reads drawn by MixGraph's key-range hotness.

This half lives with the node. It differs from ``RefreshRec`` in set-up
only:

- ``prepare()`` first ASKS THE PROGRAM whether its served device door
  takes a shard of keys of the configuration's length and values of
  differing widths up to the widest
  (``tpu.compaction_service.device_mixed_key_bytes_max`` and
  ``device_mixed_value_bytes_max``), and ends the run, nonzero, "nothing
  was built", where either is missing (a program from before them, as a
  parent commit is) or says less: such a program declines every shard of
  the cell to the host, or compiles nothing the cell measures;
- the bulk files hold ``workload_mixgraph.bulk_rows`` (key order), each
  value at its own length;
- the client is ``refresh_mixgraph_client.py``.

``run.py``'s two ``--control`` names answer the reads from the reference
with a fault this configuration could have (``workload_mixgraph.
MixModel``): ``bits32`` is a value padded to its bucket's width (a sink
that ignores the value-length plane), ``fold32`` is the overwritten
value winning over the load that shadows it. ``correct`` comes out false
under both.
"""

from __future__ import annotations

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

from .. import cluster as cl
from .. import workload_mixgraph as wm
from . import refresh_client as rc
from . import refresh_mixgraph_client as rmc
from .refresh_rec import BUILD_THREADS, RefreshRec


def device_takes(config: dict) -> str:
    """Why the program's served device door does NOT take a shard of the
    configuration's keys and values ('' where it does), asked of the
    program itself."""
    try:
        from rocksplicator_tpu.tpu.compaction_service import (
            device_mixed_key_bytes_max, device_mixed_value_bytes_max)
    except ImportError:
        return ("the program has no tpu.compaction_service."
                "device_mixed_key_bytes_max / device_mixed_value_bytes_max:"
                " it cannot say whether its device path takes 48-byte keys"
                " and values of differing widths in one shard")
    operator = cl.options_generator(config["options"])("seg").merge_operator
    keys = int(device_mixed_key_bytes_max(operator))
    vals = int(device_mixed_value_bytes_max(operator))
    if wm.KEY_BYTES > keys:
        return (f"the program's device path takes keys up to {keys} B with "
                f"merge_operator {config['options']['merge_operator']!r}, "
                f"the configuration's are {wm.KEY_BYTES} B")
    if wm.VALUE_MAX > vals:
        return (f"the program's device path takes values of differing "
                f"widths up to {vals} B with merge_operator "
                f"{config['options']['merge_operator']!r}, the "
                f"configuration's reach {wm.VALUE_MAX} B")
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
    for key, value in wm.bulk_rows(seed, slot, rows):
        w.add(key, 0, OpType.PUT, value)
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"{prefix}/bulk.tsst")
    os.remove(path)
    return size


class RefreshMixGraph(RefreshRec):
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
            [sys.executable, os.path.abspath(rmc.__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self._send({"hello": {
            "admin_port": self.cluster.server.port,
            "data_port": self.cluster.replicator.port,
            "buckets": self.buckets, "config": self.config,
            "traffic": self.traffic, "seed": self.seed,
            "control": self.control}})

        def build(s: int) -> int:
            return build_bulk_sst(
                LocalObjectStore(rc.slot_bucket(self.buckets, s)),
                self.root, self.seed, s, int(self.config["rows_per_slot"]),
                rc.SST_PATH)

        with ThreadPoolExecutor(BUILD_THREADS) as pool:
            list(pool.map(build, range(int(self.config["slots"]))))
        self._expect("ready", 600.0)


make = RefreshMixGraph  # what run.py calls: make(cluster, root, config,
#                         traffic, seed, control)
