"""Traffic driver ``refresh_rec``: ``refresh``'s rolling refresh of a
segment (``refresh.py``: the unit, the window, the drain, the read-back)
over records of the configuration's ``value_bytes``: the live traffic
before a load is PUTs, a reply is compared in all its bytes.

This half lives with the node. It differs from ``Refresh`` in set-up
only:

- ``prepare()`` first ASKS THE PROGRAM whether its device path takes the
  configuration's widths (``tpu.compaction_service.
  device_value_bytes_max``), and ends the run, nonzero, before a file is
  built where the function is missing or says no: a program without the
  path would sit in a compile of many minutes or compact on the host;
- the bulk files hold ``workload_rec.bulk_rows`` and are written by a few
  threads (the row writer's zlib drops the GIL);
- the client is ``refresh_rec_client.py``.

``run.py``'s two ``--control`` names answer the reads from the reference
with a fault this configuration could have (``workload_rec.RecModel``):
``bits32`` is a value cut to its first 8 bytes (a device path that still
moved counters' widths), ``fold32`` is the overwritten value winning over
the load that shadows it. ``correct`` comes out false under both.
"""

from __future__ import annotations

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

from .. import cluster as cl
from .. import workload_rec as wr
from . import refresh_client as rc
from . import refresh_rec_client as rrc
from .refresh import Refresh

BUILD_THREADS = 8  # bulk files written at once in set-up


def device_takes(config: dict) -> str:
    """Why the program's device path does NOT take the configuration's
    widths ('' where it does), asked of the program itself."""
    try:
        from rocksplicator_tpu.tpu.compaction_service import (
            device_value_bytes_max)
    except ImportError:
        return ("the program has no tpu.compaction_service."
                "device_value_bytes_max: it cannot say what its device "
                "path takes")
    operator = cl.options_generator(config["options"])("seg").merge_operator
    limit = int(device_value_bytes_max(operator))
    if int(config["value_bytes"]) > limit:
        return (f"the program's device path takes values up to {limit} B "
                f"with merge_operator {config['options']['merge_operator']!r}"
                f", the configuration has {config['value_bytes']} B")
    return ""


def build_bulk_sst(store, tmp: str, seed: int, slot: int, rows: int,
                   value_bytes: int, prefix: str) -> int:
    """The slot's bulk file, written with the plain row-format writer
    (not the array sink under test) and uploaded to ``store`` under
    ``prefix``. Returns its size in bytes."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter

    path = os.path.join(tmp, f"slot{slot}.tsst")
    w = SSTWriter(path)
    for key, value in wr.bulk_rows(seed, slot, rows, value_bytes):
        w.add(key, 0, OpType.PUT, value)
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"{prefix}/bulk.tsst")
    os.remove(path)
    return size


class RefreshRec(Refresh):
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
            [sys.executable, os.path.abspath(rrc.__file__)],
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
                int(self.config["value_bytes"]), rc.SST_PATH)

        with ThreadPoolExecutor(BUILD_THREADS) as pool:
            list(pool.map(build, range(int(self.config["slots"]))))
        self._expect("ready", 600.0)


make = RefreshRec  # what run.py calls: make(cluster, root, config,
#                    traffic, seed, control)
