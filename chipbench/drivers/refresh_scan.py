"""Traffic driver ``refresh_scan``: ``refresh_rec``'s rolling refresh of
a segment of records (``refresh.py``: the unit, the window, the drain,
the read-back) under YCSB core workload E (``workload_ycsb``): YCSB's
hashed keys of differing length and 1,140-byte records, a unit's live
writes its inserts of new keys, and readers that scan.

This half lives with the node. It differs from ``RefreshRec`` in set-up
only:

- ``prepare()`` first ASKS THE PROGRAM whether its scan seeks
  (``storage.engine.scan_seeks``) and whether its served device door
  takes a shard of the configuration's keys and values
  (``tpu.compaction_service.device_mixed_key_bytes_max``,
  ``device_value_bytes_max``), and ends the run, nonzero, "nothing was
  built", where any is missing (a program from before the scan, as a
  parent commit is) or says no: such a program serves every scan from
  the whole version under the DB lock, and the window would measure
  that alone;
- the bulk files hold ``workload_ycsb.bulk_rows`` (key order);
- the client is ``refresh_scan_client.py``.

``run.py``'s two ``--control`` names answer the scans from the reference
with a fault this configuration could have (``workload_ycsb.ScanModel``):
``bits32`` is a value cut to its first 8 bytes, ``fold32`` a version
without its unit's live inserts. ``correct`` comes out false under both.
"""

from __future__ import annotations

import os
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor

from .. import cluster as cl
from .. import workload_ycsb as wy
from . import refresh_client as rc
from . import refresh_rec
from . import refresh_scan_client as rsc
from .refresh_rec import BUILD_THREADS, RefreshRec


def program_takes(config: dict) -> str:
    """Why the program does NOT serve the configuration's scans from a
    seeking engine, or its device path does not take a shard of its
    keys and values ('' where it does), asked of the program itself."""
    try:
        from rocksplicator_tpu.storage.engine import scan_seeks
        from rocksplicator_tpu.tpu.compaction_service import (
            device_mixed_key_bytes_max)
    except ImportError as e:
        return (f"the program cannot say whether its scan seeks or what "
                f"keys its device path takes ({e})")
    if not scan_seeks():
        return "the program's scan reads the whole version"
    operator = cl.options_generator(config["options"])("seg").merge_operator
    keys = int(device_mixed_key_bytes_max(operator))
    longest = max(int(n) for n in str(config["key_bytes"]).split("-"))
    if longest > keys:
        return (f"the program's device path takes shards of differing key "
                f"lengths up to {keys} B a key with merge_operator "
                f"{config['options']['merge_operator']!r}, the "
                f"configuration's keys reach {longest} B")
    return refresh_rec.device_takes(config)


def build_bulk_sst(store, tmp: str, seed: int, slots: int, slot: int,
                   rows: int, prefix: str) -> int:
    """The slot's bulk file, written with the plain row-format writer
    (not the array sink under test) and uploaded to ``store`` under
    ``prefix``. Returns its size in bytes."""
    from rocksplicator_tpu.storage import OpType
    from rocksplicator_tpu.storage.sst import SSTWriter

    path = os.path.join(tmp, f"slot{slot}.tsst")
    w = SSTWriter(path)
    for key, value in wy.bulk_rows(seed, slots, rows, slot):
        w.add(key, 0, OpType.PUT, value)
    w.finish()
    size = os.path.getsize(path)
    store.put_object(path, f"{prefix}/bulk.tsst")
    os.remove(path)
    return size


class RefreshScan(RefreshRec):
    def prepare(self) -> None:
        """Ask the program, start the client (it makes its models and
        batches from the seed meanwhile) and build one bulk file per
        slot, each in the slot's own bucket."""
        from rocksplicator_tpu.utils.objectstore import LocalObjectStore

        why_not = program_takes(self.config)
        if why_not:
            raise SystemExit(f"chipbench: {why_not}; nothing was built")
        env = dict(os.environ, JAX_PLATFORMS="cpu")  # it never needs a chip
        self.child = subprocess.Popen(
            [sys.executable, os.path.abspath(rsc.__file__)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env)
        self._send({"hello": {
            "admin_port": self.cluster.server.port,
            "data_port": self.cluster.replicator.port,
            "buckets": self.buckets, "config": self.config,
            "traffic": self.traffic, "seed": self.seed,
            "control": self.control}})
        slots = int(self.config["slots"])

        def build(s: int) -> int:
            return build_bulk_sst(
                LocalObjectStore(rc.slot_bucket(self.buckets, s)),
                self.root, self.seed, slots, s,
                int(self.config["rows_per_slot"]), rc.SST_PATH)

        with ThreadPoolExecutor(BUILD_THREADS) as pool:
            list(pool.map(build, range(slots)))
        self._expect("ready", 600.0)


make = RefreshScan  # what run.py calls: make(cluster, root, config,
#                     traffic, seed, control)
