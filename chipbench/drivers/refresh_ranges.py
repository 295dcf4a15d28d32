"""Traffic driver ``refresh_ranges``: ``refresh``'s rolling refresh of a
segment (``refresh.py``: the unit, the window, the drain, the read-back;
``refresh_client.py`` the client; ``workload.SlotModel`` the reference,
all as they stand) over shards whose rows into a unit's compaction are
MORE than one launch of the program's device path holds.

This half lives with the node. It differs from ``Refresh`` in one
question that ``prepare()`` puts to the program before anything is
built: ``tpu.compaction_service.device_shard_rows_max(merge_operator)``
— the most rows of ONE shard that the served door compacts on the device
without building a program that a smaller shard has not built. Where the
function is missing (a program from before it, as a parent commit is) or
says fewer than a unit brings (``workload.unit_row_counts``), the run
ends, nonzero, with "nothing was built". Why it asks first: such a
program admits the shard as one place of a launch at
``_next_pow2(rows)`` and sits in a compile of many minutes inside the
first ingest RPC, or compacts on the host; either way the run would
measure nothing of this cell, and a parent that hangs there refuses the
PR that adds it.
"""

from __future__ import annotations

from .. import cluster as cl
from .. import workload as wl
from .refresh import Refresh


def device_takes(config: dict) -> str:
    """Why the program's device path does NOT take a unit's rows as one
    shard ('' where it does), asked of the program itself."""
    try:
        from rocksplicator_tpu.tpu.compaction_service import (
            device_shard_rows_max)
    except ImportError:
        return ("the program has no tpu.compaction_service."
                "device_shard_rows_max: it cannot say how large a shard "
                "its device path takes")
    operator = cl.options_generator(config["options"])("seg").merge_operator
    limit = int(device_shard_rows_max(operator))
    rows_in = wl.unit_row_counts(int(config["rows_per_slot"]),
                                 bool(config["live_counters"]))[0]
    if rows_in > limit:
        return (f"the program's device path takes shards of up to {limit} "
                f"rows into a compaction with merge_operator "
                f"{config['options']['merge_operator']!r}, a unit of the "
                f"configuration brings {rows_in}")
    return ""


class RefreshRanges(Refresh):
    def prepare(self) -> None:
        """Ask the program; then ``Refresh``'s set-up as it stands."""
        why_not = device_takes(self.config)
        if why_not:
            raise SystemExit(f"chipbench: {why_not}; nothing was built")
        super().prepare()


make = RefreshRanges  # what run.py calls: make(cluster, root, config,
#                       traffic, seed, control)
