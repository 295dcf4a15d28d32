"""Layer kernels: the batched index-path merge-resolve + bloom pipeline's
share of its roofline on YCSB E's records. Least time = bytes the merge
needs for the REAL rows in and out / the chip's peak HBM bytes/s; it is
bytes-bound. The bytes: each row's key at its own length, its sequence
number and type, and its 1,140-byte record, in and out, and the output's
bloom filter once (``workload_ycsb.unit_bytes``: from the keys and the
row counts alone, never from the launch, whose rows are 24-byte key
lanes and 512-word value rows). Time = device time of the index
pipeline's XLA module events in the traced slice. Real shards per
launched group: the window's ``tpu.compact_stream`` spans (every group
of a unit's configuration holds equal shards).
``compact_pipeline_roofline`` takes one integer ``key_bytes`` a
configuration and cannot read this one."""

from chipbench import workload_ycsb
from chipbench.reduce import launched

PIPELINE_MODULE = "one_shard_index"  # jit(one_shard_index), compaction_service


def read(run):
    if run.trace is None:
        return None
    events = seconds = 0.0
    for name, m in run.trace["modules"].items():
        if PIPELINE_MODULE in name:
            events += m["count"]
            seconds += m["seconds"]
    real, groups, _places = launched(run)
    if not events or not seconds or not groups:
        return None
    least = (events * real / groups * workload_ycsb.unit_bytes(run.config)
             / float(run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
