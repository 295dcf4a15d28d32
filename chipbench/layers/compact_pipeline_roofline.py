"""Layer kernels: the batched merge-resolve + bloom pipeline's share of
its roofline. Least time = bytes the merge needs for the REAL rows in and
out (chipbench/work_model.py: from row counts and widths, never from the
padded launch) / the chip's peak HBM bytes/s; it is bytes-bound. Time =
device time of the pipeline's XLA module events in the traced slice.
Real shards per launched group: the window's ``tpu.compact_stream``
spans (every group of a unit's configuration holds equal shards)."""

from chipbench import work_model
from chipbench.reduce import launched

PIPELINE_MODULE = "one_shard"  # jit(vmap(one_shard)) in compaction_service


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
    least = (events * real / groups * work_model.unit_bytes(run.config)
             / float(run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
