"""Layer kernels: the batched merge-resolve + bloom pipeline's share of
its roofline where a shard is SEVERAL places of the launch. Least time =
bytes the merge of the whole shards needs (chipbench/work_model.py: from
a unit's row counts and widths, the same whatever implements the cut,
never from the padded launch) / the chip's peak HBM bytes/s. Time =
device time of the pipeline's XLA module events in the traced slice.
Whole shards per launched group: the window's ``tpu.compact_stream``
spans (``dbs`` over the groups their ``shards`` places fill; every group
of a unit's configuration holds equal shares). A program that does not
say ``dbs`` gives nothing to read. ``compact_pipeline_roofline`` reckons
one whole unit a place and would read the places' number high."""

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
    dbs = 0
    for s in run.spans:
        if s["name"] == "tpu.compact_stream":
            if "dbs" not in s["annotations"]:
                return None
            dbs += int(s["annotations"]["dbs"])
    _real, groups, _places = launched(run)
    if not events or not seconds or not groups or not dbs:
        return None
    least = (events * dbs / groups * work_model.unit_bytes(run.config)
             / float(run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
