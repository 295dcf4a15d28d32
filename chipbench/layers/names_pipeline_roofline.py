"""Layer kernels: the batched merge-resolve + bloom pipeline's share of
its roofline where a shard's KEYS differ in length (counter names). Least
time = bytes the merge needs for the REAL rows in and out / the chip's
peak HBM bytes/s; it is bytes-bound. The bytes: each key at its own
length (``workload_names.unit_key_bytes``: from the row count alone,
never from the 24-byte lanes or the padded launch), a sequence number,
an op type and a value a row (``work_model``'s widths), the output's
bloom filter once. Time = device time of the pipeline's XLA module
events in the traced slice. Real shards per launched group: the window's
``tpu.compact_stream`` spans (every group of a unit's configuration
holds equal shards). ``compact_pipeline_roofline`` takes one integer
``key_bytes`` a configuration and cannot read this one."""

from chipbench import work_model, workload_names
from chipbench.reduce import launched

PIPELINE_MODULE = "one_shard"  # jit(vmap(one_shard)) in compaction_service


def unit_bytes(config: dict) -> float:
    """Bytes the compaction of one unit of ``config`` has to move."""
    rows, live = int(config["rows_per_slot"]), bool(config["live_counters"])
    rows_in, rows_out = workload_names.unit_row_counts(rows, live)
    keys_in, keys_out = workload_names.unit_key_bytes(rows, live)
    rest = work_model.row_bytes(0, int(config["value_bytes"]))
    return (keys_in + keys_out + (rows_in + rows_out) * rest
            + work_model.bloom_bytes(
                rows_out, int(config["options"]["bits_per_key"])))


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
    least = (events * real / groups * unit_bytes(run.config)
             / float(run.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / seconds
