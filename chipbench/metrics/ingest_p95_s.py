"""95th percentile, over every ``add_s3_sst_files_to_db`` RPC sent in the
window (those acknowledged after its close too), of send -> acknowledged
(loaded, compacted, servable)."""

from chipbench.reduce import latencies, percentile


def read(run):
    return percentile(latencies(run, "ingest"), 95)
