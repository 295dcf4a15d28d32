"""Layer compaction seam: real shards over launched shard places
(``reduce.launched``: the window's ``tpu.compact_stream`` spans)."""

from chipbench.reduce import launched


def read(run):
    real, _groups, places = launched(run)
    return 100.0 * real / places if places else None
