"""Layer compaction seam: launched places per whole shard over the
window's ``tpu.compact_stream`` spans (sum of ``shards``, the places a
dispatch launched, over sum of ``dbs``, the whole shards they were cut
from): 1 where no shard is cut, 3 where every shard is cut in three. A
program that does not say ``dbs`` (the annotation is this metric's own)
gives nothing to read."""


def read(run):
    places = dbs = 0
    for s in run.spans:
        if s["name"] != "tpu.compact_stream":
            continue
        if "dbs" not in s["annotations"]:
            return None
        places += int(s["annotations"]["shards"])
        dbs += int(s["annotations"]["dbs"])
    return places / dbs if dbs else None
