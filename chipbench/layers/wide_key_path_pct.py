"""Layer compaction seam: the share of the window's launched places whose
launch carried key lanes of twelve words (a key over 24 bytes in the
group: db_bench MixGraph's 48-byte keys): the ``key_lanes`` annotation
(6 / 12) of the window's ``tpu.compact_stream`` spans, weighted by their
``shards``. The engagement rate of the wide-key path: 100 where every
shard's keys are 48 bytes. A program that does not say (the annotation
is this metric's own) gives nothing to read."""


def read(run):
    wide = shards = 0
    for s in run.spans:
        if s["name"] != "tpu.compact_stream":
            continue
        lanes = s["annotations"].get("key_lanes")
        if lanes is None:
            return None
        n = int(s["annotations"]["shards"])
        shards += n
        wide += n if int(lanes) == 12 else 0
    return 100.0 * wide / shards if shards else None
