"""Layer compaction seam: the share of the window's launched shards
whose values took the index path (one row-index lane rides the sorts,
the values are moved once) and not the riding path: the ``value_path``
annotation of the window's ``tpu.compact_stream`` spans, weighted by
their ``shards``. A program that does not say (the annotation is this
metric's own) gives nothing to read."""


def read(run):
    index = shards = 0
    for s in run.spans:
        if s["name"] != "tpu.compact_stream":
            continue
        path = s["annotations"].get("value_path")
        if path is None:
            return None
        n = int(s["annotations"]["shards"])
        shards += n
        index += n if path == "index" else 0
    return 100.0 * index / shards if shards else None
