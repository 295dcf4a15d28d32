"""Layer compaction seam: the share of the window's launched shards that
went through a launch carrying the key-length lane (keys of DIFFERING
length in one shard: ``uniform_klen=False``): the ``key_widths``
annotation (``uniform`` / ``mixed``) of the window's
``tpu.compact_stream`` spans, weighted by their ``shards``. The
engagement rate of the mixed-key path: 100 where every shard's keys
differ in length, as counter names do. A program that does not say (the
annotation is this metric's own) gives nothing to read."""


def read(run):
    mixed = shards = 0
    for s in run.spans:
        if s["name"] != "tpu.compact_stream":
            continue
        widths = s["annotations"].get("key_widths")
        if widths is None:
            return None
        n = int(s["annotations"]["shards"])
        shards += n
        mixed += n if widths == "mixed" else 0
    return 100.0 * mixed / shards if shards else None
