"""Layer host codecs: host milliseconds a compacted shard spends in the
codecs: files -> lanes (``tpu.lanes.decode``, one span a shard) plus
lanes -> PLANAR files (``tpu.planar.write``, one span a file), summed
over the window and divided by its shards, host clock."""

from chipbench.reduce import span_ms


def read(run):
    decode = span_ms(run, "tpu.lanes.decode")
    if not decode:
        return None
    total = sum(decode) + sum(span_ms(run, "tpu.planar.write"))
    return total / len(decode) or None
