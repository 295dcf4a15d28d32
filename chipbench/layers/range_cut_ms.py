"""Layer compaction seam: mean ``tpu.range_cut`` span of the window (a
shard over one place's rows planned and cut into its key ranges, on the
pool thread that decoded it), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "tpu.range_cut")
    return sum(ms) / len(ms) if ms and sum(ms) else None
