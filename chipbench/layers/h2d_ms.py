"""Layer compaction seam: mean ``tpu.h2d`` span of the window (one
launch group stacked and padded on the host and handed to
``device_put``), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "tpu.h2d")
    return (sum(ms) / len(ms) or None) if ms else None
