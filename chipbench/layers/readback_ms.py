"""Layer compaction seam: mean ``tpu.readback`` span of the window (the
host blocked on one launch group's device work and its copy back),
host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "tpu.readback")
    return (sum(ms) / len(ms) or None) if ms else None
