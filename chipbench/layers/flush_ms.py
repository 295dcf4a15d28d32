"""Layer engine: mean ``storage.flush`` span of the window (memtables ->
an L0 file, on the engine's flusher thread; the post-load compaction's
plan stage waits for it), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "storage.flush")
    return (sum(ms) / len(ms) or None) if ms else None
