"""Layer admin / ingest: mean ``admin.compact.wait`` span of the window
(an ingest RPC's post-load compaction queued in the BatchCompactor:
enqueue -> the start of the dispatch that takes it), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "admin.compact.wait")
    return (sum(ms) / len(ms) or None) if ms else None
