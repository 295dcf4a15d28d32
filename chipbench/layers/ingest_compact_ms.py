"""Layer admin / ingest: mean ``admin.ingest.compact`` span of the window
(the post-load compaction as the ingest RPC sees it, queueing in the
BatchCompactor's group commit included), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "admin.ingest.compact")
    return sum(ms) / len(ms) if ms else None
