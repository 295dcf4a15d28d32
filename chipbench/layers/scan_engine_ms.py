"""Layer engine: mean ``storage.scan`` span of the window (the engine's
part of a served scan: the view taken under the DB lock, the seek, the
lazy merge and resolve up to the limit), host clock. A program without
the span (one whose scan reads the whole version) gives nothing to
read."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "storage.scan")
    return (sum(ms) / len(ms) or None) if ms else None
