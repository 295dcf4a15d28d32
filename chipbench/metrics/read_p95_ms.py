"""95th percentile of every ``get`` RPC sent in the window, send ->
reply, in milliseconds."""

from chipbench.reduce import latencies, percentile


def read(run):
    p = percentile(latencies(run, "read"), 95)
    return None if p is None else p * 1000.0
