"""95th percentile of every ``write`` RPC (one pre-load WriteBatch) sent
in the window, send -> acknowledged, in milliseconds. Only a configuration with
live counters sends any."""

from chipbench.reduce import latencies, percentile


def read(run):
    p = percentile(latencies(run, "write"), 95)
    return None if p is None else p * 1000.0
