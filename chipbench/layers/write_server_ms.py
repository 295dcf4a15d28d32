"""Layer data plane: mean ``rpc.server.write`` root of the window (a
``write`` RPC inside the server: dispatch -> reply handed to the
transport), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "rpc.server.write")
    return (sum(ms) / len(ms) or None) if ms else None
