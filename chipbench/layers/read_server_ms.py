"""Layer data plane: mean ``rpc.server.read`` root of the window (a
``read`` RPC inside the server: dispatch -> reply handed to the
transport), host clock."""

from chipbench.reduce import span_ms


def read(run):
    ms = span_ms(run, "rpc.server.read")
    return (sum(ms) / len(ms) or None) if ms else None
