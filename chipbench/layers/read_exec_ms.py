"""Layer data plane: mean ``exec_ms`` of the window's
``rpc.server.read`` roots (a phase of the root, PR 37): a ``read``'s
executor half on the pool thread, wall clock (the engine read), host
clock. ``None`` on a program whose roots carry no phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "read", "exec")
