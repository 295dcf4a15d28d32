"""Layer data plane: mean ``exec_ms`` of the window's
``rpc.server.write`` roots (a phase of the root, PR 37): a ``write``'s
executor half on the pool thread, wall clock (parse + commit), host
clock. ``None`` on a program whose roots carry no phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "write", "exec")
