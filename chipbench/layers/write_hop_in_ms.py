"""Layer wire: mean ``hop_in_ms`` of the window's ``rpc.server.write``
roots (a phase of the root, PR 37): the loop submits a ``write``'s
executor half -> it starts on the pool thread (the pool's wake-up and
the GIL), host clock. ``None`` on a program whose roots carry no
phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "write", "hop_in")
