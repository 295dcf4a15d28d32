"""Layer engine: mean ``parse_ms`` of the window's ``rpc.server.write``
roots (a phase of the root, PR 37): ``decode_batch`` of a ``write``'s
frame on the pool thread (the one parse; it never drops the GIL), host
clock. ``None`` on a program whose roots carry no phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "write", "parse")
