"""Layer wire: mean ``reply_ms`` of the window's ``rpc.server.write``
roots (a phase of the root, PR 37): a ``write``'s reply, on the loop:
the JSON header and the coalesced send, host clock. ``None`` on a
program whose roots carry no phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "write", "reply")
