"""Layer wire: mean ``hop_out_ms`` of the window's ``rpc.server.read``
roots (a phase of the root, PR 37): a ``read``'s executor half ends ->
its coroutine runs again on the loop (``call_soon_threadsafe``, the
selector, the GIL, the loop's backlog), host clock. ``None`` on a
program whose roots carry no phases."""

from chipbench.phases import phase_mean


def read(run):
    return phase_mean(run, "read", "hop_out")
