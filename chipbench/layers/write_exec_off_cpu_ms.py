"""Layer data plane: mean ``exec_ms - exec_cpu_ms`` (not below 0) of the
window's ``rpc.server.write`` roots (PR 37): the part of a ``write``'s
executor half in which its thread did not run (GIL wait, locks, blocking
IO): wall clock less the thread's own CPU time (``time.thread_time``)
between the same two points, over the roots whose hop was timed (one in
eight). ``None`` on a program whose roots carry no phases."""

from chipbench.phases import exec_off_cpu_mean


def read(run):
    return exec_off_cpu_mean(run, "write")
