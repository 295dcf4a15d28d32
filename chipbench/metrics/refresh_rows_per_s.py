"""Bulk rows refreshed per second of the window: all the work over all
the time.

Every unit the window started is waited for (the drain), so each unit's
life from its first RPC to its acknowledgement is known. A unit counts
with the share of that life that lies inside the window: whole where it
was acknowledged inside, in part where the close cut it. Counting only
whole units would step the rate by the 8 units in flight at once (they
finish together, coalesced by the group commit), some 4 % of a window;
``run.py`` prints the count of whole units beside it (PERF.md, Findings
of PR 26, has both readings)."""


def read(run):
    units = 0.0
    for u in run.units:
        inside = min(u["acked"], run.t1) - max(u["started"], run.t0)
        if inside > 0:
            units += inside / (u["acked"] - u["started"])
    return units * int(run.config["rows_per_slot"]) / run.seconds
