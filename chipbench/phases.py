"""What the readers of a served RPC's phases share (PR 37). A
``rpc.server.<method>`` root carries, as annotations, the milliseconds of
each phase it went through (``hop_in_ms``, ``exec_ms``, ``hop_out_ms``,
``reply_ms``, ``parse_ms``, ...: ``observability/hop.py`` and the stamps
in ``rpc/server.py``, ``replication/replicated_db.py``) and
``exec_cpu_ms``, its pool thread's own CPU time inside ``exec`` (taken on
one root in eight: the two reads of the thread's clock cost as much as
the hop's other stamps together). A root of a program without phases
carries none of them: every reader here then finds nothing to read."""

from __future__ import annotations

from typing import Optional


def _roots(run, method: str, *keys: str):
    name = "rpc.server." + method
    return [s["annotations"] for s in run.spans if s["name"] == name
            and all(k in s["annotations"] for k in keys)]


def phase_mean(run, method: str, phase: str) -> Optional[float]:
    """Mean ``<phase>_ms`` over the window's roots of ``method`` that
    carry it; a mean of 0 is left out as the other readers do."""
    key = phase + "_ms"
    ms = [a[key] for a in _roots(run, method, key)]
    return (sum(ms) / len(ms) or None) if ms else None


def exec_off_cpu_mean(run, method: str) -> Optional[float]:
    """Mean ``exec_ms - exec_cpu_ms``, each clamped at 0, over the roots
    that carry both (the one in eight whose hop was timed)."""
    ms = [max(0.0, a["exec_ms"] - a["exec_cpu_ms"])
          for a in _roots(run, method, "exec_ms", "exec_cpu_ms")]
    return (sum(ms) / len(ms) or None) if ms else None
