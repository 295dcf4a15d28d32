"""Layer device: share of the traced slice's idle time (slice - device
busy) that the host's open spans account for: 1 - the ``unattributed``
entry of ``idle_gaps`` (no program span open) / idle. ``idle_gaps``
holds the ten largest entries: where ``unattributed`` is not among them
the share reads 100."""


def read(run):
    if run.trace is None:
        return None
    idle_s = run.trace["window_s"] - run.trace["busy_s"]
    if idle_s <= 0:
        return None
    unattributed_s = dict(run.trace["idle_gaps"]).get("unattributed", 0.0)
    return 100.0 * (1.0 - unattributed_s / idle_s) or None
