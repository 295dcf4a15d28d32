"""Layer device: share of the traced slice in which no op ran on the
chip: 1 - union of device-op intervals / slice."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
