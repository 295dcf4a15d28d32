"""Process start -> window start: native build or load, data from the
seed, version 0 of every slot, compile or cache read."""


def read(run):
    return run.setup_s
