"""Device busy time of the round step per round, in ms: the union of the
device-op intervals in the traced window over its rounds (averaged over
the chips used)."""


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace.busy_s / run.rounds
