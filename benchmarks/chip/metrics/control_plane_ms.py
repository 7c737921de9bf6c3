"""Host time per round in the clients' ``signal_ready`` (round-status
updates, which run the coordinator's role arrangement), in ms."""


def read(run):
    if not run.control_s:
        return None
    return 1e3 * sum(run.control_s) / run.rounds
