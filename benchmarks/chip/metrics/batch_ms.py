"""Host time per round in the trainer's batch request, in ms."""


def read(run):
    if not run.batch_s:
        return None
    return 1e3 * sum(run.batch_s) / run.rounds
