"""Device idle time per round inside the trainer's ``sdflmq.wait`` spans
(the host blocked on the round step's state): a stall between the step's
dispatch and its end, in ms."""
from chipbench import program_trace

program_trace.watch()


def read(run):
    return program_trace.per_round_ms(run, "idle_s", "wait")
