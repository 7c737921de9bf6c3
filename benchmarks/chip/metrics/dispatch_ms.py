"""Host time per round in the trainer's ``sdflmq.dispatch`` span (the
round step's call, until it returns), in ms, from the profiler trace."""
from chipbench import program_trace

program_trace.watch()


def read(run):
    return program_trace.per_round_ms(run, "span_s", "dispatch")
