"""Host time per round in the trainer's ``sdflmq.h2d`` span (the batch's
host-to-device copy), in ms, from the profiler trace."""
from chipbench import program_trace

program_trace.watch()


def read(run):
    return program_trace.per_round_ms(run, "span_s", "h2d")
