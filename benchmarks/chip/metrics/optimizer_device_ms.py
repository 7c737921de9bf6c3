"""Device self time per round of the round step's ops in the
``optimizer`` scope (forward and backward), in ms."""
from chipbench import program_trace

program_trace.watch()


def read(run):
    return program_trace.per_round_ms(run, "scope_s", "optimizer")
