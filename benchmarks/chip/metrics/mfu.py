"""Model FLOP utilization of the whole round, in percent: the forward and
backward FLOPs the configuration's model needs per round (no
recomputation), times the rounds of the window, over the window's seconds
times the chips times each chip's bf16 peak."""


def read(run):
    if run.peaks is None or run.window_s <= 0:
        return None
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_round * run.rounds / (run.window_s * peak)
