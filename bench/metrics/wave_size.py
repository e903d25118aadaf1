"""Mean real (unpadded) wave size of the window's replay, in reads:
the scheduler's batching, from ``ReplayResult.batch_sizes``."""


def read(ctx):
    return ctx["counters"].get("mean_wave")
