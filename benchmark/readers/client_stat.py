"""A number the benchmark's own client took: the generator's schedule
and the supervisor's pod watch (loadgen.window_stats)."""


def read(ctx, stat):
    return ctx["client"].get(stat)
