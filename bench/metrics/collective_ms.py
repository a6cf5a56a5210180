"""Device time a round of the collectives (all-gather, all-reduce,
reduce-scatter, collective-permute, all-to-all), per chip, in ms: the union
of their intervals in the traced window (``bench.trace.Reduced``)."""


def read(ctx):
    if not ctx.rounds or not ctx.reduced.devices:
        return None
    return ctx.reduced.collective_ns / ctx.rounds / 1e6
