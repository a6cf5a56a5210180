"""Device time a round, per chip, in ms, during which a collective runs and
no other operation does on that chip: what the exchange adds to the round
(``bench.trace.Reduced.collective_exposed_ns``)."""


def read(ctx):
    if not ctx.rounds or not ctx.reduced.devices:
        return None
    return ctx.reduced.collective_exposed_ns / ctx.rounds / 1e6
