"""Device time a round of the operations in no stage of the program, or
missing from its map, in ms: what escapes the stages' instrumentation."""

from bench.stages import UNSCOPED, ms_per_round


def read(ctx):
    return ms_per_round(ctx, (UNSCOPED,))
