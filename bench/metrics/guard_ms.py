"""Device time a round of the non-finite guard, its check and its revert
(the program's stage ``trainer.guard``), in ms."""

from bench.stages import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("trainer.guard",))
