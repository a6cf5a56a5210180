"""Device time of every worker's forward and backward pass a round, loss
included (the program's stage ``marina.backprop``), in ms."""

from bench.stages import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("marina.backprop",))
