"""Device time a round, per chip, of the mesh's compressed uplink outside
its Pallas kernels, in ms: the operations that are not kernels in the
program's stage ``transport.uplink`` (the per-leaf index draws, payload
staging, the worker all-gather, the reshapes around the kernels)."""

from bench.stages import ms_per_round


def read(ctx):
    return ms_per_round(ctx, ("transport.uplink",), kernels=False)
