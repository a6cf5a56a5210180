"""Device time a round of the flat engine's data movement outside its
Pallas kernels, in ms: the operations that are not kernels in the stages
``marina.diff``, ``flat.pack``, ``flat.compress``, ``flat.epilogue`` and
``flat.unpack`` (``kernel_ms`` counts the kernels)."""

from bench.stages import FLAT_GLUE, ms_per_round


def read(ctx):
    return ms_per_round(ctx, FLAT_GLUE, kernels=False)
