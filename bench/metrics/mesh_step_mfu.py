"""Model FLOP/s utilisation of the four-chip round, in %: the count that
``step_mfu`` makes for the one-chip cells (6·N per position of every
worker's rows, over the window, the chips and the chip's bf16 peak), read
here under a name of its own because ``step_mfu``'s entry lists its cells."""

from bench.metrics.step_mfu import read  # noqa: F401
