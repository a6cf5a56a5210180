"""Share of the roofline of the flat engine's Pallas kernels, in %: the
least time their calls in the window could take, the bytes each call must
move (``bench.yardstick.kernel_bytes``, from the cell's shapes) at the chip's
HBM bandwidth, over their measured device time. Every one of these kernels
is bound by memory (a few operations a byte)."""

from bench.yardstick import blocks, kernel_bytes


def read(ctx):
    s = ctx.session
    nblk = blocks(s.params_count(), s.block)
    least, spent = 0.0, 0.0
    for name, ns in ctx.reduced.kernel_ns.items():
        b = kernel_bytes(name, s.n, nblk, s.block, s.level)
        if b is None:
            continue
        least += ctx.reduced.kernel_count[name] * b / ctx.peaks["hbm_bytes_per_s"]
        spent += ns / 1e9
    if not spent:
        return None
    return 100.0 * least / spent
