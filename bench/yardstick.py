"""The benchmark's own arithmetic: model FLOPs and the bytes the flat
engine's kernels must move, from the cell's shapes alone."""

from __future__ import annotations


def model_flops(params: int, positions: int) -> float:
    """6·N·T: forward and backward of every parameter at every position
    (recomputation not counted)."""
    return 6.0 * params * positions


def blocks(params: int, block: int) -> int:
    """Blocks of ``block`` coordinates that hold ``params`` (the wire's)."""
    return -(-params // block)


def kernel_bytes(kernel: str, n: int, nblk: int, block: int, level: int):
    """Bytes one call of a flat-engine kernel must move between HBM and the
    chip, for n workers' (nblk, block) f32 buffers; None for a kernel not
    counted here. Per-block scalars (norms) count 4 bytes; the constant
    packing matrices are read once and left out."""
    f32 = 4
    buf = nblk * block                      # coordinates of one buffer
    table = {
        # per-worker block QSGD: read the f32 diffs, write int8 levels + norms
        "qsgd_block_workers": n * buf * f32 + n * buf + n * nblk * f32,
        # int8 levels -> 4-bit words and back
        "nibble_pack": n * buf + n * buf // 2,
        "nibble_unpack": n * buf // 2 + n * buf,
        # levels + norms, g and x in; g and x out
        "qsgd_epilogue": n * buf + n * nblk * f32 + 4 * buf * f32,
        # worker gradients and x in; g and x out
        "mean_epilogue": n * buf * f32 + 3 * buf * f32,
        # per-worker seeded RandK: read the diffs, write values + offsets
        "randk_seeded": n * buf * f32 + 2 * n * nblk * level * f32,
        # values + offsets, g and x in; g and x out
        "scatter_epilogue": 2 * n * nblk * level * f32 + 4 * buf * f32,
    }
    for name, nbytes in table.items():
        if name in kernel:
            return nbytes
    return None
