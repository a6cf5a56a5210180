"""Row tiling and lane gathers shared by the flat-engine kernels.

Mosaic requires the last two dims of every block to be multiples of
(8, 128) or to span the whole array. The flat engine's buffers are
``(nblk, B)`` with B a lane multiple, so each kernel takes R whole block rows
per grid step — R a multiple of 32 (the int8 sublane tile), or all of nblk
when nblk is smaller. The last tile may run past nblk: rows are independent,
the rows past the end only feed rows whose writes are dropped, and no kernel
reduces across rows. Per-row scalars (block norms and scales) travel as
``(…, nblk, 1)`` columns, which satisfy the same rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: bytes of one grid step's blocks (inputs + outputs, before double
#: buffering); keeps a step's tiles and temporaries well inside scoped VMEM
TILE_BYTES = 1 << 20


def lane_bytes(width: int, itemsize: int) -> int:
    """VMEM bytes of one row of a ``width``-lane block (lanes pad to 128)."""
    return -(-width // LANES) * LANES * itemsize


def row_tile(nblk: int, row_bytes: int) -> int:
    """Rows per grid step for blocks that move ``row_bytes`` per buffer row."""
    r = max(32, TILE_BYTES // max(row_bytes, 1) // 32 * 32)
    return nblk if nblk <= r else r


def tile_rows(j: jax.Array, R: int, width: int) -> jax.Array:
    """(R, width) uint32 ids of the block rows in row tile ``j``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (R, width), 0) + j * R
    return r.astype(jnp.uint32)


def lanes(R: int, width: int) -> jax.Array:
    """(R, width) int32 lane index."""
    return jax.lax.broadcasted_iota(jnp.int32, (R, width), 1)


def to_unit(bits: jax.Array) -> jax.Array:
    """Kernel twin of ``ref.uniform_from_bits_ref``: (bits >> 8) < 2^24, so
    the int32 detour is exact (Mosaic has no uint32 → f32 convert)."""
    top = (bits >> jnp.uint32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0**-24)


def lane_gather(x: jax.Array, idx: jax.Array) -> jax.Array:
    """x (R, B) f32, idx (R, 128) int32 in [0, B) → x[r, idx[r, l]], exact.

    Mosaic gathers within one 128-lane vreg only, so the row is read one
    128-lane slice at a time and each lane keeps the slice that holds its
    index. Rows of B < 128 lanes (interpret-mode test shapes) are one slice.
    """
    B = x.shape[-1]
    if B <= LANES:
        return jnp.take_along_axis(x, idx, axis=1)
    assert B % LANES == 0, "block width must be a multiple of 128 lanes"
    lo = idx & (LANES - 1)
    hi = idx >> 7
    out = jnp.take_along_axis(x[:, :LANES], lo, axis=1)
    for s in range(1, B // LANES):
        g = jnp.take_along_axis(x[:, s * LANES:(s + 1) * LANES], lo, axis=1)
        out = jnp.where(hi == s, g, out)
    return out


class Whole:
    """Marks a ``row_call`` operand taken whole in every grid step (a
    constant matrix, a (1, 1) scalar)."""

    def __init__(self, array: jax.Array):
        self.array = array


def row_call(kernel, args, outs, *, name: str, interpret: bool):
    """pallas_call ``name`` over row tiles of the block axis.

    ``args`` are 2-D ``(nblk, w)`` operands, tiled (R, w); 3-D worker stacks
    ``(n, nblk, w)``, tiled (n, R, w) — all workers in every step; or
    :class:`Whole` operands. ``outs`` lists each ``(nblk, width)`` output's
    (width, dtype)."""
    arrays = [a.array if isinstance(a, Whole) else a for a in args]
    tiled = [a for a in args if not isinstance(a, Whole)]
    nblk = tiled[0].shape[-2]
    row_bytes = sum(
        (a.shape[0] if a.ndim == 3 else 1) * lane_bytes(a.shape[-1], a.dtype.itemsize)
        for a in tiled
    ) + sum(lane_bytes(w, jnp.dtype(dt).itemsize) for w, dt in outs)
    R = row_tile(nblk, row_bytes)

    def spec(a):
        if isinstance(a, Whole):
            return pl.BlockSpec(a.array.shape, lambda i: (0,) * a.array.ndim)
        if a.ndim == 3:
            return pl.BlockSpec((a.shape[0], R, a.shape[2]), lambda i: (0, i, 0))
        return pl.BlockSpec((R, a.shape[1]), lambda i: (i, 0))

    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(nblk, R),),
        in_specs=[spec(a) for a in args],
        out_specs=[pl.BlockSpec((R, w), lambda i: (i, 0)) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((nblk, w), dt) for w, dt in outs],
        interpret=interpret,
        name=name,
    )(*arrays)


class Smem:
    """Marks a ``stack_call`` operand placed whole in SMEM (scalars such as
    the per-worker seeds)."""

    def __init__(self, array: jax.Array):
        self.array = array


def stack_call(kernel, args, outs, *, name: str, interpret: bool):
    """pallas_call ``name`` over (worker, row tile) of ``(n, nblk, w)``
    worker stacks.

    Each stack operand is tiled (R, w) of one worker — the grid never folds
    workers into the row axis, so no (n, nblk) ↔ (n·nblk) relayout is ever
    needed; :class:`Smem` and :class:`Whole` operands come whole. ``outs``
    lists each ``(n, nblk, width)`` output's (width, dtype). Kernels read the
    worker and the row tile from ``pl.program_id(0)`` and
    ``pl.program_id(1)``."""
    stacks = [a for a in args if not isinstance(a, (Whole, Smem))]
    n, nblk = stacks[0].shape[:2]
    row_bytes = sum(
        lane_bytes(a.shape[-1], a.dtype.itemsize) for a in stacks
    ) + sum(lane_bytes(w, jnp.dtype(dt).itemsize) for w, dt in outs)
    R = row_tile(nblk, row_bytes)
    row = lambda w, j: (w, j, 0)

    def spec(a):
        if isinstance(a, Smem):
            return pl.BlockSpec(memory_space=pltpu.SMEM)
        if isinstance(a, Whole):
            return pl.BlockSpec(a.array.shape, lambda w, j: (0,) * a.array.ndim)
        return pl.BlockSpec((None, R, a.shape[-1]), row)

    return pl.pallas_call(
        kernel,
        grid=(n, pl.cdiv(nblk, R)),
        in_specs=[spec(a) for a in args],
        out_specs=[pl.BlockSpec((None, R, w), row) for w, _ in outs],
        out_shape=[jax.ShapeDtypeStruct((n, nblk, w), dt) for w, dt in outs],
        interpret=interpret,
        name=name,
    )(*[a.array if isinstance(a, (Whole, Smem)) else a for a in args])


def per_block(cols: jax.Array) -> jax.Array:
    """(n, nblk) per-block scalars → the (n, nblk, 1) f32 column stack the
    kernels tile."""
    return cols.reshape(*cols.shape, 1).astype(jnp.float32)
