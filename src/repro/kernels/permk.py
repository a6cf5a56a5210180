"""Pallas TPU kernel for the PermK correlated compressor (DESIGN.md §5).

PermK partitions every block's coordinates across the n workers through one
SHARED seeded permutation, so unlike ``randk_seeded_workers`` the grid reads a
single scalar seed and derives worker-DISJOINT supports from the program id.
A full Fisher–Yates permutation does not map to the TPU; instead each block
uses a seeded *affine* bijection

    π_b(t) = (a_b · t + c_b) mod B,   a_b odd  (a unit of Z_B, B = 2^k)

with (a_b, c_b) drawn from the murmur3 counter RNG at counters (2b, 2b+1) —
pure uint32 VPU arithmetic, bit-exactly reproduced by
``ref.affine_perm_params_ref``. Worker w gathers permuted slots
[w·B/n, (w+1)·B/n): the n supports partition the block, so the server mean is
collision-free (``scatter_accum`` degenerates to assembly; the jnp ref also
provides a scatter-free inverse-perm gather, ``ref.permk_concat_mean_ref``).

The gather is the lane gather of kernels/tiling.py (one 128-lane vreg slice
of the row at a time), so values are copied exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .randk import murmur_bits
from .tiling import LANES, Smem, lane_gather, lanes, stack_call, tile_rows


def _permk_workers_kernel(seed_ref, x_ref, vals_ref, off_ref, *, n: int):
    w, j = pl.program_id(0), pl.program_id(1)
    R, B = x_ref.shape
    chunk = vals_ref.shape[-1]    # B // n
    x = x_ref[...].astype(jnp.float32)
    seed = seed_ref[0].astype(jnp.uint32)
    # shared per-block affine permutation (same π_b for every worker):
    # counters (2b, 2b+1) of block row b
    b2 = tile_rows(j, R, 1) * jnp.uint32(2)
    mask = jnp.uint32(B - 1)
    a = (murmur_bits(seed, b2) | jnp.uint32(1)) & mask       # (R, 1)
    c = murmur_bits(seed, b2 + jnp.uint32(1)) & mask
    for q in range(-(-chunk // LANES)):
        t = (lanes(R, LANES) + (w * chunk + q * LANES)).astype(jnp.uint32)
        off = ((a * t + c) & mask).astype(jnp.int32)
        vals = lane_gather(x, off) * float(n)
        width = min(LANES, chunk - q * LANES)
        sl = slice(q * LANES, q * LANES + width)
        vals_ref[:, sl] = vals[:, :width].astype(vals_ref.dtype)
        off_ref[:, sl] = off[:, :width]


def permk_seeded_workers(x3d: jax.Array, seed: jax.Array, *, interpret: bool):
    """PermK uplink: (n, nblk, B) + one shared uint32 seed → values/offsets,
    both (n, nblk, B/n). Values carry the ×n Perm-K scale; the n workers'
    offsets partition [0, B) in every block. Requires n | B (powers of two)."""
    n, nblk, B = x3d.shape
    assert B & (B - 1) == 0, "block width must be a power of two"
    assert B % n == 0, "worker count must divide the block width"
    chunk = B // n
    return stack_call(
        functools.partial(_permk_workers_kernel, n=n),
        [Smem(seed.reshape(1).astype(jnp.int32)), x3d],
        [(chunk, x3d.dtype), (chunk, jnp.int32)],
        name="permk_seeded_workers", interpret=interpret,
    )
