"""Pallas TPU kernels for RandK compression / server-side decompression.

TPU adaptation (DESIGN.md §3/§5): a GPU RandK uses cuRAND + global gather +
atomics. Neither maps to the TPU. Instead:

* the flat gradient is reshaped to ``(nblk, B)`` blocks; each grid step owns
  R whole rows, an ``(R, B)`` VMEM tile (kernels/tiling.py);
* *gather* is a lane gather inside 128-lane vregs, one vreg-wide slice of the
  row at a time — it copies values, so it is exact;
* *scatter-accumulate* is a compare-and-select sweep per sampled coordinate,
  adding the payloads worker-major in sampling order (the order of XLA's
  scatter-add in the oracle);
* the index sampler is a counter-based hash evaluated in-kernel
  (``randk_seeded_workers``), bit-exactly reproducible by ref.py; a gather
  with host-supplied offsets (``randk_gather``) serves the ops.py wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .tiling import LANES, Smem, lane_gather, lanes, row_call, stack_call, tile_rows


def _rounded_scale(scale: float, dtype) -> float:
    """The scale as the oracle applies it: rounded to the payload dtype
    (``values * jnp.asarray(scale, dtype)``); the f32 product of two values of
    that dtype is exact, so one final rounding matches the oracle."""
    return float(np.asarray(scale, np.float32).astype(dtype))


# ---------------------------------------------------------------------------
# Gather (compress): values[i, j] = x[i, offsets[i, j]] * scale
# ---------------------------------------------------------------------------


def _randk_gather_kernel(x_ref, off_ref, out_ref, *, scale: float):
    x = x_ref[...].astype(jnp.float32)        # (R, B)
    for q in range(off_ref.shape[-1] // LANES):
        sl = slice(q * LANES, (q + 1) * LANES)
        vals = lane_gather(x, off_ref[:, sl]) * scale
        out_ref[:, sl] = vals.astype(out_ref.dtype)


def randk_gather(
    x2d: jax.Array, offsets: jax.Array, scale: float, *, interpret: bool
) -> jax.Array:
    """x2d (nblk, B), offsets (nblk, kb) → (nblk, kb) scaled values."""
    nrows, kb = offsets.shape
    L = -(-kb // LANES) * LANES
    # whole 8-row sublane tiles for the lane gather (the mesh transport
    # gathers from leaves of any row count)
    pad = -nrows % 8
    x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
    offs = jnp.pad(offsets.astype(jnp.int32), ((0, pad), (0, L - kb)))
    (out,) = row_call(
        functools.partial(
            _randk_gather_kernel, scale=_rounded_scale(scale, x2d.dtype)
        ),
        [x2d, offs], [(L, x2d.dtype)], name="randk_gather", interpret=interpret,
    )
    return out[:nrows, :kb]


# ---------------------------------------------------------------------------
# Scatter-accumulate (decompress + server mean over n workers)
# ---------------------------------------------------------------------------


def scatter_rows(vals_ref, off_ref, R: int, B: int) -> jax.Array:
    """Σ over workers of each worker's scatter-add payload into an (R, B)
    f32 tile: vals/off refs (n, R, kb). Worker-major, then sample order."""
    n, _, kb = vals_ref.shape
    lane = lanes(R, B)

    def body(w, acc):
        vals = vals_ref[w].astype(jnp.float32)  # (R, kb)
        offs = off_ref[w]
        for t in range(kb):
            acc = acc + jnp.where(lane == offs[:, t:t + 1], vals[:, t:t + 1], 0.0)
        return acc

    return jax.lax.fori_loop(0, n, body, jnp.zeros((R, B), jnp.float32))


def _scatter_accum_kernel(vals_ref, off_ref, out_ref):
    R, B = out_ref.shape
    n = vals_ref.shape[0]
    out_ref[...] = (scatter_rows(vals_ref, off_ref, R, B) / n).astype(out_ref.dtype)


def scatter_accum(
    values: jax.Array, offsets: jax.Array, block: int, *, interpret: bool
) -> jax.Array:
    """values/offsets (n, nblk, kb) → dense (nblk, block) mean over workers."""
    (out,) = row_call(
        _scatter_accum_kernel, [values, offsets.astype(jnp.int32)],
        [(block, values.dtype)], name="scatter_accum", interpret=interpret,
    )
    return out


# ---------------------------------------------------------------------------
# Seeded production sampler: indices from an on-chip counter-based PRNG
# ---------------------------------------------------------------------------
#
# We use the murmur3 finalizer as a counter-based hash RNG: pure uint32 vector
# arithmetic, so it lowers on the TPU VPU, runs in any interpreter, and is
# *bit-exactly* reproducible by the pure-jnp oracle (ref.murmur_bits_ref).
# (``pltpu.prng_random_bits`` would also work on hardware but is stubbed in the
# CPU interpreter, making it untestable here.)


def murmur_bits(seed: jax.Array, ctr: jax.Array) -> jax.Array:
    """murmur3 finalizer over (seed, counter): uint32 → uint32 hash."""
    x = ctr.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def _randk_seeded_kernel(seed_ref, x_ref, vals_ref, off_ref, *, scale: float):
    w, j = pl.program_id(0), pl.program_id(1)
    R, B = x_ref.shape
    kb = vals_ref.shape[-1]
    x = x_ref[...].astype(jnp.float32)
    seed = seed_ref[w].astype(jnp.uint32)
    # worker-local counter stream: block b covers counters [b·kb, (b+1)·kb) —
    # the same stream tree_compress produces per worker, so the flat path is
    # bit-identical to the per-leaf path on block-aligned layouts. Lanes past
    # kb hash junk counters and are never stored.
    rows = tile_rows(j, R, LANES) * jnp.uint32(kb)
    for q in range(-(-kb // LANES)):
        ctr = lanes(R, LANES).astype(jnp.uint32) + jnp.uint32(q * LANES) + rows
        # B is a power of two; mask instead of mod.
        off = (murmur_bits(seed, ctr) & jnp.uint32(B - 1)).astype(jnp.int32)
        vals = lane_gather(x, off) * scale
        width = min(LANES, kb - q * LANES)
        sl = slice(q * LANES, q * LANES + width)
        vals_ref[:, sl] = vals[:, :width].astype(vals_ref.dtype)
        off_ref[:, sl] = off[:, :width]


def randk_seeded_workers(
    x3d: jax.Array, seeds: jax.Array, kb: int, scale: float, *,
    interpret: bool,
):
    """Per-worker seeded RandK: (n, nblk, B) + seeds (n,) → values/offsets
    (n, nblk, kb), sampled with replacement (unbiased with ω = B/kb,
    DESIGN.md §5). The grid is (worker, row tile) with per-worker seeds read
    from SMEM; each worker restarts its counter stream at 0, matching the
    tree path's per-worker key split (DESIGN.md §4.2). B is a power of two."""
    B = x3d.shape[-1]
    assert B & (B - 1) == 0, "block width must be a power of two"
    return stack_call(
        functools.partial(
            _randk_seeded_kernel, scale=_rounded_scale(scale, x3d.dtype)
        ),
        [Smem(seeds.astype(jnp.int32)), x3d],
        [(kb, x3d.dtype), (kb, jnp.int32)], name="randk_seeded",
        interpret=interpret,
    )


def randk_seeded(
    x2d: jax.Array, seed: jax.Array, kb: int, scale: float, *, interpret: bool
):
    """One worker's seeded RandK: (nblk, B) → values/offsets (nblk, kb)."""
    vals, offs = randk_seeded_workers(
        x2d[None], seed.reshape(1), kb, scale, interpret=interpret
    )
    return vals[0], offs[0]
