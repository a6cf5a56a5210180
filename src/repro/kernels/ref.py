"""Pure-jnp oracles for the compression kernels.

Every function here is the semantic ground truth for its Pallas counterpart;
tests assert_allclose kernel-vs-ref over shape/dtype sweeps in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def randk_block_compress_ref(x2d: jax.Array, offsets: jax.Array, scale: float) -> jax.Array:
    """Gather per-block coordinates and scale.

    x2d:     (nblk, B)   the flat gradient reshaped into VMEM-sized blocks
    offsets: (nblk, kb)  local indices in [0, B) chosen by the (host) sampler
    returns: (nblk, kb)  values · scale  (scale = d/K for unbiasedness)
    """
    gathered = jnp.take_along_axis(x2d, offsets, axis=1)
    return gathered * jnp.asarray(scale, x2d.dtype)


def scatter_accum_ref(
    values: jax.Array, offsets: jax.Array, block: int
) -> jax.Array:
    """Server-side aggregation: mean over n workers of scatter-add payloads.

    values:  (n, nblk, kb)
    offsets: (n, nblk, kb) local indices in [0, block)
    returns: (nblk, block) dense mean; duplicates within a worker accumulate
             (with-replacement sampling is allowed).
    """
    n, nblk, kb = values.shape
    out = jnp.zeros((nblk, block), values.dtype)

    def per_block(vals_b, offs_b):
        # vals_b, offs_b: (n, kb)
        dense = jnp.zeros((block,), values.dtype)
        return dense.at[offs_b.reshape(-1)].add(vals_b.reshape(-1))

    dense = jax.vmap(per_block, in_axes=(1, 1))(values, offsets)  # (nblk, block)
    return dense / n


#: counter offset separating the composition's dither stream from the index
#: stream of the same seed (index counters are < nblk·kb ≪ 2^30). Plain int:
#: a module-level jnp constant would capture a tracer if the module is first
#: imported inside a jit trace (the engine imports lazily).
DITHER_CTR_OFFSET = 0x40000000


def uniform_from_bits_ref(bits: jax.Array) -> jax.Array:
    """uint32 hash bits → f32 uniform in [0, 1), bit-exact on every backend.

    (bits >> 8) < 2^24 is exactly representable in f32, so the conversion and
    the 2^-24 scale are both exact — ref and kernel agree bit for bit."""
    return (bits >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(2.0**-24)


def qsgd_quantize_ref(
    x2d: jax.Array, u2d: jax.Array, norm: jax.Array, s: int
) -> jax.Array:
    """Stochastic s-level quantization (QSGD): int8 levels with sign.

    x2d/u2d: (nblk, B);  u ~ U[0,1) supplied by the host sampler
    norm:    scalar ℓ2 norm of the full vector
    returns: (nblk, B) int8, value = sign(x)·⌊s|x|/‖x‖ + u⌋
    """
    safe = jnp.where(norm > 0, norm, 1.0).astype(jnp.float32)
    level = jnp.floor(s * jnp.abs(x2d.astype(jnp.float32)) / safe + u2d)
    return (jnp.sign(x2d.astype(jnp.float32)) * level).astype(jnp.int8)


def qsgd_dequantize_ref(q2d: jax.Array, norm: jax.Array, s: int) -> jax.Array:
    return q2d.astype(jnp.float32) * (norm / s)


def block_sumsq_ref(x2d: jax.Array) -> jax.Array:
    """Per-block Σx² (pass 1 of the two-pass fused QSGD norm)."""
    return jnp.sum(jnp.square(x2d.astype(jnp.float32)), axis=1)


def murmur_bits_ref(seed: jax.Array, ctr: jax.Array) -> jax.Array:
    """Bit-exact oracle for the kernel's counter-based RNG (murmur3 finalizer)."""
    x = ctr.astype(jnp.uint32) * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def randk_seeded_ref(x2d: jax.Array, seed: jax.Array, kb: int, scale: float):
    """Oracle for randk_seeded: same hash, same masking, same gather."""
    nblk, B = x2d.shape
    ctr = (
        jnp.arange(kb, dtype=jnp.uint32)[None, :]
        + (jnp.arange(nblk, dtype=jnp.uint32) * kb)[:, None]
    )
    bits = murmur_bits_ref(seed, ctr)
    off = (bits & jnp.uint32(B - 1)).astype(jnp.int32)
    vals = jnp.take_along_axis(x2d, off, axis=1) * jnp.asarray(scale, x2d.dtype)
    return vals, off


def randk_seeded_workers_ref(
    x3d: jax.Array, seeds: jax.Array, kb: int, scale: float
):
    """Oracle for randk_seeded_workers: per-worker seed, worker-local counters.

    x3d: (n, nblk, B);  seeds: (n,) uint32
    returns: values/offsets, both (n, nblk, kb)
    """
    return jax.vmap(
        lambda x2d, s: randk_seeded_ref(x2d, s.astype(jnp.uint32), kb, scale)
    )(x3d, seeds)


# ---------------------------------------------------------------------------
# PermK: seeded affine block permutations (disjoint worker supports)
# ---------------------------------------------------------------------------


def affine_perm_params_ref(seed: jax.Array, nblk: int, block: int):
    """Per-block affine bijection π_b(t) = (a_b·t + c_b) mod block.

    a_b is forced odd (a unit of Z_{2^k}, so π_b is a permutation of the
    block) and both coefficients come from the murmur3 counter RNG at
    counters (2b, 2b+1) — disjoint from the randk sampler's stream only by
    convention (different compressor, different seed).
    Returns a, c: (nblk,) uint32."""
    b = jnp.arange(nblk, dtype=jnp.uint32)
    mask = jnp.uint32(block - 1)
    a = (murmur_bits_ref(seed, 2 * b) | jnp.uint32(1)) & mask
    c = murmur_bits_ref(seed, 2 * b + 1) & mask
    return a, c


def odd_inverse_ref(a: jax.Array) -> jax.Array:
    """Multiplicative inverse of odd a modulo 2^32 (Newton iteration; exact
    after 5 steps). Masking to block−1 gives the inverse mod any 2^k."""
    a = a.astype(jnp.uint32)
    inv = a  # correct mod 2^3 already for odd a
    for _ in range(5):
        inv = inv * (jnp.uint32(2) - a * inv)
    return inv


def permk_offsets_ref(
    seed: jax.Array, nblk: int, block: int, n: int, wid: jax.Array
) -> jax.Array:
    """Worker wid's PermK support: offsets (nblk, block/n) int32 in [0, block).

    Worker w owns permuted slots [w·C, (w+1)·C), C = block/n; across the n
    workers the offsets partition every block exactly (π is a bijection)."""
    assert block % n == 0, "worker count must divide the block width"
    chunk = block // n
    a, c = affine_perm_params_ref(seed.astype(jnp.uint32), nblk, block)
    t = (
        jnp.arange(chunk, dtype=jnp.uint32)[None, :]
        + jnp.asarray(wid, jnp.uint32) * jnp.uint32(chunk)
    )
    off = (a[:, None] * t + c[:, None]) & jnp.uint32(block - 1)
    return off.astype(jnp.int32)


def permk_seeded_workers_ref(x3d: jax.Array, seed: jax.Array, n: int):
    """Oracle for the PermK uplink: one SHARED seed, per-worker disjoint chunk.

    x3d: (n, nblk, B); returns values/offsets, both (n, nblk, B/n); values are
    scaled by n (Perm-K's unbiasedness factor)."""
    nblk, B = x3d.shape[1], x3d.shape[2]
    wids = jnp.arange(n, dtype=jnp.int32)

    def one(x2d, w):
        off = permk_offsets_ref(seed.astype(jnp.uint32), nblk, B, n, w)
        vals = jnp.take_along_axis(x2d, off, axis=1) * jnp.asarray(n, x2d.dtype)
        return vals, off

    return jax.vmap(one)(x3d, wids)


def permk_concat_mean_ref(
    values: jax.Array, seed: jax.Array, block: int
) -> jax.Array:
    """Disjoint-support aggregation: mean over n PermK payloads WITHOUT scatter.

    values: (n, nblk, block/n) worker payloads (already scaled by n).
    The supports partition each block, so the mean is assembly, not
    accumulation: concatenate the chunks in slot order t = w·C+j and gather
    through the inverse permutation π⁻¹(s) = a⁻¹·(s − c) mod block.
    Returns (nblk, block) f32 — bit-compatible with scatter_accum_ref on the
    same payloads (collision-free ⇒ identical sums)."""
    n, nblk, chunk = values.shape
    a, c = affine_perm_params_ref(seed.astype(jnp.uint32), nblk, block)
    a_inv = odd_inverse_ref(a)
    s = jnp.arange(block, dtype=jnp.uint32)[None, :]
    slot = (a_inv[:, None] * (s - c[:, None])) & jnp.uint32(block - 1)
    # (nblk, block) values ordered by slot: slot t holds worker t//C's j-th value
    by_slot = jnp.moveaxis(values, 0, 1).reshape(nblk, n * chunk)
    dense = jnp.take_along_axis(by_slot, slot.astype(jnp.int32), axis=1)
    return dense.astype(jnp.float32) / n


# ---------------------------------------------------------------------------
# Packed quantization wire: block QSGD / natural compression (DESIGN.md §4.6)
# ---------------------------------------------------------------------------


def qsgd_block_ref(x2d: jax.Array, seed: jax.Array, s: int):
    """Blockwise s-level ℓ2 QSGD with seeded murmur3 dither.

    x2d: (nblk, B); each block quantized against its OWN ℓ2 norm (the
    per-block f32 norm rides the wire — DESIGN.md §4.6), dither counters
    [b·B, (b+1)·B) so the stream is a pure function of (seed, coordinate).
    Returns (levels int8 (nblk, B), norms f32 (nblk,)); |level| ≤ s, so
    levels fit a signed nibble for s ≤ 7 and int8 for s ≤ 127."""
    nblk, B = x2d.shape
    x = x2d.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(x * x, axis=1))                    # (nblk,)
    safe = jnp.where(norm > 0, norm, 1.0)
    ctr = (
        jnp.arange(B, dtype=jnp.uint32)[None, :]
        + (jnp.arange(nblk, dtype=jnp.uint32) * B)[:, None]
    )
    u = uniform_from_bits_ref(murmur_bits_ref(seed.astype(jnp.uint32), ctr))
    level = jnp.floor(s * jnp.abs(x) / safe[:, None] + u)
    return (jnp.sign(x) * level).astype(jnp.int8), norm


def qsgd_block_workers_ref(x3d: jax.Array, seeds: jax.Array, s: int):
    """Per-worker blockwise QSGD: (n, nblk, B) + (n,) seeds →
    (levels (n, nblk, B) int8, norms (n, nblk) f32). Worker counter streams
    restart at 0, mirroring the tree path's per-worker key split."""
    return jax.vmap(
        lambda x2d, sd: qsgd_block_ref(x2d, sd.astype(jnp.uint32), s)
    )(x3d, seeds)


def qsgd_dequant_mean_ref(
    levels: jax.Array, norms: jax.Array, s: int
) -> jax.Array:
    """Fused server aggregation: (n, nblk, B) int8 levels + (n, nblk) norms
    → (nblk, B) f32 mean. Accumulates worker by worker (fori_loop) so the
    only dense f32 buffer is the single (nblk, B) accumulator — the (n, d)
    dequantized trees are never materialized, and the input traffic stays at
    int8 bandwidth. Same accumulation order as the Pallas kernel (bit-exact
    float sums)."""
    n, nblk, B = levels.shape

    def body(w, acc):
        lw = jax.lax.dynamic_index_in_dim(levels, w, 0, keepdims=False)
        nw = jax.lax.dynamic_index_in_dim(norms, w, 0, keepdims=False)
        return acc + lw.astype(jnp.float32) * (nw / s)[:, None]

    acc = jax.lax.fori_loop(0, n, body, jnp.zeros((nblk, B), jnp.float32))
    return acc / n


def natural_block_ref(x2d: jax.Array, seed: jax.Array):
    """Blockwise natural compression (Horváth et al. 2019) on the packed wire.

    |x| is stochastically rounded to a power of two (E preserved, ω = 1/8);
    the wire code is the exponent *delta* from the block's reference scale
    ``2^(⌊log2 max|x_b|⌋ + 1)``: code = sign·(delta + 1) in int8, 0 for true
    zeros AND for magnitudes ≥ 2^126 below the block max (dropping those is a
    ≤ 2^-126·‖x_b‖_∞ perturbation — below f32 relative resolution).
    Returns (codes int8 (nblk, B), scales f32 (nblk,))."""
    nblk, B = x2d.shape
    x = x2d.astype(jnp.float32)
    ax = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.where(ax > 0, ax, 1.0)))
    lo = jnp.exp2(e)
    p_up = jnp.where(ax > 0, (ax - lo) / lo, 0.0)              # in [0, 1)
    ctr = (
        jnp.arange(B, dtype=jnp.uint32)[None, :]
        + (jnp.arange(nblk, dtype=jnp.uint32) * B)[:, None]
    )
    u = uniform_from_bits_ref(murmur_bits_ref(seed.astype(jnp.uint32), ctr))
    e_q = e + (u < p_up).astype(jnp.float32)
    mx = jnp.max(ax, axis=1)                                   # (nblk,)
    e_ref = jnp.floor(jnp.log2(jnp.where(mx > 0, mx, 1.0))) + 1.0
    scale = jnp.exp2(e_ref)
    delta = e_ref[:, None] - e_q                               # ≥ 0
    keep = (ax > 0) & (delta <= 126.0)
    code = jnp.where(keep, jnp.sign(x) * (delta + 1.0), 0.0)
    return code.astype(jnp.int8), scale


def natural_block_workers_ref(x3d: jax.Array, seeds: jax.Array):
    """Per-worker blockwise natural compression: (n, nblk, B) + (n,) seeds →
    (codes (n, nblk, B) int8, scales (n, nblk) f32)."""
    return jax.vmap(
        lambda x2d, sd: natural_block_ref(x2d, sd.astype(jnp.uint32))
    )(x3d, seeds)


def natural_decode_ref(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(nblk, B) int8 codes + (nblk,) f32 scales → dense f32 block buffer."""
    c = codes.astype(jnp.float32)
    mag = scales[:, None] * jnp.exp2(-(jnp.abs(c) - 1.0))
    return jnp.where(c != 0, jnp.sign(c) * mag, 0.0)


def natural_dequant_mean_ref(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """Fused server aggregation of natural payloads: (n, nblk, B) int8 +
    (n, nblk) f32 → (nblk, B) f32 mean; single dense accumulator."""
    n, nblk, B = codes.shape

    def body(w, acc):
        cw = jax.lax.dynamic_index_in_dim(codes, w, 0, keepdims=False)
        sw = jax.lax.dynamic_index_in_dim(scales, w, 0, keepdims=False)
        return acc + natural_decode_ref(cw, sw)

    acc = jax.lax.fori_loop(0, n, body, jnp.zeros((nblk, B), jnp.float32))
    return acc / n


def nibble_pack_ref(q2d: jax.Array) -> jax.Array:
    """(…, nblk, B) int8 levels in [-8, 7] → (…, nblk, B/8) uint32 lane words.

    Level t of each 8-group occupies bits [4t, 4t+4) as a two's-complement
    nibble; this IS the 4-bit wire representation (half a byte per
    coordinate). Requires B % 8 == 0 (lane-aligned layouts always satisfy)."""
    *lead, B = q2d.shape
    assert B % 8 == 0, "block width must pack into whole uint32 words"
    nib = (q2d.astype(jnp.int32) & 0xF).astype(jnp.uint32).reshape(*lead, B // 8, 8)
    word = nib[..., 0]
    for t in range(1, 8):
        word = word | (nib[..., t] << jnp.uint32(4 * t))
    return word


def nibble_unpack_ref(words: jax.Array, block: int) -> jax.Array:
    """(…, nblk, B/8) uint32 lane words → (…, nblk, B) int8 (sign-extended
    nibbles). Exact inverse of :func:`nibble_pack_ref` on levels in [-8, 7]."""
    *lead, nw = words.shape
    assert nw * 8 == block
    nib = jnp.stack(
        [(words >> jnp.uint32(4 * t)) & jnp.uint32(0xF) for t in range(8)],
        axis=-1,
    ).astype(jnp.int8)                                         # values 0..15
    q = jnp.where(nib >= 8, nib - jnp.int8(16), nib)
    return q.reshape(*lead, block)


# ---------------------------------------------------------------------------
# Fused server epilogue (DESIGN.md §4.7): dequant/scatter-mean → g += δ →
# x −= γ·g in one (nblk, B)-tile sweep. Every oracle mirrors its Pallas twin
# in kernels/epilogue.py accumulation-order for accumulation-order, so integer
# payload handling is bit-exact and float sums agree to the same 1-ulp
# standard as the dequant-mean kernels (DESIGN.md §4.4).
# ---------------------------------------------------------------------------


def delta_epilogue_ref(delta2d, g2d, x2d, gamma: float):
    """Apply an already-dense round delta: g' = g + δ, x' = x − γ·g'.

    delta2d/g2d: (nblk, B) f32; x2d: (nblk, B) in the layout compute dtype.
    Returns (g_new f32, x_new x.dtype). The x update is evaluated exactly as
    the per-leaf path's ``tree_axpy(-γ, g', x)`` (IEEE sign-flip + commuted
    add are exact), so fused and unfused trajectories coincide bit for bit."""
    g_new = g2d.astype(jnp.float32) + delta2d.astype(jnp.float32)
    x_new = (-gamma) * g_new + x2d.astype(jnp.float32)
    return g_new, x_new.astype(x2d.dtype)


def mean_epilogue_ref(gbufs, x2d, gamma: float):
    """Sync-round epilogue: g' = mean over the worker axis of the packed
    gradient buffers (the ONE fused psum replacing the per-leaf tree mean),
    x' = x − γ·g'. gbufs: (n, nblk, B); returns (g_new f32, x_new x.dtype)."""
    g_new = jnp.mean(gbufs.astype(jnp.float32), axis=0)
    x_new = (-gamma) * g_new + x2d.astype(jnp.float32)
    return g_new, x_new.astype(x2d.dtype)


def scatter_epilogue_ref(values, offsets, g2d, x2d, gamma: float):
    """Seeded-RandK epilogue: scatter-accumulate the n worker payloads into
    the round delta and apply it, never materializing per-worker dense trees.
    values/offsets: (n, nblk, kb); returns (g_new f32, x_new x.dtype)."""
    delta = scatter_accum_ref(
        values.astype(jnp.float32), offsets, g2d.shape[-1]
    )
    return delta_epilogue_ref(delta, g2d, x2d, gamma)


def qsgd_epilogue_ref(levels, norms, g2d, x2d, gamma: float, s: int):
    """Packed-QSGD epilogue: fused dequantize-and-mean of the int8 payloads
    (same worker-indexed accumulation as ``qsgd_dequant_mean_ref``) + the
    g/x update. levels: (n, nblk, B) int8; norms: (n, nblk) f32."""
    delta = qsgd_dequant_mean_ref(levels, norms, s)
    return delta_epilogue_ref(delta, g2d, x2d, gamma)


def natural_epilogue_ref(codes, scales, g2d, x2d, gamma: float):
    """Natural-compression epilogue: fused decode-and-mean + g/x update."""
    delta = natural_dequant_mean_ref(codes, scales)
    return delta_epilogue_ref(delta, g2d, x2d, gamma)


def row_ranks_ref(rows: jax.Array) -> jax.Array:
    """Stable coordinate-wise ranks over the worker axis (sort-free).

    rows: (n, ...) — returns int32 ranks of the same shape where
    ``rank_i = #{j: v_j < v_i} + #{j < i: v_j == v_i}``. Ties break by worker
    index, so per coordinate the ranks are always a permutation of 0..n−1 —
    the k-th order statistic is the row with rank k, no sort needed. O(n²)
    compares per coordinate, accumulated worker by worker (fori_loop) in the
    exact order of the Pallas kernel; integer sums are order-free, so the
    ranks are bit-identical across backends."""
    n = rows.shape[0]
    x = rows.astype(jnp.float32)
    tail = (1,) * (x.ndim - 1)
    after_j = lambda j: (
        jnp.arange(n, dtype=jnp.int32) > j
    ).astype(jnp.int32).reshape((n,) + tail)

    def body(j, acc):
        vj = jax.lax.dynamic_index_in_dim(x, j, 0, keepdims=True)   # (1, ...)
        lt = (vj < x).astype(jnp.int32)
        tie = (vj == x).astype(jnp.int32) * after_j(j)
        return acc + lt + tie

    return jax.lax.fori_loop(0, n, body, jnp.zeros(x.shape, jnp.int32))


def trimmed_mean_rows_ref(rows: jax.Array, lo: int, hi: int) -> jax.Array:
    """Coordinate-wise trimmed mean over the worker axis.

    rows: (n, ...) → (...) f32: per coordinate, sort the n worker values and
    average the window ``[lo, hi)``. ``lo = f, hi = n−f`` is the f-trimmed
    mean; the coordinate-wise median is the trim-bound special case
    ``((n−1)//2, (n−1)//2+1)`` for odd n and ``(n//2−1, n//2+1)`` (mean of
    the two middle values) for even n.

    Implemented as an odd-even transposition sorting network over the
    (small) worker axis — ~n²/2 vectorized compare-exchanges, kept as a
    flat min/max DAG over per-row slices so XLA fuses it without buffer
    copies. That beats both the O(n²) sequential rank sweep of the Pallas
    kernel's formulation (``epilogue._trimmed_rows``) and ``jnp.sort``
    (whose CPU lowering is pathologically slow on a tiny sort axis with
    millions of batch columns) by an order of magnitude, and is
    *value-identical* to the kernel: the stable ranks are a permutation
    per coordinate, so the kept multiset is exactly the sorted window.
    NaN payloads are substituted with +inf before the network (min/max
    would propagate a NaN into BOTH lanes of a compare-exchange), sending
    them to the END, while under the rank semantics they rank 0 (every
    NaN comparison is false) — both land OUTSIDE every real trim window
    (``trim_bounds`` only emits lo ≥ 1 whenever hi < n), so the NaN
    exclusion matches; with f NaN rows the survivors are the honest
    values minus their f smallest. (More NaN rows than the trim width
    exceeds the rule's breakdown point — only the failure shape differs
    between the two formulations there.) Float sums may differ from the
    kernel by accumulation order — cross-backend tests compare with
    allclose, as for every other epilogue."""
    n = rows.shape[0]
    assert 0 <= lo < hi <= n, f"trim window [{lo}, {hi}) invalid for n={n}"
    x = rows.astype(jnp.float32)
    x = jnp.where(jnp.isnan(x), jnp.inf, x)
    r = [x[i] for i in range(n)]
    for stage in range(n):
        for i in range(stage % 2, n - 1, 2):
            a, b = r[i], r[i + 1]
            r[i] = jnp.minimum(a, b)
            r[i + 1] = jnp.maximum(a, b)
    acc = r[lo]
    for i in range(lo + 1, hi):
        acc = acc + r[i]
    return acc / (hi - lo)


def trimmed_delta_epilogue_ref(bufs, g2d, x2d, gamma: float, lo: int, hi: int):
    """Robust compressed-round epilogue: g' = g + trimmed_mean(worker rows),
    x' = x − γ·g'. bufs: (n, nblk, B) per-worker dense payload rows."""
    delta = trimmed_mean_rows_ref(bufs, lo, hi)
    return delta_epilogue_ref(delta, g2d, x2d, gamma)


def trimmed_sync_epilogue_ref(bufs, x2d, gamma: float, lo: int, hi: int):
    """Robust sync-round epilogue: g' = trimmed_mean of the packed worker
    gradient buffers (replacing the worker mean), x' = x − γ·g'."""
    g_new = trimmed_mean_rows_ref(bufs, lo, hi)
    x_new = (-gamma) * g_new + x2d.astype(jnp.float32)
    return g_new, x_new.astype(x2d.dtype)


def randk_qsgd_workers_ref(
    x3d: jax.Array, seeds: jax.Array, kb: int, scale: float, s: int
):
    """RandK∘QSGD composition uplink: seeded RandK keeps kb coords per block
    (scaled B/kb), then blockwise QSGD quantizes ONLY those K values against
    the per-block norm of the sampled vector. Dither counters live at
    DITHER_CTR_OFFSET so they never collide with the index stream of the same
    seed. Returns (levels (n, nblk, kb) int8, offsets (n, nblk, kb) int32,
    norms (n, nblk) f32). K-sized compute: no Pallas kernel needed — the
    quantization touches ζ ≪ d values (the gather/scatter stay on the fused
    kernels)."""
    vals, offs = randk_seeded_workers_ref(x3d, seeds, kb, scale)
    levels, norms = qsgd_sampled_quantize_ref(vals, seeds, s)
    return levels, offs, norms


def qsgd_sampled_quantize_ref(vals: jax.Array, seeds: jax.Array, s: int):
    """QSGD stage of the composition: quantize already-sampled values
    (n, nblk, kb) against per-block norms of the SAMPLED vector. Works on
    whatever the gather kernel produced (so the gather itself can stay on the
    backend-switched Pallas path). Returns (levels int8, norms f32)."""
    _, nblk, kb = vals.shape
    ctr = (
        jnp.arange(kb, dtype=jnp.uint32)[None, :]
        + (jnp.arange(nblk, dtype=jnp.uint32) * kb)[:, None]
        + jnp.uint32(DITHER_CTR_OFFSET)
    )

    def quantize(v2d, sd):
        v = v2d.astype(jnp.float32)
        norm = jnp.sqrt(jnp.sum(v * v, axis=1))
        safe = jnp.where(norm > 0, norm, 1.0)
        u = uniform_from_bits_ref(murmur_bits_ref(sd.astype(jnp.uint32), ctr))
        level = jnp.floor(s * jnp.abs(v) / safe[:, None] + u)
        return (jnp.sign(v) * level).astype(jnp.int8), norm

    return jax.vmap(quantize)(vals, seeds)


def randk_qsgd_dequant_ref(
    levels: jax.Array, norms: jax.Array, s: int
) -> jax.Array:
    """Composition payload → f32 values ready for scatter-accumulate:
    (n, nblk, kb) int8 + (n, nblk) f32 → (n, nblk, kb) f32. K-sized."""
    return levels.astype(jnp.float32) * (norms / s)[..., None]


# ---------------------------------------------------------------------------
# Paged KV cache: block-table-gather attention + int8 page rows (DESIGN.md §8)
# ---------------------------------------------------------------------------

#: masking sentinel, matching models/attention.py (exp(−1e30 − m) underflows
#: to exactly 0.0 in f32, so masked positions contribute exact zeros)
_NEG_INF = -1e30


def paged_gather_ref(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """(npage, P, ...) pool + (S, max_pages) int32 tables →
    (S, max_pages·P, ...) per-slot flat cache views. Token t of slot s lands
    at flat index t (pages are gathered in block-table order), so position
    masks are plain ``arange(L) < n_valid`` — no indirection survives the
    gather."""
    g = pages[tables]                       # (S, maxp, P, ...)
    S, maxp, P = g.shape[:3]
    return g.reshape(S, maxp * P, *g.shape[3:])


def paged_attend_ref(
    q: jax.Array, k_flat: jax.Array, v_flat: jax.Array, n_valid: jax.Array
) -> jax.Array:
    """Single-query attention over gathered per-slot caches.

    q (S, H, hd); k_flat/v_flat (S, L, KV, hd); n_valid (S,) int32 — valid
    positions per slot INCLUDING the current token (callers write k_t/v_t
    before attending). Same op sequence as the dense ``attn_decode`` body
    (GQA repeat, f32 logits/softmax, v-dtype output) and, per slot, as the
    Pallas kernel in kernels/paged.py — the bit-exactness contract."""
    S, H, hd = q.shape
    KV = k_flat.shape[2]
    rep = H // KV
    k_e = jnp.repeat(k_flat, rep, axis=2) if rep > 1 else k_flat
    v_e = jnp.repeat(v_flat, rep, axis=2) if rep > 1 else v_flat
    scale = 1.0 / jnp.sqrt(hd)
    logits = jnp.einsum("shd,skhd->shk", q, k_e).astype(jnp.float32) * scale
    L = k_flat.shape[1]
    valid = jnp.arange(L)[None, :] < n_valid[:, None]
    logits = jnp.where(valid[:, None, :], logits, _NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("shk,skhd->shd", w.astype(v_e.dtype), v_e)


def paged_attn_decode_ref(
    q: jax.Array,
    kpages: jax.Array,
    vpages: jax.Array,
    tables: jax.Array,
    n_valid: jax.Array,
) -> jax.Array:
    """Oracle for the paged-attention decode kernel: gather pages through the
    block tables, then one-shot masked attention. q (S, H, hd);
    kpages/vpages (npage, P, KV, hd); tables (S, max_pages) int32;
    n_valid (S,) int32. Returns (S, H, hd) in v dtype."""
    return paged_attend_ref(
        q, paged_gather_ref(kpages, tables), paged_gather_ref(vpages, tables),
        n_valid,
    )


def absmax_quant_rows_ref(x2d: jax.Array):
    """Symmetric absmax int8 quantization per row (the quantized-page wire).

    x2d (R, W) → (codes int8 (R, W), scales f32 (R,)): scale = max|x|/127,
    code = round-to-nearest-even(x / scale). Deterministic (no dither —
    KV entries are read many times, so unbiased-per-read stochastic noise
    would not average out the way a gradient's does). Error model:
    |x − x̂| ≤ scale/2 = max|x|/254 per element (DESIGN.md §8)."""
    x = x2d.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=1)
    # multiply by the f32 reciprocal instead of dividing: XLA rewrites x/127
    # into x * (1/127) in some lowerings but not others, and the kernel must
    # match this oracle bit-for-bit
    scale = amax * jnp.float32(1.0 / 127.0)
    safe = jnp.where(scale > 0, scale, 1.0)
    codes = jnp.round(x / safe[:, None]).astype(jnp.int8)
    return codes, scale


def absmax_dequant_rows_ref(codes: jax.Array, scales: jax.Array) -> jax.Array:
    """(R, W) int8 codes + (R,) f32 scales → (R, W) f32 rows."""
    return codes.astype(jnp.float32) * scales[:, None]


def paged_attn_decode_q8_ref(
    q: jax.Array,
    kq: jax.Array,
    vq: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    tables: jax.Array,
    n_valid: jax.Array,
) -> jax.Array:
    """Quantized-page decode attention: gather int8 pages (kq/vq
    (npage, P, KV, hd) int8, scales (npage, P, KV) f32) through the block
    tables, dequantize ONLY the gathered rows, then the same attention body
    as the f32 path. HBM traffic for the cache read is int8 + one f32 scale
    per (row, kv-head) — the 2–4× KV-memory cut of the quantized-page mode."""
    kgf = paged_gather_ref(kq, tables).astype(jnp.float32)      # (S, L, KV, hd)
    vgf = paged_gather_ref(vq, tables).astype(jnp.float32)
    ks = paged_gather_ref(k_scale, tables)                      # (S, L, KV)
    vs = paged_gather_ref(v_scale, tables)
    return paged_attend_ref(q, kgf * ks[..., None], vgf * vs[..., None], n_valid)
