"""Pallas TPU kernels for the packed quantization wire (DESIGN.md §4.6/§5).

Every entry point takes ``backend="auto"`` and routes through
``repro.core.flat.resolve_backend`` exactly like the randk/permk primitives:
compiled Pallas on TPU, the bit-exact jnp oracle (kernels/ref.py) on CPU,
``pallas_interpret`` for interpreter-mode validation. (The v1 module
hardcoded ``interpret=True`` everywhere, so TPU ran these kernels in the
interpreter — the one backend that should never see interpret mode.)

Kernel inventory:

* ``block_sumsq`` / ``qsgd_quantize`` / ``qsgd_dequantize`` — the original
  two-pass global-norm QSGD (kept for the ops.py flat-vector wrappers).
* ``qsgd_block_workers`` — fused blockwise QSGD uplink: each grid step takes
  an (R, B) tile of whole blocks (kernels/tiling.py), computes each block's
  ℓ2 norm, draws the murmur3 dither on-chip, and writes int8 levels + the
  per-block f32 norms in a single VPU sweep (memory-bound at the int8
  *output* bandwidth). The grid is (worker, row tile) with per-worker seeds
  in SMEM, like ``randk_seeded_workers``.
* ``natural_block_workers`` — fused natural compression: stochastic
  power-of-two rounding, wire code = sign·(exponent-delta+1) int8 against the
  block's reference scale.
* ``qsgd_dequant_mean`` / ``natural_dequant_mean`` — the fused
  dequantize-and-mean server side: accumulates the n workers' int8 payloads
  into one (R, B) f32 tile; input traffic is int8, the (n, d) dequantized
  trees are never materialized.
* ``nibble_pack`` / ``nibble_unpack`` — the 4-bit wire: two's-complement
  nibbles, eight per uint32 lane word (half a byte per coordinate for
  s ≤ 7). Gathering eight neighbouring lanes into one word is a lane
  shuffle Mosaic does not offer, so it runs on the MXU as a product with a
  constant 0/1/16^t matrix: nibbles, bytes and their partial words are small
  integers, exact in bf16 operands and f32 sums.

Per-block norms and scales cross the kernel boundary as (…, nblk, 1) columns
and are (…, nblk) outside it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import ref as _ref
from .randk import murmur_bits
from .tiling import (
    Smem, Whole, lanes, per_block, row_call, stack_call, tile_rows, to_unit,
)


def _resolve(backend: str) -> str:
    from repro.core.flat import resolve_backend

    return resolve_backend(backend)


# ---------------------------------------------------------------------------
# Two-pass global-norm QSGD (ops.py flat-vector path)
# ---------------------------------------------------------------------------


def _block_sumsq_kernel(x_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)   # (R, B)
    out_ref[...] = jnp.sum(x * x, axis=-1, keepdims=True)  # (R, 1)


def block_sumsq(x2d: jax.Array, *, backend: str = "auto") -> jax.Array:
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.block_sumsq_ref(x2d)
    nblk, B = x2d.shape
    (out,) = row_call(
        _block_sumsq_kernel, [x2d], [(1, jnp.float32)], name="block_sumsq",
        interpret=(backend == "pallas_interpret"),
    )
    return out.reshape(nblk)


def _qsgd_kernel(x_ref, u_ref, norm_ref, out_ref, *, s: int):
    x = x_ref[...].astype(jnp.float32)   # (R, B)
    u = u_ref[...]                        # (R, B)
    norm = norm_ref[...]                  # (1, 1)
    safe = jnp.where(norm > 0, norm, 1.0)
    level = jnp.floor(s * jnp.abs(x) / safe + u)
    out_ref[...] = (jnp.sign(x) * level).astype(jnp.int8)


def qsgd_quantize(
    x2d: jax.Array, u2d: jax.Array, norm: jax.Array, s: int, *,
    backend: str = "auto",
) -> jax.Array:
    """(nblk, B) f32/bf16 → (nblk, B) int8 levels; norm is the global ℓ2 norm."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.qsgd_quantize_ref(x2d, u2d, norm, s)
    B = x2d.shape[1]
    (out,) = row_call(
        functools.partial(_qsgd_kernel, s=int(s)),
        [x2d, u2d, Whole(norm.reshape(1, 1).astype(jnp.float32))],
        [(B, jnp.int8)], name="qsgd_quantize", interpret=(backend == "pallas_interpret"),
    )
    return out


def _dequant_kernel(q_ref, norm_ref, out_ref, *, s: int):
    q = q_ref[...].astype(jnp.float32)
    out_ref[...] = (q * (norm_ref[...] / s)).astype(out_ref.dtype)


def qsgd_dequantize(
    q2d: jax.Array, norm: jax.Array, s: int, *, backend: str = "auto"
) -> jax.Array:
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.qsgd_dequantize_ref(q2d, norm, s)
    B = q2d.shape[1]
    (out,) = row_call(
        functools.partial(_dequant_kernel, s=int(s)),
        [q2d, Whole(norm.reshape(1, 1).astype(jnp.float32))],
        [(B, jnp.float32)], name="qsgd_dequantize", interpret=(backend == "pallas_interpret"),
    )
    return out


# ---------------------------------------------------------------------------
# Fused blockwise QSGD uplink (per-block norms on the wire — DESIGN.md §4.6)
# ---------------------------------------------------------------------------


def _dither(seed_ref, R: int, B: int) -> jax.Array:
    """(R, B) f32 uniforms of the current (worker, row tile): worker-local
    dither stream where block b covers counters [b·B, (b+1)·B) — the stream
    the per-leaf compressors draw, so tree/flat paths coincide."""
    w, j = pl.program_id(0), pl.program_id(1)
    ctr = tile_rows(j, R, B) * jnp.uint32(B) + lanes(R, B).astype(jnp.uint32)
    return to_unit(murmur_bits(seed_ref[w].astype(jnp.uint32), ctr))


def _qsgd_block_workers_kernel(seed_ref, x_ref, q_ref, norm_ref, *, s: int):
    x = x_ref[...].astype(jnp.float32)   # (R, B)
    R, B = x.shape
    norm = jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True))   # (R, 1)
    safe = jnp.where(norm > 0, norm, 1.0)
    level = jnp.floor(s * jnp.abs(x) / safe + _dither(seed_ref, R, B))
    q_ref[...] = (jnp.sign(x) * level).astype(jnp.int8)
    norm_ref[...] = norm


def qsgd_block_workers(
    x3d: jax.Array, seeds: jax.Array, s: int, *, backend: str = "auto"
):
    """Fused per-worker blockwise QSGD: (n, nblk, B) + (n,) seeds →
    (levels (n, nblk, B) int8, norms (n, nblk) f32). One VPU sweep per
    (R, B) tile: norm, dither, scale, floor, int8 cast — the quantize pass
    writes at int8 bandwidth instead of three f32 round trips."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.qsgd_block_workers_ref(x3d, seeds.astype(jnp.uint32), s)
    n, nblk, B = x3d.shape
    q, norms = stack_call(
        functools.partial(_qsgd_block_workers_kernel, s=int(s)),
        [Smem(seeds.astype(jnp.int32)), x3d],
        [(B, jnp.int8), (1, jnp.float32)], name="qsgd_block_workers",
        interpret=(backend == "pallas_interpret"),
    )
    return q, norms.reshape(n, nblk)


def qsgd_rows(q_ref, norm_ref, s: int) -> jax.Array:
    """Σ over workers of the dequantized (R, B) level tiles, worker order."""
    n, R, B = q_ref.shape

    def body(w, acc):
        return acc + q_ref[w].astype(jnp.float32) * (norm_ref[w] / s)

    return jax.lax.fori_loop(0, n, body, jnp.zeros((R, B), jnp.float32))


def _qsgd_dequant_mean_kernel(q_ref, norm_ref, out_ref, *, s: int):
    out_ref[...] = qsgd_rows(q_ref, norm_ref, s) / q_ref.shape[0]


def qsgd_dequant_mean(
    levels: jax.Array, norms: jax.Array, s: int, *, backend: str = "auto"
) -> jax.Array:
    """Fused dequantize-and-mean: (n, nblk, B) int8 + (n, nblk) f32 →
    (nblk, B) f32 mean over workers. Each grid step owns one (R, B) output
    tile and streams the n int8 payloads through it — aggregation runs at
    int8 input bandwidth with a single dense f32 accumulator."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.qsgd_dequant_mean_ref(levels, norms, s)
    B = levels.shape[-1]
    (out,) = row_call(
        functools.partial(_qsgd_dequant_mean_kernel, s=int(s)),
        [levels, per_block(norms)], [(B, jnp.float32)], name="qsgd_dequant_mean",
        interpret=(backend == "pallas_interpret"),
    )
    return out


# ---------------------------------------------------------------------------
# Fused blockwise natural compression (power-of-two stochastic rounding)
# ---------------------------------------------------------------------------


def _natural_block_workers_kernel(seed_ref, x_ref, code_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)   # (R, B)
    R, B = x.shape
    ax = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.where(ax > 0, ax, 1.0)))
    lo = jnp.exp2(e)
    p_up = jnp.where(ax > 0, (ax - lo) / lo, 0.0)
    e_q = e + (_dither(seed_ref, R, B) < p_up).astype(jnp.float32)
    mx = jnp.max(ax, axis=-1, keepdims=True)                  # (R, 1)
    e_ref = jnp.floor(jnp.log2(jnp.where(mx > 0, mx, 1.0))) + 1.0
    delta = e_ref - e_q
    keep = (ax > 0) & (delta <= 126.0)
    code_ref[...] = jnp.where(
        keep, jnp.sign(x) * (delta + 1.0), 0.0
    ).astype(jnp.int8)
    scale_ref[...] = jnp.exp2(e_ref)


def natural_block_workers(
    x3d: jax.Array, seeds: jax.Array, *, backend: str = "auto"
):
    """Fused per-worker natural compression: (n, nblk, B) + (n,) seeds →
    (codes (n, nblk, B) int8, scales (n, nblk) f32)."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.natural_block_workers_ref(x3d, seeds.astype(jnp.uint32))
    n, nblk, B = x3d.shape
    codes, scales = stack_call(
        _natural_block_workers_kernel, [Smem(seeds.astype(jnp.int32)), x3d],
        [(B, jnp.int8), (1, jnp.float32)], name="natural_block_workers",
        interpret=(backend == "pallas_interpret"),
    )
    return codes, scales.reshape(n, nblk)


def natural_rows(code_ref, scale_ref) -> jax.Array:
    """Σ over workers of the decoded (R, B) natural-code tiles."""
    n, R, B = code_ref.shape

    def body(w, acc):
        c = code_ref[w].astype(jnp.float32)
        mag = scale_ref[w] * jnp.exp2(-(jnp.abs(c) - 1.0))
        return acc + jnp.where(c != 0, jnp.sign(c) * mag, 0.0)

    return jax.lax.fori_loop(0, n, body, jnp.zeros((R, B), jnp.float32))


def _natural_dequant_mean_kernel(code_ref, scale_ref, out_ref):
    out_ref[...] = natural_rows(code_ref, scale_ref) / code_ref.shape[0]


def natural_dequant_mean(
    codes: jax.Array, scales: jax.Array, *, backend: str = "auto"
) -> jax.Array:
    """Fused decode-and-mean of natural payloads: (n, nblk, B) int8 +
    (n, nblk) f32 → (nblk, B) f32; int8 input bandwidth."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.natural_dequant_mean_ref(codes, scales)
    B = codes.shape[-1]
    (out,) = row_call(
        _natural_dequant_mean_kernel, [codes, per_block(scales)],
        [(B, jnp.float32)], name="natural_dequant_mean", interpret=(backend == "pallas_interpret"),
    )
    return out


# ---------------------------------------------------------------------------
# 4-bit wire: nibble pack/unpack (two levels per byte, eight per uint32)
# ---------------------------------------------------------------------------


def _pack_matrix(B: int) -> jax.Array:
    """(B, B/4) bf16: column k of the low half sums nibbles 0–3 of word k
    weighted 16^t, column B/8 + k of the high half sums nibbles 4–7."""
    m = np.zeros((B, B // 4), np.float32)
    k = np.arange(B // 8)
    for t in range(8):
        m[8 * k + t, (t // 4) * (B // 8) + k] = 16.0 ** (t % 4)
    return jnp.asarray(m, jnp.bfloat16)


def _unpack_matrix(B: int) -> jax.Array:
    """(B/2, B) bf16: lane 8k+t takes byte t//2 of word k, where the bytes
    enter as B/8-lane groups, byte m at lanes [m·B/8, (m+1)·B/8)."""
    m = np.zeros((B // 2, B), np.float32)
    k = np.arange(B // 8)
    for t in range(8):
        m[(t // 2) * (B // 8) + k, 8 * k + t] = 1.0
    return jnp.asarray(m, jnp.bfloat16)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _nibble_pack_kernel(q_ref, m_ref, out_ref):
    nw = out_ref.shape[-1]               # B/8
    nib = (q_ref[...].astype(jnp.int32) & 0xF).astype(jnp.float32)
    halves = _dot(nib.astype(jnp.bfloat16), m_ref[...]).astype(jnp.int32)
    word = halves[:, :nw] | (halves[:, nw:] << 16)
    out_ref[...] = jax.lax.bitcast_convert_type(word, jnp.uint32)


def _stacked(fn, x: jax.Array) -> jax.Array:
    """Run a (n, nblk, w) stack kernel on a 2-D or 3-D operand."""
    return fn(x[None])[0] if x.ndim == 2 else fn(x)


def nibble_pack(q: jax.Array, *, backend: str = "auto") -> jax.Array:
    """(…, nblk, B) int8 levels in [-8, 7] → (…, nblk, B/8) uint32 lane
    words — the genuine 4-bit on-wire representation (DESIGN.md §4.6).
    Takes one buffer or a worker stack, which stays a stack: flattening
    (n, nblk) rows of int8 is a relayout whenever nblk is odd."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.nibble_pack_ref(q)
    B = q.shape[-1]
    assert B % 8 == 0, "block width must pack into whole uint32 words"
    return _stacked(
        lambda q3: stack_call(
            _nibble_pack_kernel, [q3, Whole(_pack_matrix(B))],
            [(B // 8, jnp.uint32)], name="nibble_pack", interpret=(backend == "pallas_interpret"),
        )[0],
        q,
    )


def _nibble_unpack_kernel(w_ref, m_ref, out_ref):
    words = jax.lax.bitcast_convert_type(w_ref[...], jnp.int32)  # (R, B/8)
    R, B = out_ref.shape
    bytes_ = jnp.concatenate(
        [(words >> (8 * m)) & 0xFF for m in range(4)], axis=1
    ).astype(jnp.float32)                                      # (R, B/2)
    b = _dot(bytes_.astype(jnp.bfloat16), m_ref[...]).astype(jnp.int32)
    nib = jnp.where((lanes(R, B) & 1) == 1, b >> 4, b) & 0xF   # 0..15
    out_ref[...] = jnp.where(nib >= 8, nib - 16, nib).astype(jnp.int8)


def nibble_unpack(
    words: jax.Array, block: int, *, backend: str = "auto"
) -> jax.Array:
    """(…, nblk, B/8) uint32 lane words → (…, nblk, B) int8; exact inverse
    of :func:`nibble_pack` on levels in [-8, 7]."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.nibble_unpack_ref(words, block)
    assert words.shape[-1] * 8 == block
    return _stacked(
        lambda w3: stack_call(
            _nibble_unpack_kernel, [w3, Whole(_unpack_matrix(block))],
            [(block, jnp.int8)], name="nibble_unpack", interpret=(backend == "pallas_interpret"),
        )[0],
        words,
    )


# ---------------------------------------------------------------------------
# int8 KV-page rows (serving engine quantized-page mode, DESIGN.md §8)
# ---------------------------------------------------------------------------


def _absmax_quant_rows_kernel(x_ref, code_ref, scale_ref):
    x = x_ref[...].astype(jnp.float32)                       # (R, W)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)       # (R, 1)
    scale = amax * jnp.float32(1.0 / 127.0)  # reciprocal-multiply: see ref
    safe = jnp.where(scale > 0, scale, 1.0)
    code_ref[...] = jnp.round(x / safe).astype(jnp.int8)
    scale_ref[...] = scale


def absmax_quant_rows(x2d: jax.Array, *, backend: str = "auto"):
    """Symmetric absmax int8 quantization per row: (R, W) → (codes int8
    (R, W), scales f32 (R,)). The KV-page write path — deterministic
    round-to-nearest-even, no dither (cache rows are read many times, so
    per-read stochastic noise would not average out like a gradient's);
    error model |x − x̂| ≤ max|x|/254 per element (DESIGN.md §8)."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.absmax_quant_rows_ref(x2d)
    R, W = x2d.shape
    codes, scales = row_call(
        _absmax_quant_rows_kernel, [x2d],
        [(W, jnp.int8), (1, jnp.float32)], name="absmax_quant_rows",
        interpret=(backend == "pallas_interpret"),
    )
    return codes, scales.reshape(R)


def _absmax_dequant_rows_kernel(code_ref, scale_ref, out_ref):
    out_ref[...] = code_ref[...].astype(jnp.float32) * scale_ref[...]


def absmax_dequant_rows(
    codes: jax.Array, scales: jax.Array, *, backend: str = "auto"
) -> jax.Array:
    """(R, W) int8 codes + (R,) f32 scales → (R, W) f32 rows; exact inverse
    of the representable points of :func:`absmax_quant_rows`."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.absmax_dequant_rows_ref(codes, scales)
    R, W = codes.shape
    (out,) = row_call(
        _absmax_dequant_rows_kernel,
        [codes, scales.reshape(R, 1).astype(jnp.float32)],
        [(W, jnp.float32)], name="absmax_dequant_rows", interpret=(backend == "pallas_interpret"),
    )
    return out
