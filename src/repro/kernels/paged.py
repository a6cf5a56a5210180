"""Pallas TPU kernel for paged-KV single-query attention (DESIGN.md §8).

The serving engine's decode hot path: each slot's query attends over the KV
pages its block-table row names. The kernel is a scalar-prefetch gather —
grid ``(n_slots, max_pages)``, with the block table and valid-length vector
prefetched into SMEM so the *index map itself* performs the page gather:
step ``(s, p)`` DMAs page ``tables[s, p]`` of the pool into VMEM, and the
last page step runs one masked softmax over the assembled per-slot cache.
No dense (S, max_len) cache is ever materialized; idle table entries point
at the null page and are masked by ``n_valid``.

Decode attention is memory-bound (every step streams the active KV pages
once, at arithmetic intensity ~1 FLOP/byte against the ~240 FLOP/byte
ridge), so the win is exactly the bytes the paging avoids: the pool holds
``Σ ceil(len_i / P)`` pages instead of ``n_slots × max_len`` rows.

Backend contract (like every kernel in this package): ``auto`` → compiled
Pallas on TPU, the bit-exact jnp oracle (kernels/ref.py) elsewhere;
``pallas_interpret`` validates the kernel body op-for-op against the
oracle. The int8 quantized-page mode routes through the jnp gather+dequant
path on every backend — int8 HBM traffic is already the win; a fused int8
kernel is future work (DESIGN.md §8).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as _ref

_NEG_INF = -1e30


def _resolve(backend: str) -> str:
    from repro.core.flat import resolve_backend

    return resolve_backend(backend)


def _paged_attn_kernel(
    tbl_ref, nv_ref, q_ref, k_ref, v_ref, out_ref, k_scr, v_scr,
    *, page_size: int, max_pages: int,
):
    """Grid step (s, p): land page ``tables[s, p]`` in the per-slot scratch
    cache; on the slot's last page, attend. Mirrors ``paged_attend_ref``
    op for op (GQA repeat, f32 logits/softmax, v-dtype output)."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    k_scr[pl.ds(p * page_size, page_size)] = k_ref[0]
    v_scr[pl.ds(p * page_size, page_size)] = v_ref[0]

    @pl.when(p == max_pages - 1)
    def _attend():
        q = q_ref[0]                                  # (H, hd)
        k = k_scr[...]                                # (L, KV, hd)
        v = v_scr[...]
        H, hd = q.shape
        KV = k.shape[1]
        rep = H // KV
        k_e = jnp.repeat(k, rep, axis=1) if rep > 1 else k
        v_e = jnp.repeat(v, rep, axis=1) if rep > 1 else v
        scale = 1.0 / jnp.sqrt(hd)
        logits = jnp.einsum("hd,khd->hk", q, k_e).astype(jnp.float32) * scale
        L = k.shape[0]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
        logits = jnp.where(idx < nv_ref[s], logits, _NEG_INF)
        w = jax.nn.softmax(logits, axis=-1)
        out_ref[0] = jnp.einsum("hk,khd->hd", w.astype(v_e.dtype), v_e)


def paged_attn_decode(
    q: jax.Array,
    kpages: jax.Array,
    vpages: jax.Array,
    tables: jax.Array,
    n_valid: jax.Array,
    *,
    backend: str = "auto",
) -> jax.Array:
    """Block-table-gather single-query attention.

    q (S, H, hd); kpages/vpages (npage, P, KV, hd); tables (S, max_pages)
    int32 (page 0 = null); n_valid (S,) int32 — valid cache positions per
    slot INCLUDING the current token. Returns (S, H, hd) in v dtype.
    """
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.paged_attn_decode_ref(q, kpages, vpages, tables, n_valid)
    S, H, hd = q.shape
    _, P, KV, _ = kpages.shape
    maxp = tables.shape[1]
    L = maxp * P
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, maxp),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda s, p, tbl, nv: (s, 0, 0)),
            pl.BlockSpec(
                (1, P, KV, hd), lambda s, p, tbl, nv: (tbl[s, p], 0, 0, 0)
            ),
            pl.BlockSpec(
                (1, P, KV, hd), lambda s, p, tbl, nv: (tbl[s, p], 0, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda s, p, tbl, nv: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((L, KV, hd), kpages.dtype),
            pltpu.VMEM((L, KV, hd), vpages.dtype),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_attn_kernel, page_size=P, max_pages=maxp),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, hd), vpages.dtype),
        interpret=(backend == "pallas_interpret"),
        name="paged_attn_decode",
    )(tables.astype(jnp.int32), n_valid.astype(jnp.int32), q, kpages, vpages)


def paged_attn_decode_q8(
    q: jax.Array,
    kq: jax.Array,
    vq: jax.Array,
    k_scale: jax.Array,
    v_scale: jax.Array,
    tables: jax.Array,
    n_valid: jax.Array,
    *,
    backend: str = "auto",
) -> jax.Array:
    """int8 quantized-page decode attention: every backend runs the jnp
    gather + dequantize-gathered-rows path (see module docstring); the
    ``backend`` arg is accepted for routing symmetry and validated."""
    _resolve(backend)
    return _ref.paged_attn_decode_q8_ref(
        q, kq, vq, k_scale, v_scale, tables, n_valid
    )
