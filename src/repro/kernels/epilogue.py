"""Pallas TPU kernels for the fused server epilogue (DESIGN.md §4.7/§5).

One HBM sweep over (R, B) row tiles (kernels/tiling.py) finishes a compressed round on the receiving
side: dequantize/scatter-mean the worker payloads into the round delta,
advance the estimator ``g += δ`` and step the iterate ``x −= γ·g`` — three
passes (dequant-mean kernel + two ``tree.map`` sweeps) collapsed into one
kernel whose only dense traffic is reading (g, x) and writing (g', x') once.
The same kernels consume either direction's wire format: the n-worker uplink
payloads directly (no downlink configured), or the single server payload of
the compressed downlink ``Q_down(g^{k+1} − g^k)`` (n = 1), which makes them
the worker-side decompress-accumulate of the bidirectional wire.

Variants (one per wire family, mirroring the PR-3 kernel suite):

* ``delta_epilogue``   — already-dense δ (PermK concat-mean, tree paths).
* ``mean_epilogue``    — sync rounds: worker-mean of the packed gradient
                         buffers fused with the x update (the "sync rounds
                         ride the flat buffer" exchange).
* ``scatter_epilogue`` — seeded-RandK payloads: scatter-accumulate (one-hot
                         MXU matmuls, as in ``scatter_accum``) + apply.
* ``qsgd_epilogue``    — packed block-QSGD payloads: worker-indexed int8
                         dequant accumulation (input bandwidth stays int8).
* ``natural_epilogue`` — natural-compression payloads.
* ``trimmed_delta_epilogue`` / ``trimmed_sync_epilogue`` — Byzantine-robust
  rounds (DESIGN.md §4.9): coordinate-wise trimmed mean / median over the n
  worker rows via a sort-free rank selection, fused with the same update.

Every entry point takes ``backend="auto"`` and routes through
``repro.core.flat.resolve_backend``; the pure-jnp oracles live in
``kernels/ref.py`` (integer payload handling bit-exact, float accumulations
to the 1-ulp standard of DESIGN.md §4.4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.tracing import stage

from . import ref as _ref
from .quantize import natural_rows, qsgd_rows
from .randk import scatter_rows
from .tiling import per_block, row_call


def _resolve(backend: str) -> str:
    from repro.core.flat import resolve_backend

    return resolve_backend(backend)


def _apply(g_new, x, gamma):
    """The shared tail: x' = (−γ)·g' + x, evaluated exactly like the
    per-leaf ``tree_axpy(-γ, g', x)`` so fused/unfused trajectories agree
    bit for bit (sign-flip and commuted add are IEEE-exact)."""
    return ((-gamma) * g_new + x.astype(jnp.float32))


# ---------------------------------------------------------------------------
# Dense-δ and sync-mean epilogues
# ---------------------------------------------------------------------------


def _finish(g_new, x_ref, gout_ref, xout_ref, gamma):
    gout_ref[...] = g_new
    xout_ref[...] = _apply(g_new, x_ref[...], gamma).astype(xout_ref.dtype)


def _call(kernel, args, x2d, backend, name):
    """Row-tiled epilogue call ``name``: the last operand is x; outputs
    (g' f32, x' in x's dtype) of x's shape."""
    B = x2d.shape[-1]
    return row_call(
        kernel, [*args, x2d], [(B, jnp.float32), (B, x2d.dtype)], name=name,
        interpret=(backend == "pallas_interpret"),
    )


def _delta_epilogue_kernel(d_ref, g_ref, x_ref, gout_ref, xout_ref, *, gamma):
    g_new = g_ref[...].astype(jnp.float32) + d_ref[...].astype(jnp.float32)
    _finish(g_new, x_ref, gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def delta_epilogue(delta2d, g2d, x2d, gamma: float, *, backend: str = "auto"):
    """(nblk, B) dense δ + g + x → (g' f32, x' x.dtype) in one sweep."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.delta_epilogue_ref(delta2d, g2d, x2d, float(gamma))
    return _call(
        functools.partial(_delta_epilogue_kernel, gamma=float(gamma)),
        [delta2d, g2d], x2d, backend, "delta_epilogue",
    )


def _mean_epilogue_kernel(gb_ref, x_ref, gout_ref, xout_ref, *, gamma):
    n = gb_ref.shape[0]

    def body(w, acc):
        return acc + gb_ref[w].astype(jnp.float32)

    acc = jax.lax.fori_loop(0, n, body, jnp.zeros(x_ref.shape, jnp.float32))
    _finish(acc / n, x_ref, gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def mean_epilogue(gbufs, x2d, gamma: float, *, backend: str = "auto"):
    """Sync-round epilogue: (n, nblk, B) packed worker gradients + x →
    (g' = worker mean f32, x' x.dtype). The worker mean runs over the ONE
    packed buffer — the fused psum replacing the per-leaf tree exchange."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.mean_epilogue_ref(gbufs, x2d, float(gamma))
    return _call(
        functools.partial(_mean_epilogue_kernel, gamma=float(gamma)),
        [gbufs], x2d, backend, "mean_epilogue",
    )


# ---------------------------------------------------------------------------
# Robust (GAR) epilogues: coordinate-wise trimmed mean / median over the n
# worker rows, fused with the g/x update (DESIGN.md §4.9). Sort-free k-th
# statistic: stable ranks (rank_i = #{v_j < v_i} + index tie-break) are a
# permutation of 0..n−1 per coordinate, so "keep ranks in [lo, hi)" selects
# exactly hi−lo values — O(n²·B) compares per tile, no data movement.
# ---------------------------------------------------------------------------


def _trimmed_rows(b_ref, lo, hi):
    """In-kernel trimmed mean of the (n, R, B) worker values → (R, B) f32,
    kept values summed in worker order."""
    n = b_ref.shape[0]
    acc = jnp.zeros(b_ref.shape[1:], jnp.float32)
    for i in range(n):
        vi = b_ref[i].astype(jnp.float32)

        def rank_body(j, rank):
            vj = b_ref[j].astype(jnp.float32)
            tie = (vj == vi) & (j < i)
            return rank + (vj < vi).astype(jnp.int32) + tie.astype(jnp.int32)

        rank = jax.lax.fori_loop(0, n, rank_body, jnp.zeros(vi.shape, jnp.int32))
        # select, don't multiply: 0·NaN is NaN and trimming must drop
        # non-finite payload rows (they rank 0 — see the ref docstring)
        acc = acc + jnp.where((rank >= lo) & (rank < hi), vi, 0.0)
    return acc / (hi - lo)


def _trimmed_delta_kernel(
    b_ref, g_ref, x_ref, gout_ref, xout_ref, *, lo, hi, gamma
):
    g_new = g_ref[...].astype(jnp.float32) + _trimmed_rows(b_ref, lo, hi)
    _finish(g_new, x_ref, gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def trimmed_delta_epilogue(bufs, g2d, x2d, gamma: float, lo: int, hi: int, *,
                           backend: str = "auto"):
    """Robust compressed-round epilogue: per-worker dense payload rows
    (n, nblk, B) + g + x → (g' = g + trimmed mean, x' = x − γ·g') in one
    sweep. ``(lo, hi)`` is the rank keep-window: (f, n−f) for the f-trimmed
    mean; the median bounds make the same kernel the coordinate-wise median."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.trimmed_delta_epilogue_ref(bufs, g2d, x2d, float(gamma),
                                               lo, hi)
    return _call(
        functools.partial(
            _trimmed_delta_kernel, lo=int(lo), hi=int(hi), gamma=float(gamma),
        ),
        [bufs, g2d], x2d, backend, "trimmed_delta_epilogue",
    )


def _trimmed_sync_kernel(b_ref, x_ref, gout_ref, xout_ref, *, lo, hi, gamma):
    _finish(_trimmed_rows(b_ref, lo, hi), x_ref, gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def trimmed_sync_epilogue(bufs, x2d, gamma: float, lo: int, hi: int, *,
                          backend: str = "auto"):
    """Robust sync-round epilogue: (n, nblk, B) packed worker gradients + x →
    (g' = trimmed mean over workers, x' = x − γ·g') — ``mean_epilogue`` with
    the worker mean replaced by the rank-window trimmed mean."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.trimmed_sync_epilogue_ref(bufs, x2d, float(gamma), lo, hi)
    return _call(
        functools.partial(
            _trimmed_sync_kernel, lo=int(lo), hi=int(hi), gamma=float(gamma),
        ),
        [bufs], x2d, backend, "trimmed_sync_epilogue",
    )


# ---------------------------------------------------------------------------
# Payload-consuming epilogues (the wire formats of DESIGN.md §4.2/§4.6)
# ---------------------------------------------------------------------------


def _scatter_epilogue_kernel(
    vals_ref, off_ref, g_ref, x_ref, gout_ref, xout_ref, *, gamma
):
    R, B = g_ref.shape
    n = vals_ref.shape[0]
    acc = scatter_rows(vals_ref, off_ref, R, B)
    _finish(g_ref[...].astype(jnp.float32) + acc / n, x_ref, gout_ref,
            xout_ref, gamma)


@stage("flat.epilogue")
def scatter_epilogue(values, offsets, g2d, x2d, gamma: float, *,
                     backend: str = "auto"):
    """Seeded-RandK epilogue: payloads (n, nblk, kb) ×2 + g + x → (g', x').
    The scatter-accumulate and the g/x update share one grid sweep;
    per-worker dense trees are never materialized."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.scatter_epilogue_ref(values, offsets, g2d, x2d,
                                         float(gamma))
    return _call(
        functools.partial(_scatter_epilogue_kernel, gamma=float(gamma)),
        [values.astype(jnp.float32), offsets.astype(jnp.int32), g2d], x2d,
        backend, "scatter_epilogue",
    )


def _qsgd_epilogue_kernel(
    q_ref, norm_ref, g_ref, x_ref, gout_ref, xout_ref, *, s, gamma
):
    acc = qsgd_rows(q_ref, norm_ref, s)
    _finish(g_ref[...].astype(jnp.float32) + acc / q_ref.shape[0], x_ref,
            gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def qsgd_epilogue(levels, norms, g2d, x2d, gamma: float, s: int, *,
                  backend: str = "auto"):
    """Packed block-QSGD epilogue: (n, nblk, B) int8 levels + (n, nblk) f32
    norms + g + x → (g', x'). Same worker-indexed accumulation as
    ``qsgd_dequant_mean`` — input bandwidth stays int8 — fused with the
    estimator/iterate update."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.qsgd_epilogue_ref(levels, norms, g2d, x2d, float(gamma),
                                      s)
    return _call(
        functools.partial(_qsgd_epilogue_kernel, s=int(s), gamma=float(gamma)),
        [levels, per_block(norms), g2d], x2d, backend, "qsgd_epilogue",
    )


def _natural_epilogue_kernel(
    code_ref, scale_ref, g_ref, x_ref, gout_ref, xout_ref, *, gamma
):
    acc = natural_rows(code_ref, scale_ref)
    _finish(g_ref[...].astype(jnp.float32) + acc / code_ref.shape[0], x_ref,
            gout_ref, xout_ref, gamma)


@stage("flat.epilogue")
def natural_epilogue(codes, scales, g2d, x2d, gamma: float, *,
                     backend: str = "auto"):
    """Natural-compression epilogue: (n, nblk, B) int8 codes + (n, nblk) f32
    scales + g + x → (g', x'), decode-and-mean fused with the update."""
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.natural_epilogue_ref(codes, scales, g2d, x2d,
                                         float(gamma))
    return _call(
        functools.partial(_natural_epilogue_kernel, gamma=float(gamma)),
        [codes, per_block(scales), g2d], x2d, backend, "natural_epilogue",
    )
