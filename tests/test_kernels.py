"""Pallas kernel validation: interpret-mode kernel == pure-jnp oracle (ref.py)
across shape/dtype sweeps, plus statistical checks for the seeded sampler and
end-to-end unbiasedness of the fused compression round trip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.randk import randk_gather, randk_seeded, scatter_accum
from repro.kernels.quantize import block_sumsq, qsgd_dequantize, qsgd_quantize

SHAPES = [(1, 128), (2, 256), (4, 1024), (3, 384)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("nblk,B", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES, ids=str)
def test_randk_gather_matches_ref(nblk, B, dtype):
    kb = max(8, B // 16)
    key = jax.random.PRNGKey(nblk * B)
    x2d = jax.random.normal(key, (nblk, B)).astype(dtype)
    offsets = jax.random.randint(jax.random.fold_in(key, 1), (nblk, kb), 0, B)
    scale = B / kb
    out = randk_gather(x2d, offsets.astype(jnp.int32), scale, interpret=True)
    want = ref.randk_block_compress_ref(x2d, offsets, scale)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=1e-2 if dtype == jnp.bfloat16 else 1e-6,
    )


@pytest.mark.parametrize("n", [1, 4, 7])
@pytest.mark.parametrize("nblk,B", [(1, 128), (3, 256)])
def test_scatter_accum_matches_ref(n, nblk, B):
    kb = B // 8
    key = jax.random.PRNGKey(17 + n)
    values = jax.random.normal(key, (n, nblk, kb), jnp.float32)
    offsets = jax.random.randint(jax.random.fold_in(key, 1), (n, nblk, kb), 0, B)
    out = scatter_accum(values, offsets.astype(jnp.int32), B, interpret=True)
    want = ref.scatter_accum_ref(values, offsets, B)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_scatter_handles_duplicate_indices():
    values = jnp.array([[[1.0, 2.0, 3.0, 4.0, 0.0, 0.0, 0.0, 0.0]]] * 2)  # (2,1,8)
    offsets = jnp.zeros((2, 1, 8), jnp.int32)  # all collide on index 0
    out = scatter_accum(values, offsets, 128, interpret=True)
    want = ref.scatter_accum_ref(values, offsets, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6)
    assert float(out[0, 0]) == pytest.approx(10.0)


@pytest.mark.parametrize("nblk,B", SHAPES)
@pytest.mark.parametrize("s", [1, 4, 15])
def test_qsgd_quantize_matches_ref(nblk, B, s):
    key = jax.random.PRNGKey(B + s)
    x2d = jax.random.normal(key, (nblk, B), jnp.float32) * 3
    u2d = jax.random.uniform(jax.random.fold_in(key, 1), (nblk, B))
    norm = jnp.linalg.norm(x2d)
    q = qsgd_quantize(x2d, u2d, norm, s, backend="pallas_interpret")
    want = ref.qsgd_quantize_ref(x2d, u2d, norm, s)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(want))
    deq = qsgd_dequantize(q, norm, s, backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(deq), np.asarray(ref.qsgd_dequantize_ref(want, norm, s)), rtol=1e-6
    )


@pytest.mark.parametrize("nblk,B", SHAPES)
def test_block_sumsq_matches_ref(nblk, B):
    x2d = jax.random.normal(jax.random.PRNGKey(0), (nblk, B), jnp.float32)
    out = block_sumsq(x2d, backend="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.block_sumsq_ref(x2d)), rtol=1e-5
    )


@settings(max_examples=15, deadline=None)
@given(
    d=st.integers(min_value=10, max_value=3000),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_randk_roundtrip_unbiased_support(d, seed):
    """ops-level wrapper: padding + jittered offsets + gather + scatter."""
    block = 256
    kb = 32
    x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
    vals, offs = ops.randk_compress(x, jax.random.PRNGKey(seed + 1), kb, block=block)
    dense = ops.randk_decompress_mean(vals[None], offs[None], d, block=block)
    assert dense.shape == (d,)
    # every nonzero equals x * block/kb at its coordinate
    nz = np.nonzero(np.asarray(dense))[0]
    np.testing.assert_allclose(
        np.asarray(dense)[nz], np.asarray(x)[nz] * block / kb, rtol=1e-4
    )


def test_randk_roundtrip_is_unbiased_mc():
    d, block, kb = 500, 128, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (d,))

    def rt(key):
        vals, offs = ops.randk_compress(x, key, kb, block=block)
        return ops.randk_decompress_mean(vals[None], offs[None], d, block=block)

    keys = jax.random.split(jax.random.PRNGKey(1), 2000)
    mean = jnp.mean(jax.vmap(rt)(keys), axis=0)
    # E||mean - x||^2 = omega ||x||^2 / trials with omega = block/kb - 1 = 7
    rel = float(jnp.linalg.norm(mean - x) / jnp.linalg.norm(x))
    assert rel < 2.0 * np.sqrt(7 / 2000)  # 2x the expected MC error


@pytest.mark.parametrize("nblk,B,kb", [(1, 128, 16), (2, 256, 32), (3, 512, 8)])
def test_seeded_sampler_matches_ref_exactly(nblk, B, kb):
    """In-kernel counter-based RNG is bit-exact vs the pure-jnp oracle."""
    x2d = jax.random.normal(jax.random.PRNGKey(0), (nblk, B))
    scale = B / kb
    vals, offs = randk_seeded(x2d, jnp.int32(7), kb, scale, interpret=True)
    want_v, want_o = ref.randk_seeded_ref(x2d, jnp.uint32(7), kb, scale)
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(want_o))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v), rtol=1e-6)


def test_seeded_sampler_statistics():
    """Production in-kernel PRNG path: unbiased in expectation over seeds."""
    nblk, B, kb = 2, 256, 32
    x2d = jax.random.normal(jax.random.PRNGKey(0), (nblk, B))
    scale = B / kb

    def rt(seed):
        vals, offs = ref.randk_seeded_ref(x2d, seed, kb, scale)
        return ref.scatter_accum_ref(vals[None], offs[None], B)

    seeds = jnp.arange(4000, dtype=jnp.uint32) * jnp.uint32(2654435761)
    mean = jnp.mean(jax.vmap(rt)(seeds), axis=0)
    rel = float(jnp.linalg.norm(mean - x2d) / jnp.linalg.norm(x2d))
    assert rel < 2.0 * np.sqrt((B / kb) / 4000)


def test_qsgd_ops_roundtrip_unbiased():
    d, s = 700, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (d,))

    def rt(key):
        q, norm = ops.qsgd_compress(x, key, s, block=256)
        return ops.qsgd_decompress(q, norm, s, d, block=256)

    keys = jax.random.split(jax.random.PRNGKey(1), 1000)
    mean = jnp.mean(jax.vmap(rt)(keys), axis=0)
    omega = min(d / s**2, np.sqrt(d) / s)
    rel = float(jnp.linalg.norm(mean - x) / jnp.linalg.norm(x))
    assert rel < 2.0 * np.sqrt(omega / 1000)


# ---------------------------------------------------------------------------
# Row tiles: a buffer whose block count is not a multiple of the row tile
# ---------------------------------------------------------------------------

TILE_N, TILE_NBLK, TILE_B = 2, 300, 1024


def _tile_case(kernel):
    from repro.kernels import epilogue as epi
    from repro.kernels import quantize as qz
    from repro.kernels.permk import permk_seeded_workers
    from repro.kernels.randk import randk_seeded_workers

    n, nblk, B = TILE_N, TILE_NBLK, TILE_B
    k = jax.random.PRNGKey(41)
    x3d = jax.random.normal(k, (n, nblk, B))
    g, x = x3d[0], x3d[1] * 0.5
    seeds = jnp.array([5, 1234567], jnp.uint32)
    levels, norms = ref.qsgd_block_workers_ref(x3d, seeds, 7)
    vals, offs = ref.randk_seeded_workers_ref(x3d, seeds, 8, B / 8)
    P = dict(backend="pallas_interpret")
    return {
        "randk": (lambda: randk_seeded_workers(x3d, seeds, 8, B / 8,
                                               interpret=True),
                  lambda: ref.randk_seeded_workers_ref(x3d, seeds, 8, B / 8)),
        "permk": (lambda: permk_seeded_workers(x3d, jnp.uint32(77),
                                               interpret=True),
                  lambda: ref.permk_seeded_workers_ref(x3d, jnp.uint32(77), n)),
        "qsgd": (lambda: qz.qsgd_block_workers(x3d, seeds, 7, **P),
                 lambda: (levels, norms)),
        "natural": (lambda: qz.natural_block_workers(x3d, seeds, **P),
                    lambda: ref.natural_block_workers_ref(x3d, seeds)),
        "nibble": (lambda: (qz.nibble_pack(levels.reshape(-1, B), **P),),
                   lambda: (ref.nibble_pack_ref(levels.reshape(-1, B)),)),
        "scatter_epilogue": (
            lambda: epi.scatter_epilogue(vals, offs, g, x, 0.1, **P),
            lambda: epi.scatter_epilogue(vals, offs, g, x, 0.1, backend="ref")),
        "qsgd_epilogue": (
            lambda: epi.qsgd_epilogue(levels, norms, g, x, 0.1, 7, **P),
            lambda: epi.qsgd_epilogue(levels, norms, g, x, 0.1, 7,
                                      backend="ref")),
    }[kernel]


@pytest.mark.parametrize("kernel", ["randk", "permk", "qsgd", "natural",
                                    "nibble", "scatter_epilogue",
                                    "qsgd_epilogue"])
def test_row_tiles_cover_a_partial_last_tile(kernel):
    """Kernel == oracle on every block row when the row tile does not divide
    nblk: the last tile runs past the buffer, and its out-of-range rows must
    neither leak into real rows nor be lost from them."""
    from repro.kernels.tiling import lane_bytes, row_tile

    R = row_tile(TILE_NBLK, lane_bytes(TILE_B, 4) + 2 * lane_bytes(8, 4))
    assert TILE_NBLK > R and TILE_NBLK % R, "shape must leave a partial tile"
    got, want = (f() for f in _tile_case(kernel))
    for a, b in zip(got, want):
        if kernel.endswith("epilogue"):
            # 1-ulp FMA-fusion tolerance (DESIGN.md §4.4)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
