"""Flat-buffer compression engine validation (DESIGN.md §4).

* pack → unpack is the identity on ragged/odd-shaped pytrees (incl. scalars,
  0-d leaves, mixed dtypes);
* the fused RandK path is unbiased: E[Q(x)] ≈ x over many seeds;
* the jnp ref backend and the interpreted Pallas backend agree bit-exactly;
* the fused scatter-accumulate aggregation equals the unfused
  decompress-every-worker-then-average reference;
* MARINA trajectories are identical (same seeds, float tolerance) between the
  old per-leaf tree path and the new flat path when the two samplers coincide
  (single-leaf, block-aligned problem).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BlockRandK, Marina, make_engine
from repro.core.flat import (
    FlatEngine,
    block_scatter_mean,
    key_to_seed,
    make_layout,
    pack,
    pack_stacked,
    resolve_backend,
    seeded_offsets,
    unpack,
)
from repro.core.problems import make_synthetic_binclass, nonconvex_binclass_loss
from repro.kernels import ref

RAGGED_TREES = [
    {"w": jnp.arange(24.0).reshape(4, 6), "b": jnp.arange(5.0)},
    {
        "a": jnp.ones((3, 3, 3)),
        "nested": {"s": jnp.float32(2.5), "v": jnp.arange(7.0)},
        "bf16": jnp.ones((2, 129), jnp.bfloat16),
    },
    [jnp.zeros((1,)), jnp.arange(1000.0), jnp.ones((13, 17))],
]


@pytest.mark.parametrize("tree", RAGGED_TREES, ids=["small", "mixed", "list"])
@pytest.mark.parametrize("block", [128, 1024])
def test_pack_unpack_roundtrip_identity(tree, block):
    layout = make_layout(tree, block=block)
    buf = pack(layout, tree)
    assert buf.shape == (layout.rows, block)
    assert layout.nblk == -(-layout.d // block) <= layout.rows
    out = unpack(layout, buf)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32)
        )


def test_pack_pads_with_zeros():
    tree = {"v": jnp.ones((5,))}
    layout = make_layout(tree, block=128)
    flat = pack(layout, tree).reshape(-1)
    assert layout.d == 5 and layout.padded == 128
    np.testing.assert_array_equal(np.asarray(flat[5:]), 0.0)


def test_pack_stacked_worker_axis():
    tree = {"w": jnp.ones((4, 6)), "b": jnp.zeros((5,))}
    stacked = jax.tree.map(lambda x: jnp.stack([x, 2 * x, 3 * x]), tree)
    layout = make_layout(tree, block=128)
    bufs = pack_stacked(layout, stacked)
    assert bufs.shape == (3, layout.rows, 128)
    np.testing.assert_allclose(np.asarray(bufs[2]), 3 * np.asarray(bufs[0]))


# Leaf shapes in widths of B. Keys sort in flatten order.
ORACLE_TREES = {
    # qwen-shaped: every leaf whole blocks, a layer-stacked 3-D leaf and a
    # leaf whose minor dimension is 2.75·B
    "aligned": lambda B: {"a_embed": ((6, B), jnp.float32),
                          "b_norm": ((B,), jnp.float32),
                          "c_stack": ((2, 3, B), jnp.float32),
                          "d_gate": ((4, 11 * B // 4), jnp.float32)},
    "misaligned": lambda B: {"a": ((3, 5), jnp.float32),
                             "b": ((7,), jnp.float32),
                             "c": ((B + 3,), jnp.float32)},
    # musicgen-shaped: an aligned leaf, a 1.5·B norm, then whole-block
    # sizes left off the block boundary by it: one flat run
    "mixed": lambda B: {"a_embed": ((4, B), jnp.float32),
                        "b_norm": ((3 * B // 2,), jnp.float32),
                        "c_w": ((2, B), jnp.float32),
                        "d_w": ((3, B), jnp.float32)},
    # whole blocks but for a last odd-sized leaf: one flat run as well
    "odd_tail": lambda B: {"a": ((6, B), jnp.float32),
                           "b": ((B,), jnp.float32),
                           "c": ((3,), jnp.float32)},
    "bf16": lambda B: {"a": ((2, B), jnp.bfloat16),
                       "b": ((5,), jnp.float32),
                       "c": ((B - 5,), jnp.bfloat16)},
    "scalar": lambda B: {"a": ((B,), jnp.float32),
                         "b": ((), jnp.float32),
                         "c": ((2, B), jnp.float32)},
}


def _oracle_tree(name, B, lead):
    spec = ORACLE_TREES[name](B)
    keys = jax.random.split(jax.random.PRNGKey(7), len(spec))
    return {k: jax.random.normal(key, (*lead, *shape)).astype(dt)
            for key, (k, (shape, dt)) in zip(keys, sorted(spec.items()))}


def _oracle_buffer(layout, leaves):
    """NumPy: flatten and concatenate in order, zero pad, cut into rows."""
    flat = np.concatenate([np.asarray(l, np.float32).reshape(-1) for l in leaves])
    flat = np.pad(flat, (0, layout.rows * layout.block - flat.size))
    return flat.reshape(layout.rows, layout.block)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


@pytest.mark.parametrize("name", sorted(ORACLE_TREES))
@pytest.mark.parametrize("block", [128, 1024])
@pytest.mark.parametrize("lead", [(), (3,)], ids=["pack", "stacked"])
def test_pack_unpack_match_numpy_oracle(name, block, lead):
    """``pack`` (lead ()) and ``pack_stacked`` (lead (3,)) give the NumPy
    concatenate-and-pad buffer bit for bit, whatever the split into
    aligned and flat runs, and ``unpack`` gives each tree back."""
    tree = _oracle_tree(name, block, lead)
    layout = make_layout(_oracle_tree(name, block, ()), block=block)
    buf = pack(layout, tree) if not lead else pack_stacked(layout, tree)
    assert buf.shape == (*lead, layout.rows, block) and buf.dtype == jnp.float32
    for w in range(lead[0] if lead else 1):
        at = (lambda t: t[w]) if lead else (lambda t: t)
        want = _oracle_buffer(layout, [at(l) for l in jax.tree.leaves(tree)])
        np.testing.assert_array_equal(_bits(at(buf)), _bits(want))
        out = unpack(layout, at(buf))
        assert jax.tree.structure(out) == jax.tree.structure(tree)
        for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
            assert a.shape == b.shape[len(lead):] and a.dtype == b.dtype
            np.testing.assert_array_equal(_bits(a), _bits(at(b)))


@pytest.mark.parametrize("name, share, runs", [
    ("aligned", 1.0, [(0, 6, (0,), True), (6, 1, (1,), True),
                      (7, 6, (2,), True), (13, 11, (3,), True)]),
    ("mixed", 0.0, [(0, 32, (0, 1, 2, 3), False)]),
    ("misaligned", 0.0, [(0, 32, (0, 1, 2), False)]),
    ("odd_tail", 0.0, [(0, 32, (0, 1, 2), False)]),
])
def test_layout_runs_and_row_share(name, share, runs):
    """Where every leaf is whole blocks, each is a run of its own rows and
    ``row_share`` is 1; one leaf off a block (the first, a norm after an
    aligned leaf, or the last) makes one flat run of the whole tree and
    every row, and the share is 0."""
    B = 128
    layout = make_layout(_oracle_tree(name, B, ()), block=B)
    got = [(r.row, r.nrows, r.slots, r.aligned) for r in layout.runs]
    assert got == runs
    assert layout.rows == 32
    assert sum(r.nrows for r in layout.runs) == (
        layout.nblk if share else layout.rows)
    assert layout.row_share == share


def test_seeded_offsets_match_kernel_rng():
    """Server-side index regeneration is bit-exact vs the kernel sampler."""
    x2d = jax.random.normal(jax.random.PRNGKey(0), (3, 256))
    _, offs = ref.randk_seeded_ref(x2d, jnp.uint32(99), 16, 16.0)
    regen = seeded_offsets(jnp.uint32(99), 3, 256, 16)
    np.testing.assert_array_equal(np.asarray(offs), np.asarray(regen))


def test_fused_unbiased_over_keys():
    """E[Q(x)] ≈ x for the full pack→compress→scatter→unpack pipeline."""
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (20, 10)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (30,))}
    eng = make_engine(tree, kb=16, block=128, backend="ref")
    trials = 3000

    def rt(key):
        return eng.roundtrip_worker(key, tree)

    keys = jax.random.split(jax.random.PRNGKey(2), trials)
    qs = jax.vmap(rt)(keys)  # tree with leading trials axis
    mean = jax.tree.map(lambda x: jnp.mean(x, 0), qs)
    # flatten both and compare with MC tolerance: omega = B/kb = 8
    mf = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(mean)])
    xf = jnp.concatenate([jnp.ravel(l) for l in jax.tree.leaves(tree)])
    rel = float(jnp.linalg.norm(mf - xf) / jnp.linalg.norm(xf))
    assert rel < 2.0 * np.sqrt((128 / 16) / trials)


@pytest.mark.parametrize("n", [1, 4])
def test_ref_and_pallas_interpret_bit_exact(n):
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (11, 13)),
            "b": jax.random.normal(jax.random.PRNGKey(4), (200,))}
    diffs = jax.tree.map(lambda x: jnp.stack([x * (i + 1) for i in range(n)]), tree)
    key = jax.random.PRNGKey(5)
    eng_ref = make_engine(tree, kb=8, block=128, backend="ref")
    eng_pal = make_engine(tree, kb=8, block=128, backend="pallas_interpret")
    out_ref = eng_ref.fused_delta(key, diffs, n)
    out_pal = eng_pal.fused_delta(key, diffs, n)
    for a, b in zip(jax.tree.leaves(out_ref), jax.tree.leaves(out_pal)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_equals_unfused_mean():
    """Scatter-accumulate aggregation == densify-every-worker-then-average."""
    n = 5
    tree = {"w": jax.random.normal(jax.random.PRNGKey(6), (9, 31))}
    diffs = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(7), (n, *x.shape)), tree
    )
    eng = make_engine(tree, kb=4, block=128, backend="ref")
    key = jax.random.PRNGKey(8)
    fused = eng.fused_delta(key, diffs, n)

    bufs = pack_stacked(eng.layout, diffs)
    vals, offs = eng.compress_stacked(eng.worker_seeds(key, n), bufs)
    dense = sum(
        ref.scatter_accum_ref(vals[w : w + 1], offs[w : w + 1], 128)
        for w in range(n)
    ) / n
    unfused = unpack(eng.layout, dense)
    for a, b in zip(jax.tree.leaves(fused), jax.tree.leaves(unfused)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_block_randk_compressor_wire_format():
    """BlockRandK payload = values + seed; decompress regenerates indices."""
    comp = BlockRandK(kb=8, block=128)
    x = jax.random.normal(jax.random.PRNGKey(9), (300,))
    pay = comp.compress(jax.random.PRNGKey(10), x)
    assert set(pay) == {"values", "seed"}
    assert pay["values"].shape == (3, 8)  # nblk=ceil(300/128)=3
    y = comp.decompress(pay, 300)
    assert y.shape == x.shape
    # support: every nonzero equals x * block/kb at its coordinate, up to
    # with-replacement duplicate accumulation (integer multiples)
    nz = np.nonzero(np.asarray(y))[0]
    assert len(nz) <= 3 * 8
    ratio = np.asarray(y)[nz] / (np.asarray(x)[nz] * 128 / 8)
    np.testing.assert_allclose(ratio, np.round(ratio), rtol=1e-4)
    # ledger: 32-bit seed + 32 bits per retained value, indices free
    assert comp.payload_bits(300) == 32.0 + 32.0 * 3 * 8


def test_marina_tree_path_equals_flat_path():
    """Same seeds ⇒ identical trajectories between the per-leaf tree path and
    the fused flat path, on a problem where the two samplers coincide
    (single-leaf params, d a multiple of the block)."""
    N, M, D = 4, 32, 256  # D == 2 blocks of 128
    data = make_synthetic_binclass(jax.random.PRNGKey(0), N, M, D)
    comp = BlockRandK(kb=8, block=128)
    grad = jax.grad(nonconvex_binclass_loss)

    m_tree = Marina(grad, comp, gamma=0.05, p=0.3)
    eng = FlatEngine(layout=make_layout(jnp.zeros((D,)), block=128), kb=8,
                     backend="ref")
    m_flat = Marina(grad, comp, gamma=0.05, p=0.3, engine=eng)

    st_t = m_tree.init(jnp.zeros((D,)), data)
    st_f = m_flat.init(jnp.zeros((D,)), data)
    step_t = jax.jit(m_tree.step)
    step_f = jax.jit(m_flat.step)
    saw_compressed = False
    for k in range(25):
        key = jax.random.PRNGKey(k)
        st_t, met_t = step_t(st_t, key, data)
        st_f, met_f = step_f(st_f, key, data)
        saw_compressed |= int(met_t.sync_round) == 0
        np.testing.assert_allclose(
            np.asarray(st_f.params), np.asarray(st_t.params), rtol=1e-5,
            atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(st_f.g), np.asarray(st_t.g), rtol=1e-5, atol=1e-6
        )
    assert saw_compressed  # the equality must cover compressed rounds


def test_engine_payload_bits_and_backend_resolution():
    tree = {"w": jnp.ones((2000,))}
    eng = make_engine(tree, kb=8, block=1024)
    assert eng.layout.nblk == 2
    assert eng.payload_bits() == 32.0 + 32.0 * 2 * 8
    assert resolve_backend("auto") in ("pallas", "ref")
    assert resolve_backend("ref") == "ref"
    with pytest.raises(ValueError):
        resolve_backend("cuda")


@pytest.mark.parametrize("path", ["tree", "flat"])
def test_bf16_params_compressed_round_smoke(path):
    """bf16 params survive full compressed rounds on both the per-leaf tree
    path (QSGD decompresses to f32 — tree_decompress must cast back, or
    Marina.step's lax.cond branches disagree on dtype) and the fused flat
    path (pack/unpack round-trips the leaf dtype)."""
    from repro.core import QSGD
    from repro.core.tree_util import tree_sub

    n = 3
    params = {
        "w": jnp.ones((4, 40), jnp.bfloat16) * 0.5,
        "b": jnp.zeros((10,), jnp.bfloat16),
    }

    def loss(p, batch):
        return sum(
            jnp.sum((a.astype(jnp.float32) - b) ** 2)
            for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(batch))
        )

    batches = jax.tree.map(
        lambda x: jax.random.normal(jax.random.PRNGKey(0), (n, *x.shape)), params
    )
    if path == "tree":
        comp = QSGD(s=4)
        m = Marina(jax.grad(loss), comp, gamma=0.01, p=0.5)
    else:
        comp = BlockRandK(kb=8, block=128)
        eng = make_engine(params, kb=8, block=128, backend="ref")
        m = Marina(jax.grad(loss), comp, gamma=0.01, p=0.5, engine=eng)

    st = m.init(params, batches)
    step = jax.jit(m.step)
    seen = set()
    for k in range(12):
        st, met = step(st, jax.random.PRNGKey(k), batches)
        seen.add(int(met.sync_round))
    assert seen == {0, 1}  # both lax.cond branches actually traced + ran
    for leaf, like in zip(jax.tree.leaves(st.params), jax.tree.leaves(params)):
        assert leaf.dtype == like.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))
    for leaf in jax.tree.leaves(st.g):
        assert leaf.dtype == jnp.bfloat16


def test_scatter_mean_never_materializes_dense_workers():
    """The aggregation jaxpr must not contain an (n, padded) dense
    intermediate — peak memory of the fused path is payload + one
    accumulator (ISSUE acceptance: no n·d scaling)."""
    n, nblk, B, kb = 16, 64, 1024, 8

    def agg(vals, offs):
        return block_scatter_mean(vals, offs, B, backend="ref")

    jaxpr = jax.make_jaxpr(agg)(
        jnp.zeros((n, nblk, kb)), jnp.zeros((n, nblk, kb), jnp.int32)
    )
    d_padded = nblk * B
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            size = int(np.prod(shape)) if shape else 1
            assert size < n * d_padded, (
                f"dense (n·d)-sized intermediate {shape} in fused aggregation"
            )
