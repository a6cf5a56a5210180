"""PP-MARINA federated-scenario tests (Algorithm 4 + DESIGN.md §4.8).

Covers the paths that existed before this PR but were never tested, plus the
new federated extensions:

* with- vs without-replacement cohort estimator unbiasedness (both schemes
  keep the 1/r server scaling unbiased for the mean difference),
* arbitrary client weights: sync rounds aggregate Σ w_i ∇f_i and the
  compressed estimator is unbiased for Σ w_i Δ_i,
* the server-side carry table: at r = n (without replacement) the carry
  estimator coincides with the recompute path step for step,
* PP + engine trajectory equality vs the per-leaf tree path,
* the PP bits ledger books EXACTLY r·ζ_Q (wire.py drift guard),
* Dirichlet(α) partitioner / heterogeneous problem family sanity,
* mesh PP rounds (subprocess, 4 fake devices): cohort-mapped compute (the
  r clients' tokens respread over all n shards, r payload rows on the
  wire) with trajectory equality against the core PPMarina reference —
  the acceptance-criterion test.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BlockRandK,
    FlatEngine,
    PPMarina,
    RandK,
    make_engine,
    make_layout,
    tree_payload_bits,
)
from repro.core import wire
from repro.core.problems import (
    gradient_heterogeneity,
    make_dirichlet_binclass,
    make_shifted_quadratics,
    make_synthetic_binclass,
    nonconvex_binclass_loss,
    quadratic_loss,
)
from repro.data import (
    client_weights_from_counts,
    dirichlet_partition,
    dirichlet_proportions,
)

N, M, D = 6, 32, 24


@pytest.fixture(scope="module")
def problem():
    data = make_synthetic_binclass(jax.random.PRNGKey(0), N, M, D)
    return data, jax.grad(nonconvex_binclass_loss)


# ---------------------------------------------------------------------------
# cohort estimator unbiasedness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("replace", [True, False])
def test_cohort_estimator_unbiased(replace):
    """(1/r)·Σ_{i∈I'} Q(Δ_i) is unbiased for the mean difference under BOTH
    cohort schemes (with replacement = Alg. 4; without = the experiments')."""
    r, n, d = 3, N, 16
    diffs = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    comp = RandK(k=4)

    def est(key):
        _, k_sel, k_q = jax.random.split(key, 3)
        if replace:
            sel = jax.random.randint(k_sel, (r,), 0, n)
        else:
            sel = jax.random.permutation(k_sel, n)[:r]
        qs = jax.vmap(lambda k, v: comp(k, v))(
            jax.random.split(k_q, r), diffs[sel]
        )
        return jnp.mean(qs, axis=0)

    keys = jax.random.split(jax.random.PRNGKey(2), 6000)
    mean_est = jnp.mean(jax.vmap(est)(keys), axis=0)
    err = float(jnp.linalg.norm(mean_est - jnp.mean(diffs, 0)))
    assert err < 0.12, f"cohort estimator biased: {err}"


def test_weighted_cohort_estimator_unbiased():
    """Pre-scaling sampled diffs by n·w_i makes the 1/r cohort mean unbiased
    for the WEIGHTED mean Σ w_i Δ_i (PPMarina's unbalanced-dataset mode)."""
    r, n, d = 3, N, 16
    diffs = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    w = jnp.array([0.3, 0.25, 0.2, 0.1, 0.1, 0.05])
    comp = RandK(k=4)

    def est(key):
        _, k_sel, k_q = jax.random.split(key, 3)
        sel = jax.random.permutation(k_sel, n)[:r]
        scaled = diffs[sel] * (n * w[sel])[:, None]
        qs = jax.vmap(lambda k, v: comp(k, v))(
            jax.random.split(k_q, r), scaled
        )
        return jnp.mean(qs, axis=0)

    keys = jax.random.split(jax.random.PRNGKey(3), 6000)
    mean_est = jnp.mean(jax.vmap(est)(keys), axis=0)
    target = jnp.einsum("n,nd->d", w, diffs)
    err = float(jnp.linalg.norm(mean_est - target))
    assert err < 0.12, f"weighted cohort estimator biased: {err}"


def test_weighted_sync_round_aggregates_with_weights(problem):
    """p = 1 ⇒ every round is a sync round: g^{k+1} must equal Σ w_i ∇f_i."""
    data, grad = problem
    w = jnp.array([0.4, 0.2, 0.15, 0.1, 0.1, 0.05])
    m = PPMarina(grad, RandK(k=3), 0.05, p=1.0, r=2, weights=w)
    st = m.init(jnp.zeros((D,)), data)
    st, met = jax.jit(m.step)(st, jax.random.PRNGKey(0), data)
    grads = jax.vmap(grad, in_axes=(None, 0))(st.params, data)
    # note: step evaluates at x^1 = x^0 - γ·g^0; recompute the same point
    x1 = jnp.zeros((D,)) - 0.05 * jnp.einsum(
        "n,nd->d", w, jax.vmap(grad, in_axes=(None, 0))(jnp.zeros((D,)), data)
    )
    expect = jnp.einsum(
        "n,nd->d", w, jax.vmap(grad, in_axes=(None, 0))(x1, data)
    )
    np.testing.assert_allclose(np.asarray(st.g), np.asarray(expect), atol=1e-6)


# ---------------------------------------------------------------------------
# server-side carry table
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["tree", "flat"])
def test_pp_carry_equals_recompute_at_full_cohort(path):
    """r = n without replacement ⇒ every client refreshes its table row each
    round, so the carry estimator coincides with the recompute path: g^k
    equal, lookahead params lead by exactly one step."""
    n, m, d = 4, 32, 256  # single leaf, 2 blocks of 128 → flat == tree RNG
    data = make_synthetic_binclass(jax.random.PRNGKey(0), n, m, d)
    grad = jax.grad(nonconvex_binclass_loss)
    comp = BlockRandK(kb=8, block=128)
    eng = (
        make_engine(jnp.zeros((d,)), kb=8, block=128, backend="ref")
        if path == "flat" else None
    )
    seed = PPMarina(grad, comp, 0.05, 0.3, r=n, engine=eng, replace=False)
    carry = PPMarina(
        grad, comp, 0.05, 0.3, r=n, engine=eng, replace=False, carry=True
    )

    st = seed.init(jnp.zeros((d,)), data)
    step_s = jax.jit(seed.step)
    params, gs, syncs = [np.asarray(st.params)], [], []
    for k in range(12):
        st, met = step_s(st, jax.random.PRNGKey(k), data)
        params.append(np.asarray(st.params))
        gs.append(np.asarray(st.g))
        syncs.append(int(met.sync_round))
    assert 0 in syncs and 1 in syncs

    st = carry.init(jnp.zeros((d,)), data)
    np.testing.assert_allclose(np.asarray(st.params), params[1], atol=1e-6)
    step_c = jax.jit(carry.step)
    for k in range(11):
        st, met = step_c(st, jax.random.PRNGKey(k), data)
        g = np.asarray(st.g).reshape(-1)[:d]
        np.testing.assert_allclose(g, gs[k], atol=1e-6)
        np.testing.assert_allclose(np.asarray(st.params), params[k + 2],
                                   atol=1e-5)
        if int(met.sync_round) == 0:
            # one backprop per SAMPLED client: r/n of a full sweep
            assert float(met.oracle_calls) == 1.0  # r == n here


def test_pp_carry_refreshes_only_sampled_rows(problem):
    """Compressed carry rounds must update the h table ONLY at the cohort
    rows — unsampled clients' anchors stay stale by design."""
    data, grad = problem
    m = PPMarina(
        grad, RandK(k=3), 0.05, p=0.0, r=2, replace=False, carry=True
    )  # p = 0: every round compressed
    st = m.init(jnp.zeros((D,)), data)
    h0 = np.asarray(st.h)
    key = jax.random.PRNGKey(5)
    st2, _ = jax.jit(m.step)(st, key, data)
    _, k_sel, _ = jax.random.split(key, 3)
    sel = np.asarray(jax.random.permutation(k_sel, N)[:2])
    h1 = np.asarray(st2.h)
    changed = np.array([not np.allclose(h0[i], h1[i]) for i in range(N)])
    assert set(np.flatnonzero(changed)) == set(sel.tolist())


def test_pp_carry_converges(problem):
    """The lazy-anchor carry estimator still drives PP-MARINA to
    stationarity at r < n on the heterogeneous problem."""
    data, grad = problem
    from repro.core import pp_marina_gamma
    from repro.core.problems import binclass_smoothness, BinClassData, \
        binclass_full_grad

    L = binclass_smoothness(data)
    comp = RandK(k=3)
    r = 3
    p = comp.default_p(D) * r / N
    gamma = pp_marina_gamma(L, comp.omega(D), p, r)
    m = PPMarina(grad, comp, gamma, p, r=r, replace=False, carry=True)
    st = m.init(jnp.zeros((D,)), data)
    step = jax.jit(m.step)
    for k in range(900):
        st, _ = step(st, jax.random.PRNGKey(k), data)
    flat = BinClassData(a=data.a.reshape(-1, D), y=data.y.reshape(-1))
    sq = float(jnp.sum(binclass_full_grad(st.params, flat) ** 2))
    assert sq < 5e-3, f"carry PP did not converge: {sq}"


# ---------------------------------------------------------------------------
# engine vs tree trajectory + bits ledger
# ---------------------------------------------------------------------------


def test_pp_engine_equals_tree_path():
    """PP + flat engine reproduces the per-leaf tree path trajectory on a
    single-leaf block-aligned problem (same cohort, same sampler RNG)."""
    n, m, d = 4, 32, 256
    data = make_synthetic_binclass(jax.random.PRNGKey(0), n, m, d)
    grad = jax.grad(nonconvex_binclass_loss)
    comp = BlockRandK(kb=8, block=128)
    eng = FlatEngine(layout=make_layout(jnp.zeros((d,)), block=128), kb=8,
                     backend="ref")
    m_tree = PPMarina(grad, comp, 0.05, 0.3, r=2, replace=False)
    m_flat = PPMarina(grad, comp, 0.05, 0.3, r=2, replace=False, engine=eng)
    st_t = m_tree.init(jnp.zeros((d,)), data)
    st_f = m_flat.init(jnp.zeros((d,)), data)
    step_t, step_f = jax.jit(m_tree.step), jax.jit(m_flat.step)
    saw_compressed = False
    for k in range(20):
        key = jax.random.PRNGKey(k)
        st_t, met = step_t(st_t, key, data)
        st_f, _ = step_f(st_f, key, data)
        saw_compressed |= int(met.sync_round) == 0
        np.testing.assert_allclose(
            np.asarray(st_f.params), np.asarray(st_t.params), rtol=1e-5,
            atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(st_f.g), np.asarray(st_t.g), rtol=1e-5, atol=1e-6
        )
    assert saw_compressed


@pytest.mark.parametrize("path", ["tree", "flat"])
def test_pp_bits_ledger_books_r_zeta(problem, path):
    """Drift guard: the PP ledger must book n·32d on sync rounds and EXACTLY
    r·ζ_Q on compressed rounds (fleet totals / n), matching wire.py."""
    data, grad = problem
    r = 2
    if path == "flat":
        n, m, d = 4, 16, 256
        data = make_synthetic_binclass(jax.random.PRNGKey(1), n, m, d)
        comp = BlockRandK(kb=8, block=128)
        eng = make_engine(jnp.zeros((d,)), kb=8, block=128, backend="ref")
        mth = PPMarina(grad, comp, 0.05, 0.5, r=r, engine=eng, replace=False)
        st = mth.init(jnp.zeros((d,)), data)
        zeta = eng.payload_bits(r)
        nn, dd = n, d
    else:
        comp = RandK(k=3)
        mth = PPMarina(grad, comp, 0.05, 0.5, r=r, replace=False)
        st = mth.init(jnp.zeros((D,)), data)
        zeta = tree_payload_bits(comp, jnp.zeros((D,)))
        nn, dd = N, D
    step = jax.jit(mth.step)
    seen = set()
    for k in range(24):
        st, met = step(st, jax.random.PRNGKey(k), data)
        got = float(met.bits_per_worker) * nn
        if int(met.sync_round) == 1:
            assert got == wire.pp_sync_total_bits(nn, dd)
        else:
            assert got == pytest.approx(wire.pp_uplink_total_bits(r, zeta))
        seen.add(int(met.sync_round))
    assert seen == {0, 1}


def test_pp_without_replacement_converges(problem):
    """Thm 4.1 behaviour survives the without-replacement cohort (variance
    can only drop): PP-MARINA reaches stationarity on the quadratic."""
    data, L, mu = make_shifted_quadratics(
        jax.random.PRNGKey(2), 6, 16, zeta=1.0, kappa=5.0
    )
    from repro.core import pp_marina_gamma

    comp = RandK(k=4)
    r = 2
    p = comp.default_p(16) * r / 6
    gamma = pp_marina_gamma(L, comp.omega(16), p, r)
    m = PPMarina(
        jax.grad(quadratic_loss), comp, gamma, p, r=r, replace=False
    )
    st = m.init(jnp.ones((16,)), data)
    step = jax.jit(m.step)
    for k in range(800):
        st, _ = step(st, jax.random.PRNGKey(k), data)
    g = jax.grad(quadratic_loss)(st.params, jax.tree.map(
        lambda t: jnp.mean(t, 0), data))
    assert float(jnp.sum(g**2)) < 1e-4


# ---------------------------------------------------------------------------
# heterogeneity scenario layer
# ---------------------------------------------------------------------------


def test_shifted_quadratics_zeta_exact():
    """The ζ dial is exact: empirical (1/n)Σ‖∇f_i − ∇f‖² == ζ² at any x."""
    for zeta in (0.5, 2.0):
        data, L, mu = make_shifted_quadratics(
            jax.random.PRNGKey(3), 8, 12, zeta=zeta
        )
        for xseed in (0, 1):
            x = jax.random.normal(jax.random.PRNGKey(xseed), (12,))
            grads = jax.vmap(jax.grad(quadratic_loss), in_axes=(None, 0))(
                x, data
            )
            np.testing.assert_allclose(
                float(gradient_heterogeneity(grads)), zeta**2, rtol=1e-4
            )


def test_dirichlet_proportions_and_partition():
    key = jax.random.PRNGKey(4)
    # α = ∞ → uniform; α small → concentrated rows
    pu = dirichlet_proportions(key, 8, 4, np.inf)
    np.testing.assert_allclose(np.asarray(pu), 0.25)
    ps = np.asarray(dirichlet_proportions(key, 16, 8, 0.1))
    np.testing.assert_allclose(ps.sum(-1), 1.0, atol=1e-5)
    assert ps.max(-1).mean() > 0.6  # skewed clients
    # the partition is a disjoint cover of all indices
    labels = np.repeat(np.arange(5), 40)
    shards = dirichlet_partition(key, labels, 6, 0.5)
    allidx = np.sort(np.concatenate(shards))
    np.testing.assert_array_equal(allidx, np.arange(len(labels)))
    w = client_weights_from_counts([len(s) for s in shards])
    np.testing.assert_allclose(float(jnp.sum(w)), 1.0, rtol=1e-6)


def test_dirichlet_binclass_alpha_controls_heterogeneity():
    """Smaller α ⇒ larger gradient dissimilarity across clients."""
    x = jnp.zeros((10,))
    zs = {}
    for alpha in (0.1, np.inf):
        data = make_dirichlet_binclass(
            jax.random.PRNGKey(5), 16, 64, 10, alpha=alpha
        )
        grads = jax.vmap(
            jax.grad(nonconvex_binclass_loss), in_axes=(None, 0)
        )(x, data)
        zs[alpha] = float(gradient_heterogeneity(grads))
    assert zs[0.1] > 2.0 * zs[np.inf], zs


def test_lm_data_alpha_deterministic_and_skewed():
    from repro.data import make_lm_data, worker_batches

    data = make_lm_data(4, 256, 32, seed=0, alpha=0.1)
    b1 = worker_batches(data, 3, 2)
    b2 = worker_batches(data, 3, 2)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    assert b1.shape == (4, 2, 32)
    # workers concentrate on different vocab regions under small α
    data_iid = make_lm_data(4, 256, 32, seed=0, alpha=np.inf)
    b_iid = worker_batches(data_iid, 3, 2)
    spread = np.asarray(b1).reshape(4, -1).std(axis=1).mean()
    spread_iid = np.asarray(b_iid).reshape(4, -1).std(axis=1).mean()
    assert spread < spread_iid  # skewed streams are narrower per worker


def test_cohort_schedule_matches_core_sampling():
    """pp_cohort_schedule row k == the cohort PPMarina draws from the step
    key fold_in(base, k) — the prefetch cannot drift from the algorithm."""
    from repro.launch.distributed import pp_cohort_schedule

    base = jax.random.PRNGKey(9)
    n, r = 8, 3
    sched = pp_cohort_schedule(base, 12, n, r, "without")
    for k in range(12):
        _, k_sel, _ = jax.random.split(jax.random.fold_in(base, k), 3)
        expect = jax.random.permutation(k_sel, n)[:r]
        np.testing.assert_array_equal(np.asarray(sched[k]), np.asarray(expect))
    sched_w = pp_cohort_schedule(base, 5, n, r, "with")
    assert sched_w.shape == (5, r) and int(sched_w.max()) < n


# ---------------------------------------------------------------------------
# mesh PP rounds: only r of n shards compute/communicate, trajectory-equal
# to the core PPMarina reference (subprocess: fake devices)
# ---------------------------------------------------------------------------

_PP_MESH_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np

    from repro.configs import get_arch
    from repro.launch.distributed import build_train_steps, pp_cohort_schedule
    from repro.launch.topology import make_federated_mesh
    from repro.models import reduced, init_params, lm_loss
    from repro.core import PPMarina, BlockRandK, make_engine
    from repro.core.marina import MarinaState

    mesh = make_federated_mesh(4)
    arch = get_arch("qwen1.5-0.5b")
    arch = dataclasses.replace(arch, model=reduced(arch.model, layers=2, d_model=64))
    cfg = arch.model
    n, r, b = 4, 2, 2
    bundle = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=n*b, seq_len=64,
        gamma=0.1, dtype=jnp.float32, replicate_params=True,
        participation=(r, "without"), p=0.3,
    )
    # only r of n shards compute: the builder took the cohort-mapped path
    assert bundle.meta["cohort_compute"], bundle.meta
    assert bundle.meta["flat_pp"], bundle.meta

    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (n, b, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks}

    # the core reference: same flat sampler, same keys
    grad_fn = jax.grad(lambda p_, t: lm_loss(p_, cfg, t))
    eng = make_engine(params, kb=8, block=1024, backend="ref")
    ref = PPMarina(grad_fn, BlockRandK(kb=8), 0.1, 0.3, r=r, engine=eng,
                   replace=False)
    g0 = jax.tree.map(jnp.zeros_like, params)
    stref = MarinaState(params=params, g=g0, step=jnp.zeros((), jnp.int32))

    base = jax.random.PRNGKey(42)
    sched = pp_cohort_schedule(base, 8, n, r, "without")
    pd = jax.tree.map(jnp.array, params)
    gd = jax.tree.map(jnp.array, g0)
    fn, _ = bundle.fns["train_step"]
    step_ref = jax.jit(ref.step)
    comp_rounds = 0
    with bundle.mesh:
        for k in range(8):
            key = jax.random.fold_in(base, k)
            pd, gd = fn(pd, gd, batch, key, sched[k])
            stref, met = step_ref(stref, key, batch["tokens"])
            comp_rounds += 1 - int(met.sync_round)
            errg = max(float(jnp.max(jnp.abs(a-c))) for a, c in zip(
                jax.tree.leaves(gd), jax.tree.leaves(stref.g)))
            errp = max(float(jnp.max(jnp.abs(a-c))) for a, c in zip(
                jax.tree.leaves(pd), jax.tree.leaves(stref.params)))
            assert errg < 1e-4 and errp < 1e-4, (k, errg, errp)
    assert comp_rounds > 0
    print("PP_MESH_OK", comp_rounds)
    """
)


def test_mesh_pp_round_trajectory_equals_core():
    """Acceptance criterion: a mesh PP round doing r/n of a full round's
    compute with r payload rows on the wire, trajectory-equal (same keys)
    to core PPMarina."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices by design
    out = subprocess.run(
        [sys.executable, "-c", _PP_MESH_PROG],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "PP_MESH_OK" in out.stdout
