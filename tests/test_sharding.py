"""Mesh/sharding tests. The device-count flag must be set before jax
initializes, so the sharded-execution tests run in a subprocess with 8 fake
CPU devices; rule-level tests run in-process (pure metadata, no devices)."""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.launch.sharding import _fit, M, F
from jax.sharding import PartitionSpec as P


class FakeMesh:
    """Duck-typed mesh exposing .shape mapping for rule tests."""

    def __init__(self, **axes):
        self.shape = axes


def test_fit_divisibility_fallback():
    mesh = FakeMesh(data=16, model=16)
    # divisible: both axes land
    assert _fit((F, M), (1024, 4096), mesh, True) == P("data", "model")
    # fsdp off: data axis dropped
    assert _fit((F, M), (1024, 4096), mesh, False) == P(None, "model")
    # non-divisible model dim: dropped
    assert _fit((F, M), (1024, 10), mesh, True) == P("data", None)
    # leading (scan) dims replicate
    assert _fit((M, F, None), (58, 256, 7168, 2048), mesh, True) == P(
        None, "model", "data", None
    )


@pytest.mark.slow
def test_param_rules_cover_all_archs():
    """Every leaf of every full config gets a spec without error, and large
    2D+ leaves are sharded on at least one axis. Slow: eval_shape traces all
    ten full-depth configs (~60 layers each)."""
    from repro.configs import all_archs
    from repro.launch.sharding import param_spec
    from repro.models import init_params

    mesh = FakeMesh(data=16, model=16)
    for name, arch in all_archs().items():
        shapes = jax.eval_shape(
            lambda k: init_params(k, arch.model), jax.random.PRNGKey(0)
        )
        flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
        unsharded_big = []
        for path, leaf in flat:
            spec = param_spec(path, leaf, mesh, arch.fsdp)
            assert isinstance(spec, P)
            if leaf.size > 4e6 and all(s is None for s in spec):
                unsharded_big.append((path, leaf.shape))
        assert not unsharded_big, f"{name}: large replicated leaves {unsharded_big[:3]}"


_SUBPROCESS_PROG = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_arch
    from repro.launch.distributed import build_train_steps
    from repro.launch.topology import make_test_mesh
    from repro.models import reduced, init_params, lm_loss
    import dataclasses

    assert jax.device_count() == 8
    mesh = make_test_mesh(4, 2)

    arch = get_arch("qwen1.5-0.5b")
    arch = dataclasses.replace(arch, model=reduced(arch.model, layers=2, d_model=64))
    bundle = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32,
    )
    assert bundle.n_workers == 4

    # run for real on the 8 fake devices: numerical equivalence with the
    # unsharded reference step
    cfg = arch.model
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g0 = jax.tree.map(jnp.zeros_like, params)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks}

    # reference first: sync_step donates its params argument
    grads = jax.vmap(jax.grad(lambda p, t: lm_loss(p, cfg, t)), in_axes=(None, 0))(
        params, toks
    )
    g_ref = jax.tree.map(lambda t: jnp.mean(t, 0), grads)
    params_copy = jax.tree.map(jnp.array, params)

    with bundle.mesh:
        fn, _ = bundle.fns["sync_step"]
        x_new, g_new = fn(params_copy, g0, batch)
    err = max(
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_ref))
    )
    assert err < 2e-4, f"sharded sync_step grad mismatch: {err}"

    # compressed step: support/scaling invariants of Block-RandK
    params2 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params2)
    g_keep = jax.tree.map(jnp.array, g_init)
    with bundle.mesh:
        fn, _ = bundle.fns["compressed_step"]
        x2, g2 = fn(params2, g_init, batch, jax.random.PRNGKey(2))
    delta = [a - b for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g_keep))]
    nz = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta)
    tot = sum(int(t.size) for t in delta)
    frac = nz / tot
    assert 0.0005 < frac < 0.3, f"RandK support fraction {frac}"

    # Perm-K disjoint-shard round: the shared permutation partitions every
    # n-divisible lane dimension, so the decompressed delta is DENSE wherever
    # the gradient diff is — support must be far above the n*K randk round.
    bundle_pk = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32, compression="permk",
    )
    params3 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init3 = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params3)
    g_keep3 = jax.tree.map(jnp.array, g_init3)
    with bundle_pk.mesh:
        fn, _ = bundle_pk.fns["compressed_step"]
        x3, g3 = fn(params3, g_init3, batch, jax.random.PRNGKey(2))
    delta3 = [a - b for a, b in zip(jax.tree.leaves(g3), jax.tree.leaves(g_keep3))]
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in delta3)
    nz3 = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta3)
    frac3 = nz3 / tot
    assert frac3 > 2 * frac, f"PermK support {frac3} not denser than RandK {frac}"

    # packed quantization wire (DESIGN.md 4.6): dense 4-bit QSGD round on the
    # sharded mesh — int8/uint32 payload collectives, dense finite delta.
    bundle_q = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32, compression="qsgd", qsgd_s=7,
        packed_payload=True,
    )
    params4 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init4 = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params4)
    g_keep4 = jax.tree.map(jnp.array, g_init4)
    with bundle_q.mesh:
        fn, _ = bundle_q.fns["compressed_step"]
        x4, g4 = fn(params4, g_init4, batch, jax.random.PRNGKey(2))
    delta4 = [a - b for a, b in zip(jax.tree.leaves(g4), jax.tree.leaves(g_keep4))]
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in delta4)
    nz4 = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta4)
    frac4 = nz4 / tot
    assert frac4 > 2 * frac, f"QSGD support {frac4} not denser than RandK {frac}"

    # grad-carry + compressed downlink (DESIGN.md 4.7): the step carry grows
    # the per-worker h (worker-sharded like the grads, donated) and the round
    # runs ONE backprop; the downlink quantizes the aggregated delta. The
    # sync_step above already exercises the packed flat-psum exchange
    # (flat_sync is the default).
    bundle_cd = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32, grad_carry=True, downlink="qsgd",
        downlink_s=7,
    )
    params5 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init5 = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params5)
    g_keep5 = jax.tree.map(jnp.array, g_init5)
    h0 = jax.tree.map(lambda t: jnp.zeros((4, *t.shape), t.dtype), params5)
    with bundle_cd.mesh:
        fn, _ = bundle_cd.fns["compressed_step"]
        x5, g5, h5 = fn(params5, g_init5, h0, batch, jax.random.PRNGKey(2))
    delta5 = [a - b for a, b in zip(jax.tree.leaves(g5), jax.tree.leaves(g_keep5))]
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in delta5)
    nz5 = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta5)
    assert nz5 > 0, "carry+downlink round produced an empty delta"
    for t in jax.tree.leaves(h5):
        assert t.shape[0] == 4 and bool(jnp.all(jnp.isfinite(t)))

    # PP-MARINA round on the model-sharded mesh (DESIGN.md 4.8): tensor
    # parallelism disqualifies the flat-PP pipeline, so this exercises the
    # per-leaf cohort fallback. With grad_carry the h slot is the
    # server-side carry table: exactly the sampled rows refresh.
    bundle_pp = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32, grad_carry=True,
        participation=(2, "without"),
    )
    assert bundle_pp.meta["participation"] == (2, "without")
    assert not bundle_pp.meta["flat_pp"]          # model axis is sharded
    assert bundle_pp.meta["cohort_compute"]       # 2·2 batch rows over 4 shards
    params6 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init6 = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params6)
    g_keep6 = jax.tree.map(jnp.array, g_init6)
    h06 = jax.tree.map(lambda t: jnp.zeros((4, *t.shape), t.dtype), params6)
    sel = jnp.array([1, 3], jnp.int32)
    with bundle_pp.mesh:
        fn, _ = bundle_pp.fns["compressed_step"]
        x6, g6, h6 = fn(params6, g_init6, h06, batch, jax.random.PRNGKey(2), sel)
    delta6 = [a - b for a, b in zip(jax.tree.leaves(g6), jax.tree.leaves(g_keep6))]
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in delta6)
    nz6 = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta6)
    assert nz6 > 0, "PP round produced an empty delta"
    # the carry table refreshed EXACTLY the sampled rows
    for t in jax.tree.leaves(h6):
        row_nz = jnp.array([bool(jnp.any(jnp.abs(t[i]) > 0)) for i in range(4)])
        assert bool(row_nz[1]) and bool(row_nz[3]), "sampled rows not refreshed"
        assert not bool(row_nz[0]) and not bool(row_nz[2]), (
            "unsampled carry rows must stay stale"
        )
    # Byzantine-robust round (DESIGN.md 4.9): trimmed-mean GAR over the
    # per-worker decoded payload rows with one NaN-payload client — the
    # delta must stay finite (a plain mean would be NaN everywhere) and
    # dense like the honest qsgd wire.
    from repro.core import ServerAggregator, FaultSpec
    bundle_rb = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=8, seq_len=64,
        gamma=0.1, dtype=jnp.float32, compression="qsgd", qsgd_s=7,
        aggregator=ServerAggregator("trimmed_mean", f=1),
        faults=FaultSpec("nan", frac=0.25),
    )
    assert bundle_rb.meta["aggregator"] == "trimmed_mean"
    assert bundle_rb.meta["faults"] == "nan"
    params7 = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    g_init7 = jax.tree.map(lambda t: jnp.full_like(t, 0.01), params7)
    g_keep7 = jax.tree.map(jnp.array, g_init7)
    with bundle_rb.mesh:
        fn, _ = bundle_rb.fns["compressed_step"]
        x7, g7 = fn(params7, g_init7, batch, jax.random.PRNGKey(2))
    delta7 = [a - b for a, b in zip(jax.tree.leaves(g7), jax.tree.leaves(g_keep7))]
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in delta7), (
        "robust round leaked the NaN payload into the estimator"
    )
    nz7 = sum(int(jnp.sum(jnp.abs(t) > 1e-12)) for t in delta7)
    frac7 = nz7 / tot
    assert frac7 > 2 * frac, f"robust qsgd delta {frac7} not dense"

    print("SUBPROCESS_OK", err, frac, frac3, frac4, nz5 / tot, nz6 / tot, frac7)
    """
)


def test_sharded_steps_execute_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"  # fake CPU devices by design
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    assert "SUBPROCESS_OK" in out.stdout
