"""Tier-1 tests for the straggler-tolerant async path (DESIGN.md §4.10).

Covers the deadline-cohort equivalence contracts at test scale:

* p_miss = 0 ⇒ ``DeadlineMarina`` is BIT-identical to ``Marina(carry=True)``
  (the TIME_FOLD side channel never perturbs the (k_bern, k_q) split);
* a statically-slow set with tau_max = 0 is bit-identical to the same ids
  under ``FaultSpec("drop", ids=...)``;
* stale-difference acceptance: a late upload lands τ rounds later against
  the pinned anchor, refreshes it, and bills on the landing round;
* the wall-clock model, the uploaded·ζ_Q ledger drift guard (core metrics
  AND ``Transport.uplink_mean(uploaded_rows=...)``), the ``RoundTimeModel``
  statistics, the FaultSpec construction-time refusals, the atomic
  BENCH_pp.json read-merge-update, and the launch-layer retry/crash
  helpers (``RetryPolicy``/``retry_call``, heartbeat/env parsing).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    DeadlineMarina,
    FaultSpec,
    Marina,
    RandK,
    RoundTimeModel,
    ServerAggregator,
    async_marina_gamma,
    marina_gamma,
)
from repro.core.problems import (
    make_synthetic_binclass,
    nonconvex_binclass_loss,
)

N, M, D = 5, 48, 20
GRAD = jax.grad(nonconvex_binclass_loss)


@pytest.fixture(scope="module")
def data():
    return make_synthetic_binclass(jax.random.PRNGKey(0), N, M, D)


def run_states(method, data, steps, seed=3):
    st = method.init(jnp.zeros((D,)), data)
    step = jax.jit(method.step)
    states, metrics = [], []
    for k in range(steps):
        st, met = step(st, jax.random.PRNGKey(seed * 100_000 + k), data)
        states.append(st)
        metrics.append(met)
    return states, metrics


def assert_bit_identical(sa, sb):
    for name in ("params", "g"):
        np.testing.assert_array_equal(
            np.asarray(getattr(sa, name)), np.asarray(getattr(sb, name)),
            err_msg=name,
        )


# ---------------------------------------------------------------------------
# equivalence contracts (the scripts/check_async.py gate at test scale)
# ---------------------------------------------------------------------------

def test_never_miss_deadline_bit_identical_to_full_participation(data):
    dm = DeadlineMarina(GRAD, RandK(k=3), 0.05, 0.3, deadline=1e9,
                        times=RoundTimeModel(dist="fixed", mean_s=1.0))
    ref = Marina(GRAD, RandK(k=3), 0.05, 0.3, carry=True)
    sa, ma = run_states(dm, data, 15)
    sb, mb = run_states(ref, data, 15)
    for a, b in zip(sa, sb):
        assert_bit_identical(a, b)
    # identical ledger: every round bills the full fleet on both sides
    assert [float(m.bits_per_worker) for m in ma] == \
        [float(m.bits_per_worker) for m in mb]


def test_static_slow_set_bit_identical_to_drop_fault(data):
    slow = (1, 3)
    dm = DeadlineMarina(
        GRAD, RandK(k=3), 0.05, 0.3, deadline=2.0,
        times=RoundTimeModel(dist="fixed", mean_s=1.0,
                             slow_ids=slow, slow_factor=8.0),
    )
    assert dm.static_miss_faults() == FaultSpec("drop", ids=slow)
    ref = Marina(GRAD, RandK(k=3), 0.05, 0.3, carry=True,
                 faults=FaultSpec("drop", ids=slow))
    sa, ma = run_states(dm, data, 15)
    sb, mb = run_states(ref, data, 15)
    for a, b in zip(sa, sb):
        assert_bit_identical(a, b)
    assert [float(m.bits_per_worker) for m in ma] == \
        [float(m.bits_per_worker) for m in mb]


def test_static_reduction_is_none_when_late_uploads_allowed():
    tm = RoundTimeModel(dist="fixed", slow_ids=(0,), slow_factor=8.0)
    m = DeadlineMarina(GRAD, RandK(k=3), 0.05, 0.3, deadline=2.0,
                       times=tm, tau_max=2)
    assert m.static_miss_faults() is None  # stale uploads DO land
    assert DeadlineMarina(GRAD, RandK(k=3), 0.05, 0.3, deadline=2.0
                          ).static_miss_faults() is None  # no fixed slow set


def test_deadline_validation():
    with pytest.raises(ValueError, match="deadline"):
        DeadlineMarina(GRAD, RandK(k=3), 0.05, 0.3, deadline=0.0)
    with pytest.raises(ValueError, match="tau_max"):
        DeadlineMarina(GRAD, RandK(k=3), 0.05, 0.3, deadline=1.0,
                       tau_max=-1)


# ---------------------------------------------------------------------------
# stale-difference acceptance + the wall-clock model
# ---------------------------------------------------------------------------

def test_late_upload_lands_and_refreshes_anchor(data):
    """Client 0 always takes 3 deadline windows: with tau_max=2 its upload
    lands 2 rounds after it started, refreshes its (pinned) anchor, and
    bills on the landing round; the server pays the deadline whenever
    anybody is late/in flight."""
    tm = RoundTimeModel(dist="fixed", mean_s=1.0, slow_ids=(0,),
                        slow_factor=3.0)
    m = DeadlineMarina(GRAD, RandK(k=3), 0.05, p=1e-9, deadline=1.0,
                       times=tm, tau_max=2)
    states, metrics = run_states(m, data, 6)
    uploaded = [int(mt.uploaded) for mt in metrics]
    # τ = ceil(3/1) − 1 = 2: client 0 starts at k, lands at k+2 — so rounds
    # alternate: miss (n−1), in-flight (n−1), landing (n−1 on-time + 1 late)
    assert uploaded[:6] == [N - 1, N - 1, N, N - 1, N - 1, N]
    # wall clock: the deadline is paid on every round with a miss/in-flight
    assert all(float(mt.wall_clock_s) == 1.0 for mt in metrics)
    # landing round: the late anchor refreshes to the round it was BORN
    # (k=0), so entering round 3 its age is (3−1) − 0 = 2 = tau_max
    assert int(metrics[2].staleness_max) == 2
    # while in flight the anchor tag is pinned at init (−1)
    assert int(states[0].tag[0]) == -1 and int(states[1].tag[0]) == -1
    assert int(states[2].tag[0]) == 0
    assert int(states[2].arrive[0]) == -1  # idle again after landing


def test_all_on_time_round_closes_at_slowest_upload(data):
    tm = RoundTimeModel(dist="fixed", mean_s=0.7)
    m = DeadlineMarina(GRAD, RandK(k=3), 0.05, p=1e-9, deadline=1.0,
                       times=tm)
    _, metrics = run_states(m, data, 3)
    # nobody misses: the round closes at max(T_i) = 0.7, not the deadline
    assert all(float(mt.wall_clock_s) == pytest.approx(0.7)
               for mt in metrics)
    assert all(int(mt.uploaded) == N for mt in metrics)
    assert all(int(mt.staleness_max) == 0 for mt in metrics)


def test_sync_round_is_a_rendezvous(data):
    tm = RoundTimeModel(dist="fixed", mean_s=1.0, slow_ids=(0,),
                        slow_factor=3.0)
    m = DeadlineMarina(GRAD, RandK(k=3), 0.05, p=1.0 - 1e-9, deadline=1.0,
                       times=tm, tau_max=2)
    states, metrics = run_states(m, data, 2)
    for st, mt in zip(states, metrics):
        assert int(mt.sync_round) == 1
        assert int(mt.uploaded) == N
        # every anchor refreshes, nothing stays in flight
        assert np.all(np.asarray(st.arrive) == -1)
        assert float(mt.wall_clock_s) == pytest.approx(3.0)  # slowest client
        assert float(mt.bits_per_worker) == pytest.approx(32.0 * D)


# ---------------------------------------------------------------------------
# ledger drift guards (uploaded·ζ_Q — core metrics and the mesh transport)
# ---------------------------------------------------------------------------

def test_deadline_bits_scale_with_arrivals(data):
    """Compressed-round bits: miss rounds bill (n−f)/n of the full-fleet
    booking, bit-for-bit against the never-miss run (same ζ_Q source)."""
    kw = dict(gamma=0.05, p=1e-9, deadline=2.0)
    full = DeadlineMarina(GRAD, RandK(k=3), times=RoundTimeModel(
        dist="fixed", mean_s=1.0), **kw)
    slow = DeadlineMarina(GRAD, RandK(k=3), times=RoundTimeModel(
        dist="fixed", mean_s=1.0, slow_ids=(0, 2), slow_factor=8.0), **kw)
    _, mf = run_states(full, data, 4)
    _, ms = run_states(slow, data, 4)
    for f, s in zip(mf, ms):
        assert int(f.uploaded) == N and int(s.uploaded) == N - 2
        assert float(s.bits_per_worker) == pytest.approx(
            float(f.bits_per_worker) * (N - 2) / N)


def test_transport_uplink_books_only_uploaded_rows():
    """`Transport.uplink_mean(uploaded_rows=u)` scales every up booking by
    u/n while the collective still carries n (zero-padded) rows."""
    from repro.launch.topology import detect_topology, make_mesh
    from repro.launch.transport import make_transport

    mesh = make_mesh((1,), ("data",))
    topo = detect_topology(mesh)
    diffs = jax.random.normal(jax.random.PRNGKey(0), (4, 256))

    def booked(uploaded_rows):
        t = make_transport(mesh, topo, waxes=("data",), n=4)
        with t.scope("compressed_step"):
            out = t.uplink_mean(jax.random.PRNGKey(1), diffs,
                                uploaded_rows=uploaded_rows)
        assert jax.tree.leaves(out)[0].shape == (256,)
        return t.ledger.total_bits(direction="up")

    full = booked(None)
    assert full > 0.0
    assert booked(4) == pytest.approx(full)
    assert booked(2) == pytest.approx(full * 0.5)
    assert booked(0) == 0.0
    with pytest.raises(ValueError, match="uploaded_rows"):
        booked(5)


# ---------------------------------------------------------------------------
# RoundTimeModel statistics + validation
# ---------------------------------------------------------------------------

def test_roundtime_validation():
    with pytest.raises(ValueError, match="dist"):
        RoundTimeModel(dist="uniform")
    with pytest.raises(ValueError, match="mean_s"):
        RoundTimeModel(mean_s=0.0)
    with pytest.raises(ValueError, match="sigma"):
        RoundTimeModel(sigma=-0.1)
    with pytest.raises(ValueError, match="slow_factor"):
        RoundTimeModel(slow_ids=(0,), slow_factor=0.5)
    with pytest.raises(ValueError, match="duplicates"):
        RoundTimeModel(slow_ids=(1, 1))
    with pytest.raises(ValueError, match="non-negative"):
        RoundTimeModel(slow_ids=(-1,))


def test_roundtime_fixed_dist_and_slow_set():
    tm = RoundTimeModel(dist="fixed", mean_s=2.0, slow_ids=(1,),
                        slow_factor=4.0)
    t = np.asarray(tm.sample(jax.random.PRNGKey(0), 4))
    np.testing.assert_allclose(t, [2.0, 8.0, 2.0, 2.0])
    assert tm.deadline_for_quantile(0.9) == 2.0
    assert tm.miss_prob(2.0) == 0.0 and tm.miss_prob(1.9) == 1.0


@pytest.mark.parametrize("dist", ["lognormal", "exponential"])
def test_roundtime_mean_and_quantile_roundtrip(dist):
    tm = RoundTimeModel(dist=dist, mean_s=1.5, sigma=0.8)
    t = np.asarray(tm.sample(jax.random.PRNGKey(1), 200_000))
    assert np.mean(t) == pytest.approx(1.5, rel=0.05)  # mean-corrected
    for q in (0.5, 0.8, 0.95):
        dl = tm.deadline_for_quantile(q)
        # closed form agrees with itself ...
        assert tm.miss_prob(dl) == pytest.approx(1.0 - q, abs=1e-9)
        # ... and with the sampler
        assert np.mean(t > dl) == pytest.approx(1.0 - q, abs=0.01)
    with pytest.raises(ValueError, match="quantile"):
        tm.deadline_for_quantile(1.0)
    assert tm.miss_prob(0.0) == 1.0


def test_async_gamma_degrades_with_staleness_and_misses():
    base = marina_gamma(1.0, 4.0, 0.25, 8)
    assert async_marina_gamma(1.0, 4.0, 0.25, 8) == pytest.approx(base)
    g_miss = async_marina_gamma(1.0, 4.0, 0.25, 8, arrive_frac=0.5)
    g_stale = async_marina_gamma(1.0, 4.0, 0.25, 8, staleness=2.0)
    assert g_miss < base and g_stale < base
    assert async_marina_gamma(
        1.0, 4.0, 0.25, 8, arrive_frac=0.5, staleness=2.0) < min(
        g_miss, g_stale)
    with pytest.raises(ValueError, match="arrive_frac"):
        async_marina_gamma(1.0, 4.0, 0.25, 8, arrive_frac=1.5)
    with pytest.raises(ValueError, match="staleness"):
        async_marina_gamma(1.0, 4.0, 0.25, 8, staleness=-1.0)


# ---------------------------------------------------------------------------
# FaultSpec construction-time refusals (regression: ISSUE 8 satellite)
# ---------------------------------------------------------------------------

def test_faultspec_ids_validation():
    assert FaultSpec("drop", ids=(3, 1)).ids == (1, 3)  # sorted
    assert FaultSpec("drop", ids=(1, 9)).n_faulty(5) == 1  # id 9 not in fleet
    mask = FaultSpec("drop", ids=(1, 3)).byz_mask(jnp.arange(5), 5)
    assert np.asarray(mask).tolist() == [False, True, False, True, False]
    assert not np.asarray(
        FaultSpec("drop", ids=()).byz_mask(jnp.arange(5), 5)).any()
    with pytest.raises(ValueError, match="non-negative"):
        FaultSpec("drop", ids=(-1,))
    with pytest.raises(ValueError, match="duplicates"):
        FaultSpec("drop", ids=(2, 2))


def test_drop_without_carry_refused():
    with pytest.raises(ValueError, match="carry=True is required"):
        Marina(GRAD, RandK(k=3), 0.05, 0.3,
               faults=FaultSpec("drop", ids=(0,)))


def test_drop_with_robust_gar_refused():
    with pytest.raises(ValueError, match="mean aggregation"):
        Marina(GRAD, RandK(k=3), 0.05, 0.3, carry=True,
               faults=FaultSpec("drop", ids=(0,)),
               aggregator=ServerAggregator("trimmed_mean", f=1))


# ---------------------------------------------------------------------------
# atomic BENCH_pp.json read-merge-update
# ---------------------------------------------------------------------------

def test_write_merged_is_atomic_and_merges(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import bench_pp

    monkeypatch.setattr(bench_pp, "ROOT", str(tmp_path))
    path = tmp_path / "BENCH_pp.json"
    path.write_text(json.dumps({"curves": [1, 2], "robust": {"keep": True}}))
    out = bench_pp._write_merged({"async": {"quick": True}})
    on_disk = json.loads(path.read_text())
    assert on_disk == out
    assert on_disk["curves"] == [1, 2]          # other sections survive
    assert on_disk["robust"] == {"keep": True}
    assert on_disk["async"] == {"quick": True}
    # the temp file never outlives the os.replace
    assert list(tmp_path.iterdir()) == [path]


# ---------------------------------------------------------------------------
# transport retry/timeout/backoff + crash/recovery env helpers
# ---------------------------------------------------------------------------

def test_retry_policy_validation_and_backoff():
    from repro.launch.transport import RetryPolicy

    p = RetryPolicy(timeout_s=10.0, retries=3, backoff_s=0.5,
                    backoff_mult=2.0)
    assert [p.backoff(a) for a in range(3)] == [0.5, 1.0, 2.0]
    for bad in (dict(timeout_s=0.0), dict(retries=-1),
                dict(backoff_s=-1.0), dict(backoff_mult=0.5)):
        with pytest.raises(ValueError):
            RetryPolicy(**bad)


def test_retry_call_retries_then_succeeds():
    from repro.launch.transport import RetryPolicy, retry_call

    calls, sleeps, retries = [], [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"
    policy = RetryPolicy(retries=2, backoff_s=1.0, backoff_mult=3.0)
    out = retry_call(flaky, policy, retryable=(OSError,),
                     on_retry=lambda a, e: retries.append((a, str(e))),
                     sleep=sleeps.append)
    assert out == "ok" and len(calls) == 3
    assert sleeps == [1.0, 3.0]          # exponential backoff schedule
    assert retries == [(0, "transient"), (1, "transient")]


def test_retry_call_exhaustion_and_nonretryable():
    from repro.launch.transport import RetryPolicy, retry_call

    policy = RetryPolicy(retries=1, backoff_s=0.0)
    with pytest.raises(OSError):  # exhausted after retries+1 attempts
        retry_call(lambda: (_ for _ in ()).throw(OSError("down")), policy,
                   retryable=(OSError,), sleep=lambda s: None)
    with pytest.raises(KeyError):  # non-retryable escapes on attempt 0
        retry_call(lambda: {}["x"], policy, retryable=(OSError,),
                   sleep=lambda s: None)


def test_crash_recovery_env_helpers(monkeypatch):
    from repro.launch import topology as topo

    assert topo.clients_of_rank(0, 2) == (0, 1)
    assert topo.clients_of_rank(1, 3) == (3, 4, 5)

    monkeypatch.delenv(topo.CRASH_ENV, raising=False)
    assert topo.crash_spec_from_env() is None
    monkeypatch.setenv(topo.CRASH_ENV, "1@3")
    assert topo.crash_spec_from_env() == (1, 3)
    # non-matching rank/round is a no-op (a matching one would os._exit)
    topo.maybe_crash(0, 3)
    topo.maybe_crash(1, 2)

    monkeypatch.delenv(topo.DEAD_ENV, raising=False)
    monkeypatch.delenv(topo.RESUME_ENV, raising=False)
    assert topo.recovery_from_env() == ((), 0)
    monkeypatch.setenv(topo.DEAD_ENV, "2,3")
    monkeypatch.setenv(topo.RESUME_ENV, "4")
    assert topo.recovery_from_env() == ((2, 3), 4)

    out = f"x\n{topo.HEARTBEAT} 0\nnoise\n{topo.HEARTBEAT} 7\ny"
    assert topo.last_heartbeat(out) == 7
    assert topo.last_heartbeat("no beats") == -1
