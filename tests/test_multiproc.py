"""Multi-process launch tests (ISSUE 7, `multiproc` marker).

The worker program below is ONE program run two ways through the same
bring-up path (`topology.spawn_local_cluster` → `init_from_env` →
`jax.distributed.initialize` with gloo CPU collectives):

* 2 processes × 2 fake devices — the worker ("data") axis crosses the OS
  process boundary, so every payload collective genuinely leaves the
  process (the local cluster's simulated dcn);
* 1 process × 4 fake devices — the historical fake-device simulation.

Both runs execute a sync round plus three compressed grad-carry MARINA
rounds on identical data (all randomness flows from threefry keys, which
are layout-independent) and print the parameter/estimator trajectory and
the link tiers the transport booked. The assertions:

1. the trajectories agree across process layouts (the refactor's
   trajectory-equality contract extends across the process boundary — only
   collective reduction order may differ, so tolerance is float32-tight,
   not bitwise);
2. every rank of the 2-process run agrees exactly (same global program);
3. the ledger books the SAME bits under "dcn" cross-process that the
   single-process run books under "loopback" — the wire cost is a property
   of the algorithm, the tier is a property of the fabric.

Excluded from tier-1 (`-m "not multiproc"` in pytest.ini): each run
compiles the reduced model per process. CI runs these in the dedicated
`multiproc` job. Run locally:  pytest -m multiproc tests/test_multiproc.py
"""

import re

import numpy as np
import pytest

from repro.launch import topology as topo
from repro.launch.topology import spawn_local_cluster, run_with_recovery
from repro.launch.transport import RetryPolicy

pytestmark = pytest.mark.multiproc


_WORKER_PROG = r"""
from repro.launch import topology as topo
pid, nproc = topo.init_from_env()

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.launch import sharding as shd
from repro.launch.distributed import build_train_steps
from repro.models import init_params, reduced

n_dev = jax.device_count()
assert n_dev == 4, n_dev
mesh = topo.make_test_mesh(n_dev, 1)

t = topo.detect_topology(mesh)
expect = "dcn" if nproc > 1 else "loopback"
assert t.tier_for_axes(("data",)) == expect, (t.axis_tiers, nproc)
assert t.n_processes == nproc

arch = get_arch("qwen1.5-0.5b")
arch = dataclasses.replace(arch, model=reduced(arch.model, layers=2, d_model=64))
bundle = build_train_steps(
    arch, mesh, multi_pod=False, global_batch=2 * n_dev, seq_len=32,
    gamma=0.1, dtype=jnp.float32, grad_carry=True,
)
cfg = arch.model
rep = NamedSharding(mesh, P())

# all state is materialized INSIDE jit from threefry keys with replicated
# output sharding: bit-identical values regardless of the process layout,
# and globally addressable on every rank
params = jax.jit(
    lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32),
    out_shardings=rep,
)()
g0 = jax.tree.map(jnp.zeros_like, params)
h0 = jax.tree.map(lambda p: jnp.zeros((n_dev, *p.shape), p.dtype), params)
toks = jax.jit(
    lambda: jax.random.randint(
        jax.random.PRNGKey(1), (n_dev, 2, 32), 0, cfg.vocab_size
    ),
    out_shardings=rep,
)()

# the step fns are jitted with explicit in_shardings, and multi-process jit
# refuses to silently reshard committed arrays — place the state exactly
# where the round assembly expects it (same shardings build_train_steps
# computed: fsdp off and replicate_params off => inner batch axis None)
tr = bundle.transport
p_shard = tr.param_shardings
wlead = tr.waxes if len(tr.waxes) > 1 else tr.waxes[0]
h_shard = jax.tree.map(
    lambda ns: NamedSharding(mesh, P(wlead, *ns.spec)), p_shard
)
b_shard = NamedSharding(mesh, shd.batch_spec(tr.waxes, None, 3))
params = jax.device_put(params, p_shard)
g0 = jax.device_put(g0, p_shard)
h0 = jax.device_put(h0, h_shard)
batch = {"tokens": jax.device_put(toks, b_shard)}


def checksum(tree):
    fp = jax.jit(
        lambda s: sum(jnp.sum(leaf) for leaf in jax.tree.leaves(s)),
        out_shardings=rep,
    )(tree)
    return float(fp)


traj = []
with bundle.mesh:
    fs, _ = bundle.fns["sync_step"]
    fc, _ = bundle.fns["compressed_step"]
    x, g, h = fs(params, g0, h0, batch)
    traj += [checksum(x), checksum(g)]
    for i in range(3):
        # numpy keys: host-consistent across ranks, no committed-device traps
        x, g, h = fc(x, g, h, batch, np.asarray(jax.random.PRNGKey(10 + i)))
        traj += [checksum(x), checksum(g)]

led = bundle.transport.ledger
up_tiers = sorted({tier for (_s, d, tier, _k) in led.bits if d == "up"})
assert up_tiers == [expect], (up_tiers, expect)
print("TIERS", ",".join(up_tiers))
print("UPBITS", repr(led.total_bits(direction="up")))
print("TRAJ", " ".join(f"{v:.9e}" for v in traj), flush=True)
"""


_CRASH_PROG = r"""
from repro.launch import topology as topo
pid, nproc = topo.init_from_env()

import dataclasses
import os
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_arch
from repro.core.faults import FaultSpec
from repro.launch import sharding as shd
from repro.launch.distributed import build_train_steps
from repro.models import init_params, reduced

n_dev = jax.device_count()
assert n_dev == 4, n_dev
mesh = topo.make_test_mesh(n_dev, 1)

# recovery contract: rounds < resume replay fault-free (the fleet completed
# them before the crash), rounds >= resume treat the dead clients as a
# static drop set — permanent deadline-missers on the carry table.
dead, resume = topo.recovery_from_env()
rounds = int(os.environ.get("MARINA_MP_ROUNDS", "6"))

arch = get_arch("qwen1.5-0.5b")
arch = dataclasses.replace(arch, model=reduced(arch.model, layers=2, d_model=64))


def make_bundle(faults):
    return build_train_steps(
        arch, mesh, multi_pod=False, global_batch=2 * n_dev, seq_len=32,
        gamma=0.1, dtype=jnp.float32, grad_carry=True, faults=faults,
    )


bundle = make_bundle(None)
faulted = make_bundle(FaultSpec("drop", ids=dead)) if dead else None
cfg = arch.model
rep = NamedSharding(mesh, P())

params = jax.jit(
    lambda: init_params(jax.random.PRNGKey(0), cfg, jnp.float32),
    out_shardings=rep,
)()
g0 = jax.tree.map(jnp.zeros_like, params)
h0 = jax.tree.map(lambda p: jnp.zeros((n_dev, *p.shape), p.dtype), params)
toks = jax.jit(
    lambda: jax.random.randint(
        jax.random.PRNGKey(1), (n_dev, 2, 32), 0, cfg.vocab_size
    ),
    out_shardings=rep,
)()

tr = bundle.transport
p_shard = tr.param_shardings
wlead = tr.waxes if len(tr.waxes) > 1 else tr.waxes[0]
h_shard = jax.tree.map(
    lambda ns: NamedSharding(mesh, P(wlead, *ns.spec)), p_shard
)
b_shard = NamedSharding(mesh, shd.batch_spec(tr.waxes, None, 3))
params = jax.device_put(params, p_shard)
g0 = jax.device_put(g0, p_shard)
h0 = jax.device_put(h0, h_shard)
batch = {"tokens": jax.device_put(toks, b_shard)}


def checksum(tree):
    fp = jax.jit(
        lambda s: sum(jnp.sum(leaf) for leaf in jax.tree.leaves(s)),
        out_shardings=rep,
    )(tree)
    return float(fp)


with bundle.mesh:
    fs, _ = bundle.fns["sync_step"]
    fc, _ = bundle.fns["compressed_step"]
    fcd = faulted.fns["compressed_step"][0] if faulted else None
    # round 0 is the dense sync rendezvous (all clients attend either way)
    x, g, h = fs(params, g0, h0, batch)
    print(f"TRAJ0 {checksum(x):.9e} {checksum(g):.9e}")
    print(f"{topo.HEARTBEAT} 0", flush=True)
    for k in range(1, rounds):
        topo.maybe_crash(pid, k)
        step = fcd if (fcd is not None and k >= resume) else fc
        x, g, h = step(x, g, h, batch, np.asarray(jax.random.PRNGKey(10 + k)))
        print(f"TRAJ{k} {checksum(x):.9e} {checksum(g):.9e}")
        print(f"{topo.HEARTBEAT} {k}", flush=True)

# per-trace uplink bits of each bundle's compressed scope: the faulted
# bundle must book only the surviving uploads ((n-f)/n of the fault-free)
print("UPFREE", repr(
    bundle.transport.ledger.total_bits(scope="compressed_step", direction="up")
))
if faulted is not None:
    print("UPDROP", repr(
        faulted.transport.ledger.total_bits(
            scope="compressed_step", direction="up"
        )
    ))
print("DONE", flush=True)
"""


def _parse(stdout: str, tag: str) -> str:
    m = re.search(rf"^{tag} (.+)$", stdout, re.M)
    assert m, f"no {tag} line in:\n{stdout[-2000:]}"
    return m.group(1)


def _run(num_processes: int, devices_per_process: int):
    results = spawn_local_cluster(
        _WORKER_PROG,
        num_processes=num_processes,
        devices_per_process=devices_per_process,
    )
    for r in results:
        assert r.returncode == 0, (
            f"rank failed ({num_processes}p):\n{r.stderr[-4000:]}"
        )
    return results


def test_two_process_compressed_carry_matches_single_process():
    mp = _run(num_processes=2, devices_per_process=2)
    sp = _run(num_processes=1, devices_per_process=4)

    # every rank of the 2-process run computed the same global trajectory
    assert _parse(mp[0].stdout, "TRAJ") == _parse(mp[1].stdout, "TRAJ")

    traj_mp = np.array([float(v) for v in _parse(mp[0].stdout, "TRAJ").split()])
    traj_sp = np.array([float(v) for v in _parse(sp[0].stdout, "TRAJ").split()])
    assert traj_mp.shape == traj_sp.shape == (8,)
    assert np.all(np.isfinite(traj_mp))
    # cross-process gloo collectives may reduce in a different order than the
    # single-process fused all-reduce — float32-tight, not bitwise
    np.testing.assert_allclose(traj_mp, traj_sp, rtol=1e-5, atol=1e-6)

    # same wire, different fabric: identical booked bits, re-tiered
    assert _parse(mp[0].stdout, "TIERS") == "dcn"
    assert _parse(sp[0].stdout, "TIERS") == "loopback"
    assert float(_parse(mp[0].stdout, "UPBITS")) == pytest.approx(
        float(_parse(sp[0].stdout, "UPBITS"))
    )


def _traj(stdout: str, k: int) -> np.ndarray:
    return np.array([float(v) for v in _parse(stdout, f"TRAJ{k}").split()])


def test_crash_recovery_matches_single_process_drop():
    """A worker killed mid-training on the 2-process gloo cluster must not
    stall the run: the resilient runner detects the death, kills the hung
    survivor, and relaunches with the crashed rank's clients as a static
    drop set from the first incomplete round. The recovered trajectory must
    match the single-process reference where those clients simply missed
    every deadline from that round on, and the drop rounds must book only
    the surviving uploads."""
    crash_round, rounds = 3, 6
    outcome, rec = run_with_recovery(
        _CRASH_PROG,
        num_processes=2,
        devices_per_process=2,
        extra_env={
            topo.CRASH_ENV: f"1@{crash_round}",
            "MARINA_MP_ROUNDS": str(rounds),
        },
        retry=RetryPolicy(timeout_s=540.0, retries=1, backoff_s=2.0),
    )
    assert outcome.crashed
    assert outcome.dead_ranks == (1,), [
        (r.returncode, r.stderr[-500:]) for r in outcome.results
    ]
    # rank 1 died at the top of round `crash_round`: the fleet completed
    # exactly the rounds before it
    assert outcome.last_round == crash_round - 1
    assert rec is not None and rec.returncode == 0, rec.stderr[-4000:]

    # reference: a straight single-process run with the same dead set from
    # the same round (no crash, no recovery machinery)
    ref = spawn_local_cluster(
        _CRASH_PROG,
        num_processes=1,
        devices_per_process=4,
        extra_env={
            topo.DEAD_ENV: "2,3",
            topo.RESUME_ENV: str(crash_round),
            "MARINA_MP_ROUNDS": str(rounds),
        },
    )[0]
    assert ref.returncode == 0, ref.stderr[-4000:]

    for k in range(rounds):
        np.testing.assert_allclose(
            _traj(rec.stdout, k), _traj(ref.stdout, k),
            rtol=1e-5, atol=1e-6, err_msg=f"round {k}",
        )
    # the recovery's replayed prefix reproduces what the 2-process fleet
    # actually computed before the crash. Looser than the recovery-vs-
    # reference check above: gloo collectives reduce in a different order
    # than the single-process fused all-reduce, and the g checksum sums
    # every parameter, compounding the reorder noise across rounds.
    for k in range(crash_round):
        np.testing.assert_allclose(
            _traj(outcome.results[0].stdout, k), _traj(rec.stdout, k),
            rtol=5e-5, atol=1e-6, err_msg=f"pre-crash round {k}",
        )

    # ledger: drop rounds book (n − f)/n of the fault-free uplink — only
    # the 2 surviving clients of 4 bill
    up_free = float(_parse(rec.stdout, "UPFREE"))
    up_drop = float(_parse(rec.stdout, "UPDROP"))
    assert up_free > 0
    assert up_drop == pytest.approx(up_free * 0.5)
