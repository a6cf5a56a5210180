"""Topology layer — the device fabric under every launch-layer round.

This module answers three questions the round-assembly code used to answer
implicitly (or not at all):

1. **What does the fabric look like?** :class:`Topology` describes hosts,
   pods, and the link tier every mesh axis crosses — ``loopback`` (devices
   inside one process: the fake-device CPU simulation), ``ici`` (intra-pod
   chip interconnect), ``dcn`` (the cross-pod / cross-host data-center
   network — the bandwidth cliff MARINA's compressed wires were built
   for). Each tier carries an α–β cost model (:class:`LinkSpec`:
   per-collective-step latency α, bandwidth β) with a documented default
   table (:data:`DEFAULT_LINKS`).

2. **How do I get a mesh on it?** The mesh constructors (folded in from
   the old ``launch/mesh.py``) stay functions — importing this module never
   touches jax device state — and :func:`detect_topology` classifies any
   mesh's axes against the *runtime* process layout (an axis whose devices
   span OS processes on CPU is a dcn axis: cross-process is exactly the
   slow link the local cluster simulates).

3. **How do multiple processes come up?** :func:`initialize_multiprocess`
   wraps ``jax.distributed.initialize`` (gloo CPU collectives included),
   :func:`init_from_env` reads the ``MARINA_MP_*`` contract, and
   :func:`spawn_local_cluster` stands up an N-process local cluster in
   subprocesses — the bring-up path tests/CI and the multiproc benchmark
   share (``tests/test_multiproc.py``, ``benchmarks.run --only
   roundstep_mp``).

The transport layer (`launch/transport.py`) consumes the topology to book
every payload collective's bits under the right tier; `roofline/analysis.py`
consumes it to price collectives α–β per tier instead of one flat ICI
bandwidth. DESIGN.md §7 is the contract.

Demo (2-process local cluster, one psum + topology report per process):

    PYTHONPATH=src python -m repro.launch.topology --processes 2
"""

from __future__ import annotations

import dataclasses
import os
import socket
import subprocess
import sys
import time
from typing import Optional

PROCESS_ENV = "MARINA_MP_PROCESS"       # "<process_id>/<num_processes>"
COORD_ENV = "MARINA_MP_COORDINATOR"     # "host:port"

# crash/recovery contract (DESIGN.md §4.10): the resilient runner and the
# worker programs communicate through these —
CRASH_ENV = "MARINA_MP_CRASH"           # "<rank>@<round>": hard-exit there
DEAD_ENV = "MARINA_MP_DEAD"             # "2,3": client ids lost to a crash
RESUME_ENV = "MARINA_MP_RESUME"         # first round the dead set applies

#: per-round liveness marker worker programs print (rank 0 AND every other
#: rank) after completing each round; the resilient runner reads the stream
#: back to locate the last fleet-wide completed round after a crash.
HEARTBEAT = "MARINA_HB"

#: link-tier names, fastest to slowest (mirrors repro.core.wire.LINK_TIERS)
TIERS = ("loopback", "ici", "dcn")


@dataclasses.dataclass(frozen=True)
class LinkSpec:
    """α–β cost model of one link tier: a collective over the tier costs
    ``steps·alpha_s + wire_bytes/bw`` (ring accounting supplies the wire
    bytes and the step count — roofline/analysis.py)."""

    alpha_s: float          # latency per collective step (seconds)
    bw: float               # bandwidth per device (bytes/s)


#: Default α–β table (DESIGN.md §7). Sources: loopback ≈ one HBM-speed
#: memcpy between fake devices in one address space; ici = TPU v5e ~50 GB/s
#: per link, ~1 µs hop latency; dcn = commodity 50 Gbit/s NIC per host
#: (6.25 GB/s) with ~25 µs round-trip software latency. These are modeling
#: constants, not measurements — the REFUTED-style check in
#: roofline/analysis.py flags any recorded variant that disagrees with the
#: model by more than 2×.
DEFAULT_LINKS: dict = {
    "loopback": LinkSpec(alpha_s=5e-7, bw=100e9),
    "ici": LinkSpec(alpha_s=1e-6, bw=50e9),
    "dcn": LinkSpec(alpha_s=25e-6, bw=6.25e9),
}


@dataclasses.dataclass(frozen=True)
class Topology:
    """The device fabric: process/pod extents plus a link tier per mesh axis.

    ``axis_tiers`` maps every mesh axis name to the SLOWEST link a
    collective over that axis crosses. ``devices_per_pod`` bounds the
    ici domain for group-size classification (collectives spanning more
    devices than one pod must cross the dcn); ``devices_per_process``
    bounds the loopback domain the same way.
    """

    axis_tiers: tuple            # ((axis, tier), ...) — frozen mapping
    n_devices: int
    n_processes: int = 1
    devices_per_pod: Optional[int] = None   # None: single-pod fabric
    links: tuple = tuple(sorted(DEFAULT_LINKS.items()))

    @property
    def devices_per_process(self) -> int:
        """Addressable devices per OS process (the loopback domain)."""
        return self.n_devices // max(1, self.n_processes)

    def tier_of_axis(self, axis: str) -> str:
        """Link tier of a collective over one mesh axis."""
        for a, t in self.axis_tiers:
            if a == axis:
                return t
        raise KeyError(f"axis {axis!r} not in topology {self.axis_tiers}")

    def tier_for_axes(self, axes) -> str:
        """Slowest tier among the given mesh axes (a collective spanning
        several axes is priced at its worst link). Empty axes (a
        device-local exchange) price as loopback."""
        if not axes:
            return "loopback"
        if isinstance(axes, str):
            axes = (axes,)
        tiers = [self.tier_of_axis(a) for a in axes]
        return max(tiers, key=TIERS.index)

    def tier_for_group_size(self, g: int) -> str:
        """Classify a collective by its replica-group extent: groups wider
        than one pod cross the dcn; wider than one process cross the ici;
        anything inside one process is loopback. This is how the roofline
        tiers HLO collectives, where only the group size survives
        compilation."""
        if self.devices_per_pod is not None and g > self.devices_per_pod:
            return "dcn"
        if g > self.devices_per_process:
            return "ici"
        # single-process fabrics distinguish modeled-ici from loopback via
        # the axis table: if any axis is ici the fabric models real chips
        if any(t != "loopback" for _a, t in self.axis_tiers):
            return "ici"
        return "loopback"

    def tier_for_ids(self, ids) -> str:
        """Classify a replica group by its member device ids — sharper than
        :meth:`tier_for_group_size` when the HLO spells the ids out. A group
        narrower than one pod can still cross the dcn if its members sit in
        different pods (e.g. a psum over the ("pod", "data") worker axes of
        a 2-pod mesh: 32 devices, strided across the pod boundary); likewise
        a group whose ids span OS processes crosses the simulated slow link
        (the same convention :func:`detect_topology` applies to axes)."""
        ids = [int(i) for i in ids]
        if len(ids) <= 1:
            return "loopback"
        if self.devices_per_pod is not None and len(
            {i // self.devices_per_pod for i in ids}
        ) > 1:
            return "dcn"
        if self.n_processes > 1 and len(
            {i // self.devices_per_process for i in ids}
        ) > 1:
            return "dcn"
        return self.tier_for_group_size(len(ids))

    def link(self, tier: str) -> LinkSpec:
        """The α–β constants of one tier."""
        return dict(self.links)[tier]


# ---------------------------------------------------------------------------
# production / test meshes (folded in from the old launch/mesh.py)
#
# Defined as functions (never module-level constants) so importing this
# module does not touch jax device state — the dry-run sets
# ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
# import; tests and benches see the real single device.
# ---------------------------------------------------------------------------


def make_mesh(shape: tuple, axes: tuple):
    """The one mesh constructor. Every axis is ``Auto``: the round assembly
    pins layouts with ``with_sharding_constraint`` and leaves the rest to
    the partitioner, which ``Explicit`` axes (``jax.make_mesh``'s default)
    refuse."""
    import jax
    from jax.sharding import AxisType

    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_topology(*, multi_pod: bool = False) -> Topology:
    """The fabric the production meshes MODEL (the dry-run runs them on
    fake devices, but §Perf prices them as real chips): every intra-pod
    axis is ici, the pod axis is dcn, one pod = 256 chips."""
    if multi_pod:
        return Topology(
            axis_tiers=(("pod", "dcn"), ("data", "ici"), ("model", "ici")),
            n_devices=512, n_processes=1, devices_per_pod=256,
        )
    return Topology(
        axis_tiers=(("data", "ici"), ("model", "ici")),
        n_devices=256, n_processes=1, devices_per_pod=256,
    )


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for CPU sharding tests (requires ≥ data·model host
    devices)."""
    return make_mesh((data, model), ("data", "model"))


def make_federated_mesh(clients: int, model: int = 1):
    """Mesh for the federated PP scenario: the worker ("data") axis is the
    client fleet, the model axis carries within-client parallelism (1 for
    cross-device clients). Requires ≥ clients·model host devices — pair
    with XLA_FLAGS=--xla_force_host_platform_device_count for CPU tests."""
    return make_mesh((clients, model), ("data", "model"))


def worker_axis_names(multi_pod: bool, worker_axes: str) -> tuple:
    """Which mesh axes form the MARINA worker dimension (DESIGN.md §3)."""
    if not multi_pod:
        return ("data",)
    return ("pod",) if worker_axes == "pod" else ("pod", "data")


def num_workers(mesh, multi_pod: bool, worker_axes: str) -> int:
    """Worker-fleet size n: product of the worker mesh axes' extents."""
    n = 1
    for ax in worker_axis_names(multi_pod, worker_axes):
        n *= mesh.shape[ax]
    return n


def cohort_group_size(n: int, r: int) -> Optional[int]:
    """Mesh slots per sampled client when a PP cohort of r is respread over
    all n worker shards (DESIGN.md §4.8): n/r when r divides n, else None.
    None means cohort-mapped compute is impossible and the builder falls
    back to masked dense compute; a non-None group is necessary but not
    sufficient — build_train_steps additionally requires the per-worker
    batch to split evenly ((per_worker·r) % n == 0)."""
    return n // r if (r > 0 and n % r == 0) else None


def detect_topology(mesh, *, multi_pod: bool = False) -> Topology:
    """Classify a RUNTIME mesh's axes against the actual process layout.

    Per axis: devices varying along it that live in different OS processes
    make it a cross-process axis — "dcn" on CPU (the local cluster's
    process boundary IS its simulated slow link) and "ici" on real
    accelerators inside one pod; an axis named "pod" is always "dcn".
    Axes local to one process are "loopback" on CPU fake devices, "ici"
    on real chips."""
    import jax
    import numpy as np

    dev = np.asarray(mesh.devices)
    procs = np.vectorize(lambda d: d.process_index)(dev)
    cpu = jax.default_backend() == "cpu"
    tiers = []
    for i, axis in enumerate(mesh.axis_names):
        if axis == "pod":
            tiers.append((axis, "dcn"))
            continue
        along = np.moveaxis(procs, i, 0)
        spans = bool((along != along[0]).any())
        if spans:
            tiers.append((axis, "dcn" if cpu else "ici"))
        else:
            tiers.append((axis, "loopback" if cpu else "ici"))
    pod_devs = None
    if "pod" in mesh.axis_names:
        pod_devs = dev.size // mesh.shape["pod"]
    return Topology(
        axis_tiers=tuple(tiers),
        n_devices=int(dev.size),
        n_processes=int(jax.process_count()),
        devices_per_pod=pod_devs,
    )


# ---------------------------------------------------------------------------
# multi-process bring-up (jax.distributed)
# ---------------------------------------------------------------------------


def initialize_multiprocess(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
) -> None:
    """``jax.distributed.initialize`` with CPU cross-process collectives.

    Must run before the first jax computation touches the backend. On CPU
    the gloo collectives implementation is selected so worker-axis psums /
    all-gathers genuinely cross the process boundary (the transport's dcn
    tier) instead of failing at dispatch."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def init_from_env() -> tuple:
    """Bring up this process from the ``MARINA_MP_*`` contract set by
    :func:`spawn_local_cluster` (no-op single-process bring-up when the
    variables are absent). Returns ``(process_id, num_processes)``."""
    spec = os.environ.get(PROCESS_ENV)
    coord = os.environ.get(COORD_ENV)
    if not spec or not coord:
        return (0, 1)
    pid_s, nproc_s = spec.split("/")
    pid, nproc = int(pid_s), int(nproc_s)
    initialize_multiprocess(coord, nproc, pid)
    return (pid, nproc)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _launch_procs(
    prog: str,
    num_processes: int,
    devices_per_process: int,
    extra_env: Optional[dict],
) -> list:
    """Start the cluster's subprocesses (rank order) on a fresh coordinator
    port — the shared bring-up of :func:`spawn_local_cluster` and
    :func:`run_resilient_cluster`."""
    port = _free_port()
    env_base = dict(os.environ)
    # CPU simulations by design (gloo collectives): the children never
    # compete with a parent for an accelerator
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices_per_process} "
        + env_base.get("XLA_FLAGS", "")
    )
    env_base[COORD_ENV] = f"127.0.0.1:{port}"
    env_base.setdefault(
        "PYTHONPATH",
        os.path.join(os.path.dirname(__file__), "..", ".."),
    )
    if extra_env:
        env_base.update(extra_env)
    procs = []
    for pid in range(num_processes):
        env = dict(env_base)
        env[PROCESS_ENV] = f"{pid}/{num_processes}"
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", prog],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env,
            )
        )
    return procs


class ClusterBringupError(RuntimeError):
    """A local-cluster attempt came back with failed children. Carries the
    per-rank ``CompletedProcess`` list so the retry wrapper can surface the
    LAST attempt's stderr when the budget runs out."""

    def __init__(self, message: str, results: Optional[list] = None):
        super().__init__(message)
        self.results = results


def spawn_local_cluster(
    prog: str,
    *,
    num_processes: int = 2,
    devices_per_process: int = 2,
    timeout: float = 560.0,
    extra_env: Optional[dict] = None,
    retry=None,
) -> list:
    """Run ``prog`` (python source) in ``num_processes`` subprocesses wired
    into one jax.distributed cluster; each child sees
    ``devices_per_process`` fake CPU devices and must call
    :func:`init_from_env` before computing. Returns the per-process
    ``CompletedProcess`` list (rank order) — callers assert on
    returncode/stdout.

    This is the CI-sized stand-in for real multi-host bring-up: same
    initialize path, same global meshes, same cross-process collectives
    (gloo), just on localhost.

    ``retry`` (a :class:`repro.launch.transport.RetryPolicy`) hardens the
    flaky bring-up: the whole attempt is torn down and relaunched — fresh
    port, fresh children — when it times out or any child exits nonzero
    (gloo rendezvous races ARE whole-cluster failures; a half-alive fleet
    cannot be patched). Each attempt gets ``retry.timeout_s``; backoff
    sleeps between attempts; the last attempt's failure propagates
    (``TimeoutExpired``) or returns its failed results for the caller's
    returncode asserts."""

    def one_attempt(attempt_timeout: float) -> list:
        procs = _launch_procs(
            prog, num_processes, devices_per_process, extra_env
        )
        done = []
        for p in procs:
            try:
                out, err = p.communicate(timeout=attempt_timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            done.append(
                subprocess.CompletedProcess(p.args, p.returncode, out, err)
            )
        return done

    if retry is None:
        return one_attempt(timeout)

    from repro.launch.transport import retry_call  # deferred: transport imports topology

    def attempt() -> list:
        results = one_attempt(retry.timeout_s)
        bad = [i for i, r in enumerate(results) if r.returncode != 0]
        if bad:
            raise ClusterBringupError(
                f"cluster ranks {bad} exited nonzero", results=results
            )
        return results

    try:
        return retry_call(
            attempt, retry,
            retryable=(ClusterBringupError, subprocess.TimeoutExpired),
        )
    except ClusterBringupError as exc:
        return exc.results


# ---------------------------------------------------------------------------
# crash detection + recovery (DESIGN.md §4.10)
#
# A killed worker process on the real gloo cluster takes its device rows
# with it, and every survivor then hangs in the next collective — there is
# no in-band signal. The resilient runner therefore watches LIVENESS from
# outside: it polls the children, and the moment any rank dies it kills the
# survivors (they are blocked, not recoverable), reads the buffered stdout
# back, and locates the last fleet-wide completed round from the heartbeat
# lines every rank prints. Recovery is a relaunch with the dead clients
# mapped to the static ``drop`` fault (FaultSpec ids) from the first
# incomplete round onward — deterministic replay makes the recovered
# trajectory equal the run where those clients had simply missed every
# deadline from the crash round (tests/test_multiproc.py proves it).
# ---------------------------------------------------------------------------


def clients_of_rank(rank: int, devices_per_process: int) -> tuple:
    """Client ids a crashed rank takes down: the local-cluster convention
    maps worker/client i to global device i, and rank r owns the contiguous
    device block [r·dpp, (r+1)·dpp)."""
    lo = rank * devices_per_process
    return tuple(range(lo, lo + devices_per_process))


def crash_spec_from_env() -> Optional[tuple]:
    """Worker side of the crash-fault contract: ``(rank, round)`` parsed
    from ``MARINA_MP_CRASH="<rank>@<round>"``; None when unset/empty."""
    spec = os.environ.get(CRASH_ENV, "")
    if not spec:
        return None
    rank_s, round_s = spec.split("@")
    return (int(rank_s), int(round_s))


def maybe_crash(rank: int, round_k: int) -> None:
    """Process-crash fault injection: hard-exit via ``os._exit`` — no
    atexit, no flushed collectives, the closest a test gets to a SIGKILL'd
    worker — when the env names this rank and round. Call at the TOP of the
    round body, before any collective: the round never completes anywhere."""
    if crash_spec_from_env() == (rank, round_k):
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(17)


def recovery_from_env() -> tuple:
    """Worker side of the recovery contract: ``(dead_client_ids,
    resume_round)`` from ``MARINA_MP_DEAD``/``MARINA_MP_RESUME``. Rounds
    before ``resume_round`` replay fault-free (the fleet completed them);
    from it onward the dead ids are a static ``drop`` set. ``((), 0)``
    when unset — a plain run."""
    dead_s = os.environ.get(DEAD_ENV, "")
    dead = tuple(
        int(x) for x in dead_s.split(",") if x.strip()
    ) if dead_s else ()
    resume = int(os.environ.get(RESUME_ENV, "") or 0)
    return dead, resume


def last_heartbeat(text: str) -> int:
    """Last round a rank reported complete (``MARINA_HB <k>`` lines in its
    stdout); −1 when it never finished one."""
    last = -1
    for line in text.splitlines():
        parts = line.strip().split()
        if len(parts) == 2 and parts[0] == HEARTBEAT:
            try:
                last = int(parts[1])
            except ValueError:
                pass
    return last


@dataclasses.dataclass
class ClusterOutcome:
    """What :func:`run_resilient_cluster` observed: per-rank results (rank
    order; survivors killed after a crash carry their buffered output),
    the ranks that died on their own, and the last round EVERY rank had
    completed (min over heartbeats — the resume point)."""

    results: list
    dead_ranks: tuple
    last_round: int

    @property
    def crashed(self) -> bool:
        return bool(self.dead_ranks)


def run_resilient_cluster(
    prog: str,
    *,
    num_processes: int = 2,
    devices_per_process: int = 2,
    timeout: float = 560.0,
    extra_env: Optional[dict] = None,
    poll_s: float = 0.2,
) -> ClusterOutcome:
    """Like :func:`spawn_local_cluster`, but crash-aware: polls child
    liveness instead of blocking on rank 0. When a rank exits while others
    run, the survivors (hung in their next gloo collective) are killed
    immediately — the cluster does NOT stall for ``timeout`` — and the
    heartbeat streams locate the last fleet-wide completed round. A clean
    fleet-wide exit returns with ``dead_ranks=()``. The overall ``timeout``
    is the hang backstop (everything killed, whatever heartbeats were seen
    are reported)."""
    procs = _launch_procs(
        prog, num_processes, devices_per_process, extra_env
    )
    deadline = time.monotonic() + timeout
    dead = ()
    while time.monotonic() < deadline:
        codes = [p.poll() for p in procs]
        dead = tuple(
            i for i, c in enumerate(codes) if c is not None and c != 0
        )
        if dead or all(c is not None for c in codes):
            break
        time.sleep(poll_s)
    for p in procs:
        if p.poll() is None:
            p.kill()
    results = []
    for p in procs:
        out, err = p.communicate()
        results.append(
            subprocess.CompletedProcess(p.args, p.returncode, out, err)
        )
    beats = [last_heartbeat(r.stdout or "") for r in results]
    return ClusterOutcome(
        results=results,
        dead_ranks=dead,
        last_round=min(beats) if beats else -1,
    )


def run_with_recovery(
    prog: str,
    *,
    num_processes: int = 2,
    devices_per_process: int = 2,
    timeout: float = 560.0,
    extra_env: Optional[dict] = None,
    retry=None,
) -> tuple:
    """The full straggler-tolerance loop: run ``prog`` on the local cluster
    crash-aware; if a rank dies, relaunch ``prog`` single-process (the
    survivors' devices fold into one process) with the crashed rank's
    clients exported as the dead set from the first incomplete round —
    rounds the fleet completed replay fault-free, everything after treats
    the dead clients as permanent deadline-missers (the carry/drop
    substitution). Returns ``(outcome, recovery)`` where ``recovery`` is
    the recovery run's ``CompletedProcess`` (None when nothing crashed).
    ``retry`` hardens the recovery relaunch's bring-up."""
    outcome = run_resilient_cluster(
        prog,
        num_processes=num_processes,
        devices_per_process=devices_per_process,
        timeout=timeout,
        extra_env=extra_env,
    )
    if not outcome.crashed:
        return outcome, None
    dead_clients = ()
    for r in outcome.dead_ranks:
        dead_clients += clients_of_rank(r, devices_per_process)
    recovery_env = dict(extra_env or {})
    recovery_env[CRASH_ENV] = ""          # the ghost must not die twice
    recovery_env[DEAD_ENV] = ",".join(str(c) for c in sorted(dead_clients))
    recovery_env[RESUME_ENV] = str(outcome.last_round + 1)
    results = spawn_local_cluster(
        prog,
        num_processes=1,
        devices_per_process=num_processes * devices_per_process,
        timeout=timeout,
        extra_env=recovery_env,
        retry=retry,
    )
    return outcome, results[0]


_DEMO_PROG = r"""
from repro.launch import topology as topo
pid, nproc = topo.init_from_env()
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = topo.make_mesh((jax.device_count(),), ("data",))
t = topo.detect_topology(mesh)
sh = NamedSharding(mesh, P("data"))
x = jax.make_array_from_callback(
    (jax.device_count(),), sh, lambda i: np.arange(jax.device_count(), dtype=np.float32)[i]
)
total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(x)
print(f"process {pid}/{nproc}: {jax.local_device_count()} local of "
      f"{jax.device_count()} global devices; worker-axis tier = "
      f"{t.tier_for_axes(('data',))}; psum(arange) = {float(total):.0f}",
      flush=True)
"""


def main():
    """CLI demo: spawn an N-process local cluster, run one cross-process
    psum, and print each process's view of the topology."""
    import argparse

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--devices-per-process", type=int, default=2)
    args = ap.parse_args()
    results = spawn_local_cluster(
        _DEMO_PROG,
        num_processes=args.processes,
        devices_per_process=args.devices_per_process,
    )
    ok = True
    for r in results:
        sys.stdout.write(r.stdout)
        if r.returncode != 0:
            ok = False
            sys.stderr.write(r.stderr[-2000:])
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
