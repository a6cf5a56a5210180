"""Topology-layer tests (ISSUE 7): mesh constructors, axis naming, link-tier
classification, the α–β link table, and the TierLedger bookkeeping contract.

Everything here is metadata-only — no test needs more than the single real
CPU device, so the whole module runs in-process (multi-device execution lives
in test_sharding.py subprocesses; multi-PROCESS execution in
test_multiproc.py behind the `multiproc` marker)."""

import jax
import pytest

from repro.core.wire import LINK_TIERS, TierLedger
from repro.launch.topology import (
    DEFAULT_LINKS,
    TIERS,
    LinkSpec,
    Topology,
    cohort_group_size,
    detect_topology,
    make_test_mesh,
    num_workers,
    production_topology,
    worker_axis_names,
)


class FakeMesh:
    """Duck-typed mesh exposing .shape mapping (same idiom as
    test_sharding.py) — worker-count math never needs devices."""

    def __init__(self, **axes):
        self.shape = axes


# ---------------------------------------------------------------------------
# link table + tier ordering
# ---------------------------------------------------------------------------


def test_link_table_matches_wire_tiers():
    # topology and wire must agree on the canonical tier names/order
    assert TIERS == LINK_TIERS == ("loopback", "ici", "dcn")
    assert set(DEFAULT_LINKS) == set(TIERS)


def test_link_table_is_monotone_fast_to_slow():
    # the documented table must actually order loopback > ici > dcn:
    # bandwidth strictly decreasing, launch latency strictly increasing
    lo, ici, dcn = (DEFAULT_LINKS[t] for t in TIERS)
    assert lo.bw > ici.bw > dcn.bw
    assert lo.alpha_s < ici.alpha_s < dcn.alpha_s
    # the headline constants DESIGN.md §7 documents
    assert ici.bw == 50e9
    assert dcn.bw == 6.25e9
    assert dcn.alpha_s == 25e-6


# ---------------------------------------------------------------------------
# production fabrics
# ---------------------------------------------------------------------------


def test_production_topology_single_pod():
    t = production_topology(multi_pod=False)
    assert t.n_devices == 256 and t.devices_per_pod == 256
    assert t.tier_of_axis("data") == "ici"
    assert t.tier_of_axis("model") == "ici"
    with pytest.raises(KeyError):
        t.tier_of_axis("pod")


def test_production_topology_multi_pod():
    t = production_topology(multi_pod=True)
    assert t.n_devices == 512 and t.devices_per_pod == 256
    assert t.tier_of_axis("pod") == "dcn"
    # a collective spanning pod+data is priced at its worst link
    assert t.tier_for_axes(("pod", "data")) == "dcn"
    assert t.tier_for_axes(("data", "model")) == "ici"
    assert t.tier_for_axes("data") == "ici"       # bare string accepted
    assert t.tier_for_axes(()) == "loopback"      # device-local exchange


def test_tier_for_group_size_production():
    t = production_topology(multi_pod=True)
    # wider than one pod -> must cross the dcn
    assert t.tier_for_group_size(512) == "dcn"
    assert t.tier_for_group_size(257) == "dcn"
    # inside one pod on a modeled-chip fabric -> ici
    assert t.tier_for_group_size(256) == "ici"
    assert t.tier_for_group_size(16) == "ici"


def test_tier_for_group_size_local_cluster():
    # the 2-process local CPU cluster: 4 devices, 2 per process; the worker
    # axis crosses the process boundary (its simulated dcn)
    t = Topology(
        axis_tiers=(("data", "dcn"), ("model", "loopback")),
        n_devices=4, n_processes=2,
    )
    assert t.devices_per_process == 2
    # groups wider than one process cross the (simulated) slow link tier —
    # without a pod bound they classify as ici at minimum
    assert t.tier_for_group_size(4) in ("ici", "dcn")
    # inside one process but fabric has non-loopback axes -> not loopback
    assert t.tier_for_group_size(2) != "dcn"
    # a pure single-process fake-device fabric is loopback end to end
    t1 = Topology(
        axis_tiers=(("data", "loopback"), ("model", "loopback")),
        n_devices=4, n_processes=1,
    )
    assert t1.tier_for_group_size(4) == "loopback"
    assert t1.tier_for_group_size(2) == "loopback"


def test_link_lookup():
    t = production_topology()
    assert t.link("ici") == LinkSpec(alpha_s=1e-6, bw=50e9)
    assert t.link("dcn").bw < t.link("ici").bw


# ---------------------------------------------------------------------------
# worker-axis math (folded in from the old launch/mesh.py)
# ---------------------------------------------------------------------------


def test_worker_axis_names():
    assert worker_axis_names(False, "data") == ("data",)
    assert worker_axis_names(True, "pod") == ("pod",)
    assert worker_axis_names(True, "pod_data") == ("pod", "data")


def test_num_workers():
    single = FakeMesh(data=16, model=16)
    multi = FakeMesh(pod=2, data=16, model=16)
    assert num_workers(single, False, "data") == 16
    assert num_workers(multi, True, "pod") == 2
    assert num_workers(multi, True, "pod_data") == 32


def test_cohort_group_size():
    assert cohort_group_size(8, 2) == 4
    assert cohort_group_size(8, 8) == 1
    assert cohort_group_size(8, 3) is None       # r does not divide n
    assert cohort_group_size(8, 0) is None       # degenerate cohort


# ---------------------------------------------------------------------------
# runtime classification (single real device — the degenerate but real case)
# ---------------------------------------------------------------------------


def test_detect_topology_single_process():
    mesh = make_test_mesh(1, 1)
    t = detect_topology(mesh)
    assert t.n_devices == 1 and t.n_processes == 1
    assert t.devices_per_pod is None
    if jax.default_backend() == "cpu":
        # no axis spans a process: fake-device loopback end to end
        assert t.tier_for_axes(("data", "model")) == "loopback"
        assert t.tier_for_group_size(1) == "loopback"


# ---------------------------------------------------------------------------
# TierLedger (repro.core.wire)
# ---------------------------------------------------------------------------


def test_tier_ledger_book_and_filter():
    led = TierLedger()
    led.book("compressed_step", "up", "dcn", "all-gather", 100.0)
    led.book("compressed_step", "up", "dcn", "all-gather", 50.0)
    led.book("compressed_step", "down", "ici", "broadcast", 10.0)
    led.book("sync_step", "up", "loopback", "psum", 1.0)

    assert led.total_bits() == pytest.approx(161.0)
    assert led.total_bits(scope="compressed_step") == pytest.approx(160.0)
    assert led.total_bits(direction="up") == pytest.approx(151.0)
    assert led.total_bits(tier="dcn") == pytest.approx(150.0)
    assert led.total_bits(scope="sync_step", tier="dcn") == 0.0
    # repeated bookings under one key accumulate bits AND trace counts
    key = ("compressed_step", "up", "dcn", "all-gather")
    assert led.counts[key] == 2


def test_tier_ledger_by_tier_and_dict_roundtrip():
    led = TierLedger()
    led.book("s", "up", "dcn", "all-gather", 8.0)
    led.book("s", "down", "dcn", "broadcast", 4.0)
    led.book("s", "up", "loopback", "psum", 2.0)
    by = led.by_tier(scope="s")
    assert by["dcn"] == {"up": 8.0, "down": 4.0}
    assert by["loopback"] == {"up": 2.0}
    d = led.to_dict()
    assert d["bits"]["s/up/dcn/all-gather"] == 8.0
    assert d["counts"]["s/down/dcn/broadcast"] == 1
    led.clear()
    assert led.total_bits() == 0.0 and led.to_dict() == {"bits": {}, "counts": {}}


def test_tier_ledger_rejects_bad_keys():
    led = TierLedger()
    with pytest.raises(AssertionError):
        led.book("s", "sideways", "dcn", "psum", 1.0)
    with pytest.raises(AssertionError):
        led.book("s", "up", "wan", "psum", 1.0)


def test_tier_for_ids_pod_straddle():
    # a 32-device group strided across the pod boundary is dcn even though
    # it is far narrower than one pod (the group-size heuristic says ici)
    t = production_topology(multi_pod=True)
    straddle = list(range(0, 512, 16))        # one id per (pod, data) slice
    assert len(straddle) == 32
    assert t.tier_for_ids(straddle) == "dcn"
    assert t.tier_for_group_size(len(straddle)) == "ici"
    # a contiguous intra-pod group stays ici; singleton groups are loopback
    assert t.tier_for_ids(range(16)) == "ici"
    assert t.tier_for_ids([7]) == "loopback"
    # local 2-process cluster: ids spanning processes cross the simulated dcn
    t2 = Topology(
        axis_tiers=(("data", "dcn"), ("model", "loopback")),
        n_devices=4, n_processes=2,
    )
    assert t2.tier_for_ids([0, 2]) == "dcn"
    assert t2.tier_for_ids([0, 1]) != "dcn"


def test_hlo_replica_group_ids_classification():
    from repro.roofline.analysis import collective_bytes_from_hlo

    t = production_topology(multi_pod=True)
    # iota reshape-transpose form: mesh (pod=2, data=16, model=16) psum over
    # (pod, data) -> 16 groups of 32, strided across pods -> dcn
    hlo_iota = (
        "  ar = f32[1024]{0} all-reduce(x), "
        "replica_groups=[16,32]<=[2,16,16]T(2,0,1), to_apply=add\n"
    )
    st = collective_bytes_from_hlo(hlo_iota, 512, t)
    assert list(st.by_tier_bytes) == ["dcn"]
    # explicit-list form straddling pods
    hlo_expl = (
        "  ar2 = f32[64]{0} all-reduce(x), "
        "replica_groups={{0,256},{1,257}}, to_apply=add\n"
    )
    st = collective_bytes_from_hlo(hlo_expl, 512, t)
    assert list(st.by_tier_bytes) == ["dcn"]
    # intra-pod iota groups classify ici; size-only form falls back to the
    # group-size heuristic
    hlo_ici = (
        "  ag = f32[256]{0} all-gather(x), replica_groups=[32,16]<=[512], "
        "dimensions={0}\n"
    )
    st = collective_bytes_from_hlo(hlo_ici, 512, t)
    assert list(st.by_tier_bytes) == ["ici"]
