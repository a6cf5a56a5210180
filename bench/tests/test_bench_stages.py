"""The stage readers (``bench/stages.py``, ``bench/metrics/{backprop,
flat_glue,guard,unscoped}_ms.py``): by hand on a made-up reduced trace and
stage map, and on a short trace of the qwen cell recorded on the chip with
the stage map of the program that ran it."""

from __future__ import annotations

import importlib
import json
import re
import types
from pathlib import Path

import pytest

from bench import stages, trace

DATA = Path(__file__).resolve().parent / "data"
FIXTURE = DATA / "qwen15-l8.qsgd4.n2.t256.named.xplane.pb"
FIXTURE_STAGES = DATA / "qwen15-l8.qsgd4.n2.t256.named.stages.json"
READERS = ("backprop_ms", "flat_glue_ms", "guard_ms", "unscoped_ms")


class _Trainer:
    def __init__(self, stage_map):
        self.stage_map = stage_map

    def chunk_stages(self, carry, steps):
        assert carry == "carry" and list(steps) == [0]
        return dict(self.stage_map)


def _ctx(reduced, stage_map, rounds, program_names_stages=True):
    tr = _Trainer(stage_map) if program_names_stages else types.SimpleNamespace()
    session = types.SimpleNamespace(tr=tr, carry="carry", rounds_per_call=1)
    return types.SimpleNamespace(session=session, reduced=reduced, rounds=rounds)


def made_up(devices=1):
    """Two rounds. Self time (ns): backprop 4e6, the pack 2e6, a relayout
    copy in the uplink difference 5e5, the QSGD epilogue kernel 1e6, the
    guard 3e5, the while loop 2e5 (in no stage) and a fusion the map does
    not hold 1e5."""
    op_ns = {"fusion.1": 4e6, "fusion.2": 2e6, "copy.4": 5e5,
             "qsgd_epilogue": 1e6, "select.5": 3e5, "while.6": 2e5,
             "fusion.9": 1e5}
    op_ns = {k: v * devices for k, v in op_ns.items()}
    reduced = trace.Reduced(
        window_ns=1e8, devices=devices, busy_ns=8.1e6, op_ns=op_ns,
        op_count={k: 2 * devices for k in op_ns},
        kernel_ns={"qsgd_epilogue": 1e6 * devices},
        kernel_count={"qsgd_epilogue": 2 * devices},
        collective_ns=0.0, collective_exposed_ns=0.0, gaps=[])
    stage_map = {"fusion.1": "marina.backprop", "fusion.2": "flat.pack",
                 "copy.4": "marina.diff", "qsgd_epilogue.3": "flat.epilogue",
                 "select.5": "trainer.guard", "fusion.7": "flat.unpack"}
    return reduced, stage_map


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


@pytest.mark.parametrize("devices", [1, 2])
def test_readers_by_hand(devices):
    reduced, stage_map = made_up(devices)
    ctx = _ctx(reduced, stage_map, rounds=2)
    got = {name: _read(name, ctx) for name in READERS}
    assert got == pytest.approx({
        "backprop_ms": 2.0,            # 4e6 ns / 2 rounds
        "flat_glue_ms": 1.25,          # pack and copy, not the kernel
        "guard_ms": 0.15,
        "unscoped_ms": 0.15,           # the while loop and the unmapped fusion
    })
    split = stages.by_stage(reduced, stage_map)
    assert split[("flat.epilogue", True)] == 1e6 * devices
    assert sum(split.values()) == sum(reduced.op_ns.values())


def test_the_map_is_asked_for_once_a_run():
    reduced, stage_map = made_up()
    ctx = _ctx(reduced, stage_map, rounds=2)
    calls = []
    ask = ctx.session.tr.chunk_stages
    ctx.session.tr.chunk_stages = lambda *a: calls.append(1) or ask(*a)
    for name in READERS:
        _read(name, ctx)
    assert calls == [1]


def test_a_program_that_names_no_stages_reports_nothing():
    reduced, stage_map = made_up()
    assert all(_read(n, _ctx(reduced, stage_map, 2, False)) is None for n in READERS)
    assert all(_read(n, _ctx(reduced, {}, 2)) is None for n in READERS)
    assert all(_read(n, _ctx(reduced, stage_map, 0)) is None for n in READERS)


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(FIXTURE)), json.loads(FIXTURE_STAGES.read_text())


def test_recorded_round_is_nearly_all_in_stages(recorded):
    reduced, stage_map = recorded
    split = stages.by_stage(reduced, stage_map)
    unscoped = sum(v for (st, _), v in split.items() if st == stages.UNSCOPED)
    assert unscoped < 0.05 * reduced.busy_ns * reduced.devices
    named = {st for (st, _) in split}
    assert {"marina.backprop", "flat.pack", "flat.compress", "flat.epilogue",
            "trainer.guard"} <= named
    # every kernel runs in the flat engine's stages
    assert {st for (st, k) in split if k} <= {"flat.compress", "flat.epilogue"}


def test_recorded_kernels_are_named_by_their_kernel():
    from jax.profiler import ProfileData

    names = [e.name for plane in ProfileData.from_file(str(FIXTURE)).planes
             if plane.name.startswith("/device:TPU:")
             for line in plane.lines if line.name == "XLA Ops"
             for e in line.events if "tpu_custom_call" in e.name]
    assert names
    for name in names:
        label = trace.kernel_label(name)
        assert re.sub(r"\.\d+$", "", trace.short_name(name)) == label, name
