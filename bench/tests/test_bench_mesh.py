"""The mesh cell's readers (``bench/metrics/{collective,collective_exposed,
mesh_uplink}_ms.py``, ``mesh_kernel_roofline.py``, ``mesh_step_mfu.py``) and
``bench/mesh_yardstick.py``, by hand on a made-up reduced trace, stage map
and compiled text; and a run of the mesh cell, cut to a tiny model on four
CPU devices, with its sharded round broken underneath comes out not
correct."""

from __future__ import annotations

import importlib
import types

import jax
import pytest

from bench import mesh_reference, mesh_yardstick, run, trace
from bench.entries import mesh_round
from bench.tests import tiny

MESH_CELL = "qwen15-l24.mesh4.randk.n4.t256"

#: two calls of the uplink's kernels as a compiled module writes them
TEXT = """\
  %randk_gather.3 = f32[16,128]{1,0:T(8,128)} custom-call(%bitcast.1, %pad.2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[16,1024]{1,0}, s32[16,128]{1,0}}, metadata={op_name="jit(scoped)/transport.uplink/shard_map/randk_gather/pallas_call"}, backend_config={"custom_call_config":{}}
  %scatter_accum.4 = f32[16,1024]{1,0:T(8,128)} custom-call(%copy.5, %copy.6), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,16,8]{2,1,0}, s32[4,16,8]{2,1,0}}, metadata={op_name="jit(scoped)/transport.uplink/shard_map/scatter_accum/pallas_call"}
  %qsgd_epilogue.7 = f32[16,1024]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[16,1024]{1,0}}
"""

GATHER = 16 * 128 * 4 + 16 * 1024 * 4 + 16 * 128 * 4
SCATTER = 16 * 1024 * 4 + 2 * 4 * 16 * 8 * 4


class _Trainer:
    def chunk_stages(self, carry, steps):
        return {"fusion.1": "marina.backprop", "all-gather.2": "transport.uplink",
                "fusion.3": "transport.uplink", "randk_gather.3": "transport.uplink",
                "all-reduce.4": "transport.sync"}


def _ctx():
    """Four devices, two rounds: backprop 4e6 ns a device, the uplink's
    all-gather 1e6 (half of it under other work), a fusion of the uplink
    5e5, its gather kernel 2e5 and its scatter kernel 3e5."""
    per_dev = {"fusion.1": 4e6, "all-gather.2": 1e6, "fusion.3": 5e5,
               "randk_gather.3": 2e5, "scatter_accum.4": 3e5}
    op_ns = {k: 4 * v for k, v in per_dev.items()}
    kernels = ("randk_gather.3", "scatter_accum.4")
    reduced = trace.Reduced(
        window_ns=1e8, devices=4, busy_ns=6e6, op_ns=op_ns,
        op_count={k: 8 for k in op_ns},
        kernel_ns={k: op_ns[k] for k in kernels},
        kernel_count={k: 8 for k in kernels},
        collective_ns=1e6, collective_exposed_ns=5e5, gaps=[])
    session = types.SimpleNamespace(
        tr=_Trainer(), carry="carry", rounds_per_call=1,
        compiled_text=lambda: TEXT, params_count=lambda: 1000,
        positions_per_call=1024)
    return types.SimpleNamespace(
        session=session, reduced=reduced, calls=2, rounds=2, window_s=0.5,
        chips=4, peaks={"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12})


def _read(name, ctx):
    return importlib.import_module(f"bench.metrics.{name}").read(ctx)


def test_call_bytes_reads_the_compiled_calls():
    assert mesh_yardstick.call_bytes(TEXT) == {
        "randk_gather.3": GATHER, "scatter_accum.4": SCATTER}


def test_readers_by_hand():
    ctx = _ctx()
    assert _read("collective_ms", ctx) == pytest.approx(0.5)
    assert _read("collective_exposed_ms", ctx) == pytest.approx(0.25)
    # the uplink's fusion and all-gather, not its kernels: (1e6 + 5e5) / 2
    assert _read("mesh_uplink_ms", ctx) == pytest.approx(0.75)
    least = 8 * (GATHER + SCATTER) / 819e9
    assert _read("mesh_kernel_roofline", ctx) == pytest.approx(
        100 * least / (4 * 5e5 / 1e9))
    assert _read("mesh_step_mfu", ctx) == pytest.approx(
        100 * 6 * 1000 * 1024 * 2 / (0.5 * 4 * 197e12))


def test_readers_report_nothing_without_rounds_or_a_program_to_read():
    ctx = _ctx()
    ctx.rounds = 0
    for name in ("collective_ms", "collective_exposed_ms", "mesh_uplink_ms",
                 "mesh_step_mfu"):
        assert _read(name, ctx) is None
    ctx = _ctx()
    ctx.session = types.SimpleNamespace()
    assert _read("mesh_kernel_roofline", ctx) is None
    assert _read("mesh_uplink_ms", ctx) is None


def test_wire_bits_by_hand():
    shapes = [(3, 1024), (2816,), (5,)]
    # rows of 1024 keep 8, a row of 2816 keeps 22, a row of 5 keeps 1
    assert mesh_reference.wire_bits(shapes, sync=False) == 64.0 * (3 * 8 + 22 + 1)
    assert mesh_reference.wire_bits(shapes, sync=True) == 32.0 * (3072 + 2816 + 5)


class MeshStateUnchanged(mesh_round.Session):
    """A sharded round that returns its state unchanged."""

    def build(self):
        super().build()
        self.tr.step = lambda state, step: state


class MeshHalfBatch(mesh_round.Session):
    """A sharded round that leaves out half of every worker's rows."""

    def build(self):
        super().build()
        whole = self.tr._inputs

        def half(step):
            batch, key = whole(step)
            return jax.tree.map(lambda t: t[:, :max(1, t.shape[1] // 2)], batch), key

        self.tr._inputs = half


@pytest.mark.parametrize("fault", [MeshStateUnchanged, MeshHalfBatch])
def test_broken_mesh_round_is_not_correct(fault, monkeypatch):
    tiny.patch(monkeypatch)
    line = run.run(MESH_CELL, 2**31 + 101, 0.5, False, require_tpu=False,
                   spec=tiny.spec(MESH_CELL), session_cls=fault)
    assert not line["correct"], line["checks"]
