"""The benchmark's arithmetic against hand counts at a tiny size, and the
kernel roofline on a trace recorded on the chip."""

from __future__ import annotations

import json
import types

import jax
import pytest

from bench import reference, trace, yardstick
from bench.metrics import kernel_roofline, kernel_ms, step_mfu
from bench.tests.test_bench_trace import FIXTURE
from bench.tests.tiny import DATA, ROOT


def test_weight_count_by_hand():
    m = reference.Model.from_config(json.loads((DATA / "tiny-qwen1.5-0.5b.json").read_text()))
    d, F, V, L = 64, 128, 512, 2
    layer = 2 * d + 4 * d * d + 3 * d + 3 * d * F      # norms, q k v o, biases, MLP
    want = V * d + d + L * layer                         # tied embedding, final norm
    got = sum(s.size for s in jax.tree.leaves(reference.weight_shapes(m)))
    assert got == want == 115_392


def test_model_flops_is_six_n_t():
    assert yardstick.model_flops(115_392, 2 * 2 * 128) == 6 * 115_392 * 512


def test_kernel_bytes_by_hand():
    n, nblk, B, k = 2, 3, 8, 2
    buf = nblk * B
    assert yardstick.blocks(258_384_896, 1024) == 252_329
    assert yardstick.kernel_bytes("qsgd_block_workers", n, nblk, B, 7) == 2 * 24 * 4 + 2 * 24 + 2 * 3 * 4
    assert yardstick.kernel_bytes("nibble_pack", n, nblk, B, 7) == 2 * 24 + 24
    assert yardstick.kernel_bytes("nibble_unpack", n, nblk, B, 7) == 24 + 2 * 24
    assert yardstick.kernel_bytes("qsgd_epilogue", n, nblk, B, 7) == 2 * 24 + 2 * 3 * 4 + 4 * buf * 4
    assert yardstick.kernel_bytes("mean_epilogue", n, nblk, B, 7) == 2 * 24 * 4 + 3 * buf * 4
    assert yardstick.kernel_bytes("randk_seeded", n, nblk, B, k) == 2 * 24 * 4 + 2 * 2 * 3 * 2 * 4
    assert yardstick.kernel_bytes("scatter_epilogue", n, nblk, B, k) == 2 * 2 * 3 * 2 * 4 + 4 * buf * 4
    assert yardstick.kernel_bytes("some_new_kernel", n, nblk, B, k) is None


@pytest.fixture(scope="module")
def ctx():
    """The recorded qwen round with that cell's shapes and the v5e's peaks."""
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())["devices"]["TPU v5 lite"]
    r = trace.load(str(FIXTURE))
    session = types.SimpleNamespace(
        n=2, block=1024, level=7, params_count=lambda: 258_384_896,
        positions_per_call=512)
    return types.SimpleNamespace(session=session, reduced=r, calls=1, rounds=1,
                                 window_s=r.window_ns / 1e9, chips=1, peaks=peaks)


def test_recorded_round_readers(ctx):
    assert set(ctx.reduced.kernel_ns) == {
        "qsgd_block_workers", "nibble_pack", "nibble_unpack", "qsgd_epilogue"}
    assert 0 < kernel_roofline.read(ctx) <= 100
    assert 0 < kernel_ms.read(ctx) < 1e3 * ctx.window_s
    assert 0 < step_mfu.read(ctx) <= 100
