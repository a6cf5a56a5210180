"""Every main-path flat-engine kernel compiles for a TPU v5e chip at the flat
width of qwen1.5-0.5b (no chip attached: the TPU compiler runs against a
described v5e topology). Interpret-mode tests cannot see what Mosaic refuses
— block shapes off the (8, 128) tiling, unsupported casts or gathers — so
these compiles guard the kernels' lowering at their real shapes.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and the test workers all import this file.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.flat import DEFAULT_BLOCK, FlatEngine, make_layout
from repro.kernels import epilogue as epi
from repro.kernels import quantize
from repro.kernels.permk import permk_seeded_workers
from repro.kernels.randk import randk_gather, randk_seeded_workers, scatter_accum
from repro.models import init_params
from repro.tracing import op_stages

B = DEFAULT_BLOCK
KB = 8
GAMMA = 0.01


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def nblk():
    """Blocks of qwen1.5-0.5b's flat buffer (shapes only, nothing allocated)."""
    cfg = get_arch("qwen1.5-0.5b").model
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    d = sum(x.size for x in jax.tree.leaves(shapes))
    return -(-d // B)


#: the kernel each wrapper runs, where its name is not the wrapper's
KERNEL = {"randk_seeded_workers": "randk_seeded"}


def _compile(one_chip, fn, *shapes, kernel=None):
    """Compile ``fn`` on row-major operands and results; returns the
    compiled text, in which the Pallas kernel ``kernel`` must be an
    instruction named after it. Left free, the compiler lays a small leading
    worker axis out second-minor at the jit boundary and copies it into the
    kernel's row-major layout — a copy the engine's in-program buffers never
    pay, which at n = 4 overflows HBM."""
    fmt = lambda nd: Format(Layout(major_to_minor=tuple(range(nd))), one_chip)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=fmt(len(s))) for s, dt in shapes]
    outs = jax.tree.map(lambda o: fmt(o.ndim), jax.eval_shape(fn, *args))
    hlo = jax.jit(fn, out_shardings=outs).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    if kernel is not None:
        name = KERNEL.get(kernel, kernel)
        assert re.search(rf"^\s*(ROOT )?%{name}(\.\d+)? = .* custom-call\(", hlo, re.M), (
            f"no instruction named {name}")
    return hlo


f32, i32, u32, i8 = jnp.float32, jnp.int32, jnp.uint32, jnp.int8


def _uplinks(n, nb):
    """(name, fn, operand shapes) of the per-worker uplink kernels."""
    x = ((n, nb, B), f32)
    seeds = ((n,), u32)
    return [
        ("randk_seeded_workers",
         lambda x, s: randk_seeded_workers(x, s, KB, B / KB, interpret=False),
         [x, seeds]),
        ("permk_seeded_workers",
         lambda x, s: permk_seeded_workers(x, s, interpret=False),
         [x, ((), u32)]),
        ("qsgd_block_workers",
         lambda x, s: quantize.qsgd_block_workers(x, s, 7, backend="pallas"),
         [x, seeds]),
        ("natural_block_workers",
         lambda x, s: quantize.natural_block_workers(x, s, backend="pallas"),
         [x, seeds]),
    ]


def _server(n, nb):
    """(name, fn, operand shapes) of the server-side kernels: aggregation,
    the 4-bit wire and every fused epilogue."""
    buf = ((nb, B), f32)
    stack = ((n, nb, B), f32)
    levels = ((n, nb, B), i8)
    cols = ((n, nb), f32)
    pay = ((n, nb, KB), f32)
    offs = ((n, nb, KB), i32)
    P = dict(backend="pallas")
    lo, hi = n // 4, n - n // 4   # the f = n/4 trimmed mean
    return [
        ("scatter_accum",
         lambda v, o: scatter_accum(v, o, B, interpret=False), [pay, offs]),
        ("qsgd_dequant_mean",
         lambda q, c: quantize.qsgd_dequant_mean(q, c, 7, **P), [levels, cols]),
        ("natural_dequant_mean",
         lambda q, c: quantize.natural_dequant_mean(q, c, **P), [levels, cols]),
        ("nibble_pack",
         lambda q: quantize.nibble_pack(q, **P), [((n * nb, B), i8)]),
        ("nibble_unpack",
         lambda w: quantize.nibble_unpack(w, B, **P), [((n * nb, B // 8), u32)]),
        ("delta_epilogue",
         lambda d, g, x: epi.delta_epilogue(d, g, x, GAMMA, **P),
         [buf, buf, buf]),
        ("mean_epilogue",
         lambda s, x: epi.mean_epilogue(s, x, GAMMA, **P), [stack, buf]),
        ("scatter_epilogue",
         lambda v, o, g, x: epi.scatter_epilogue(v, o, g, x, GAMMA, **P),
         [pay, offs, buf, buf]),
        ("qsgd_epilogue",
         lambda q, c, g, x: epi.qsgd_epilogue(q, c, g, x, GAMMA, 7, **P),
         [levels, cols, buf, buf]),
        ("natural_epilogue",
         lambda q, c, g, x: epi.natural_epilogue(q, c, g, x, GAMMA, **P),
         [levels, cols, buf, buf]),
        ("trimmed_delta_epilogue",
         lambda s, g, x: epi.trimmed_delta_epilogue(s, g, x, GAMMA, lo, hi, **P),
         [stack, buf, buf]),
        ("trimmed_sync_epilogue",
         lambda s, x: epi.trimmed_sync_epilogue(s, x, GAMMA, lo, hi, **P),
         [stack, buf]),
    ]


UPLINKS = [name for name, _, _ in _uplinks(2, 1)]
SERVER = [name for name, _, _ in _server(2, 1)]


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("kernel", UPLINKS)
def test_uplink_kernel_compiles_at_qwen_width(one_chip, nblk, kernel, n):
    (fn, shapes), = [(f, s) for k, f, s in _uplinks(n, nblk) if k == kernel]
    _compile(one_chip, fn, *shapes, kernel=kernel)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("kernel", SERVER)
def test_server_kernel_compiles_at_qwen_width(one_chip, nblk, kernel, n):
    (fn, shapes), = [(f, s) for k, f, s in _server(n, nblk) if k == kernel]
    _compile(one_chip, fn, *shapes, kernel=kernel)


@pytest.mark.parametrize("kernel", ["randk_gather", "block_sumsq",
                                    "qsgd_quantize", "qsgd_dequantize"])
def test_flat_vector_kernel_compiles_at_qwen_width(one_chip, nblk, kernel):
    """The ops.py flat-vector wrappers' kernels (host offsets, global norm)."""
    buf = ((nblk, B), f32)
    scalar = ((), f32)
    P = dict(backend="pallas")
    fn, shapes = {
        "randk_gather": (
            lambda x, o: randk_gather(x, o, B / KB, interpret=False),
            [buf, ((nblk, KB), i32)]),
        "block_sumsq": (lambda x: quantize.block_sumsq(x, **P), [buf]),
        "qsgd_quantize": (
            lambda x, u, nm: quantize.qsgd_quantize(x, u, nm, 7, **P),
            [buf, buf, scalar]),
        "qsgd_dequantize": (
            lambda q, nm: quantize.qsgd_dequantize(q, nm, 7, **P),
            [((nblk, B), i8), scalar]),
    }[kernel]
    _compile(one_chip, fn, *shapes, kernel=kernel)


def test_fused_qsgd_round_maps_its_kernels_to_their_stages(one_chip, nblk):
    """The compiled fused QSGD round at qwen's flat width: the uplink and
    the 4-bit wire run in ``flat.compress``, the epilogue in
    ``flat.epilogue``, each kernel an instruction named after it."""
    lay = make_layout(jax.ShapeDtypeStruct((nblk * B,), f32), block=B)
    eng = FlatEngine(lay, backend="pallas", sampler="qsgd", s=7)
    buf = ((lay.rows, B), f32)
    hlo = _compile(
        one_chip, lambda k, d, g, x: eng.fused_round(k, d, 2, g, x, GAMMA),
        ((2,), u32), ((2, lay.rows, B), f32), buf, buf,
    )
    stages = op_stages(hlo)
    want = {"qsgd_block_workers": "flat.compress", "nibble_pack": "flat.compress",
            "nibble_unpack": "flat.compress", "qsgd_epilogue": "flat.epilogue"}
    for kernel, stage in want.items():
        got = {st for i, st in stages.items() if re.fullmatch(rf"{kernel}(\.\d+)?", i)}
        assert got == {stage}, (kernel, got)


@pytest.mark.parametrize("rows,L", [(151936, 1024), (24 * 1024, 2816)],
                         ids=["embed", "w_gate"])
def test_mesh_scatter_into_compiles_at_qwen_leaf_widths(one_chip, rows, L):
    """The mesh uplink's scatter-mean that adds into g, at qwen1.5-0.5b's
    embedding and ``w_gate`` leaves (four workers, L/128 offsets a row):
    it compiles, and writes its result over g (the call aliases its third
    operand)."""
    kb = L // 128
    hlo = _compile(
        one_chip, lambda v, o, g: scatter_accum(v, o, L, into=g, interpret=False),
        ((4, rows, kb), f32), ((4, rows, kb), i32), ((rows, L), f32),
        kernel="scatter_accum",
    )
    call = re.search(r"^.*%scatter_accum(\.\d+)? = .* custom-call\(.*$", hlo, re.M)
    assert "output_to_operand_aliasing={{}: (2, {})}" in call.group(0)
