"""Named stages of the round, host spans of the training loop, and the map
from compiled instructions to stages (``repro.tracing``)."""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.models import init_params
from repro.models.config import ModelConfig, dense_stack
from repro.train import TrainConfig, Trainer

#: opcodes that run nothing on the device: arguments, literals, tuples and
#: views of a buffer
FREE = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}

_OP = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)\s+([\w\-]+)\(.*?'
    r'\bop_name="([^"]*)"', re.M)


def _tiny_cfg():
    return ModelConfig(
        name="tr", arch_type="dense", d_model=32, num_heads=2, num_kv_heads=2,
        d_ff=64, vocab_size=64, segments=dense_stack(1),
    )


def _trainer(**kw):
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    base = dict(
        method="marina", compressor="block_qsgd",
        comp_kwargs={"s": 7, "block": 128}, carry_grads=True, n_workers=2,
        batch_per_worker=1, gamma=0.05, steps=2, log_every=2,
    )
    return Trainer(cfg, TrainConfig(**{**base, **kw}), params)


def _chunk_hlo(tr, rounds=2):
    state = tr.method.init(tr.params0, tr._batches(0, tr.tcfg.batch_per_worker))
    carry = (state, *[jnp.zeros((), jnp.float32) for _ in range(4)])
    steps = jnp.arange(rounds, dtype=jnp.int32)
    return carry, steps, tr._jitted_chunk.lower(carry, steps).compile().as_text()


@pytest.fixture(scope="module")
def qsgd_chunk():
    """A tiny QSGD carry round on the flat engine's kernels (interpret
    mode), with dropped clients so that the carry refresh runs too."""
    tr = _trainer(flat_backend="pallas_interpret", faults="drop", faults_frac=0.5)
    carry, steps, hlo = _chunk_hlo(tr)
    return tr, carry, steps, hlo


#: the sharded round's exchanges
EXCHANGES = {"transport.uplink", "transport.sync"}
#: the stages of the sharded round alone, which the trainer's chunk does not run
MESH_ONLY = EXCHANGES | {"marina.update"}


def test_chunk_stages_name_every_stage_the_round_runs(qsgd_chunk):
    tr, carry, steps, hlo = qsgd_chunk
    stages = tr.chunk_stages(carry, steps)
    assert stages == tracing.op_stages(hlo)
    assert set(stages.values()) == set(tracing.STAGES) - MESH_ONLY


def test_few_loop_body_ops_are_left_without_a_stage(qsgd_chunk):
    *_, hlo = qsgd_chunk
    ops = [(op, name) for _, op, name in _OP.findall(hlo)
           if "/while/body" in name and op not in FREE]
    loose = [(op, name) for op, name in ops if tracing.stage_of(name) is None]
    assert len(ops) > 500
    assert len(loose) < 0.05 * len(ops), loose[:20]


def test_unknown_names_are_refused():
    with pytest.raises(ValueError):
        tracing.stage("nonsense")
    with pytest.raises(ValueError):
        tracing.span("nonsense")


def test_stage_of_keeps_the_innermost_stage():
    assert tracing.stage_of("jit(f)/while/body/marina.diff/vmap(flat.pack)/pad") == "flat.pack"
    assert tracing.stage_of("jit(f)/marina.backprop/transpose(jvp(lm))/dot") == "marina.backprop"
    assert tracing.stage_of("jit(f)/while/body/cond") is None
    assert tracing.stage_of("jit(f)/flat.packed/add") is None


def test_op_stages_reads_compiled_text():
    @tracing.stage("flat.pack")
    def pack(x):
        return jnp.pad(x, (0, 3)) * 2.0

    def f(x):
        with tracing.stage("marina.backprop"):
            g = jax.grad(lambda y: jnp.sum(jnp.sin(y) ** 2))(x)
        return jax.vmap(pack)(g.reshape(2, -1))

    hlo = jax.jit(f).lower(jnp.ones(8)).compile().as_text()
    stages = tracing.op_stages(hlo)
    entry = hlo[hlo.index("ENTRY"):]
    (root,) = re.findall(r"ROOT %([\w.\-]+) = ", entry)
    assert stages[root] == "flat.pack"        # the fusion keeps its root's scope
    assert "marina.backprop" in stages.values()


def test_run_writes_host_spans_into_the_profile(tmp_path):
    from jax.profiler import ProfileData

    tr = _trainer(steps=4, log_every=2, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2)
    tdir = str(tmp_path / "trace")
    with jax.profiler.trace(tdir):
        _, hist = tr.run()
    assert hist.step == [-1, 1, 3] and not hasattr(hist, "wall")
    # a second run of the same job resumes from its last checkpoint
    tr2 = _trainer(steps=6, log_every=2, ckpt_dir=str(tmp_path / "ck"), ckpt_every=2)
    with jax.profiler.trace(tdir):
        tr2.run()
    names = []
    for path in sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                names += [e.name for line in plane.lines for e in line.events]
    counts = {s: names.count(s) for s in tracing.SPANS}
    assert counts["trainer.chunk"] == 3 and counts["trainer.eval"] == 5
    assert counts["trainer.checkpoint"] == 3 and counts["trainer.restore"] == 1
    assert np.isfinite(hist.loss).all()


_MESH_STAGES = textwrap.dedent(
    r"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import collections, dataclasses, json, re
    import jax
    from repro import tracing
    from repro.configs import get_arch
    from repro.models import init_params, reduced
    from repro.train.mesh import MeshTrainer

    arch = get_arch("qwen1.5-0.5b")
    cfg = dataclasses.replace(reduced(arch.model, layers=1, d_model=128), d_ff=256)
    arch = dataclasses.replace(arch, model=cfg)
    tr = MeshTrainer(arch, batch_per_worker=1, seq_len=32, gamma=0.05,
                     backend="pallas_interpret")
    state = tr.init(lambda: init_params(jax.random.PRNGKey(0), cfg))
    text = tr.compiled_text(state)
    stages = tr.chunk_stages(state)
    op = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)'
                    r'\s+([\w\-]+)\((.*)$', re.M)
    seen = collections.Counter()
    for name, code, rest in op.findall(text):
        on = re.search(r'op_name="([^"]*)"', rest)
        on = on.group(1) if on else ""
        if "randk_gather" in on or "scatter_accum" in on:
            seen["kernel " + str(stages.get(name))] += 1
        if re.fullmatch(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start)?", code):
            seen["collective " + str(stages.get(name))] += 1
        if code in ("dot", "convolution"):
            seen["matmul " + str(stages.get(name))] += 1
    print(json.dumps(seen))
    """
)


def test_mesh_step_puts_its_exchange_and_backprop_in_stages():
    """In the sharded round that ``MeshTrainer`` compiles (four fake CPU
    devices, in a subprocess), every collective falls in
    ``transport.uplink`` or ``transport.sync``, every operation of the
    per-leaf RandK kernels in ``transport.uplink``, and every matmul in
    ``marina.backprop``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([os.path.join(root, "src"), env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", _MESH_STAGES], capture_output=True,
                       text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    seen = json.loads(r.stdout.strip().splitlines()[-1])
    stages = {k.split(" ", 1)[0]: set() for k in seen}
    for k in seen:
        kind, st = k.split(" ", 1)
        stages[kind].add(st)
    assert stages["kernel"] == {"transport.uplink"}, seen
    assert stages["collective"] == EXCHANGES, seen
    assert stages["matmul"] == {"marina.backprop"}, seen
