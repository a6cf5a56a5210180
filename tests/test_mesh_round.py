"""MARINA's sharded round through ``repro.train.mesh.MeshTrainer`` on four
fake CPU devices. The device count must be set before JAX starts, so each
case runs in a subprocess: the trainer's first rounds against the plain mesh
reference (``bench/mesh_reference.py``), the controls that reference must
refuse, and the training CLI's ``--backend mesh``."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

_READINGS = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r}, {src!r}]
    import json
    import jax
    import jax.numpy as jnp
    from bench import compare
    from bench.entries import mesh_round, trainer_chunk
    from bench.tests import tiny

    trainer_chunk._program_config = tiny.program_config
    spec = tiny.spec("qwen15-l24.mesh4.randk.n4.t256")
    # one row a worker, as in the cell: the half batch then leaves out workers
    spec["traffic"]["batch_per_worker"] = 1
    s = mesh_round.Session(spec["config"], spec["traffic"], 2**31 + 7)
    s.build()
    # where each worker's row of h lives: (leaf, first row, rows, device)
    _, _, h = s.carry
    rows = [[i, sh.index[0].start or 0, sh.data.shape[0], sh.device.id]
            for i, leaf in enumerate(jax.tree.leaves(h))
            for sh in leaf.addressable_shards]
    prog = s.first_rounds()
    sync = [bool(c) for c in s.tr.sync_rounds(range(3))]
    s.release()
    want = s.reference()
    out = {{"sync": sync, "limits": spec["limits"], "h_rows": rows,
            "program": compare.numbers(prog, want),
            "control": compare.numbers(s.reference(dtype=jnp.bfloat16), want),
            "half_batch": compare.numbers(s.reference(fault="half_batch"), want)}}
    print(json.dumps(out))
    """
)


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), full.get("PYTHONPATH", "")])
    return subprocess.run(args, capture_output=True, text=True, timeout=300,
                          cwd=ROOT, env=full)


@pytest.fixture(scope="module")
def readings():
    prog = _READINGS.format(root=str(ROOT), src=str(ROOT / "src"))
    r = _run([sys.executable, "-c", prog])
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_mesh_trainer_rounds_match_the_mesh_reference(readings):
    """The first three rounds, every worker's gradient, the estimator, the
    change of x and the bits, against the reference at float32 round-off;
    the bfloat16 control and a half batch fail the cell's own limits."""
    from bench import compare

    out = readings
    got = out["program"]
    assert got["bits_gap"] == 0.0, got
    assert max(got["grad_gap"], got["estimator_gap"], got["change_gap"]) < 1e-5, got
    # a sync round among the three, so both branches of the coin are read
    assert any(out["sync"]) and not all(out["sync"]), out["sync"]
    for kind in ("control", "half_batch"):
        ok, checks = compare.judge(out[kind], out["limits"])
        assert not ok, (kind, checks)


def test_each_workers_carry_lives_on_its_own_device(readings):
    """Every leaf of h is cut by worker: one row a device, the four rows on
    four distinct devices, so that no device holds the whole carry."""
    by_leaf = {}
    for leaf, row, rows, device in readings["h_rows"]:
        assert rows == 1, readings["h_rows"]
        by_leaf.setdefault(leaf, []).append((row, device))
    assert by_leaf
    for placed in by_leaf.values():
        assert sorted(r for r, _ in placed) == [0, 1, 2, 3], placed
        assert len({d for _, d in placed}) == 4, placed


def test_the_cells_check_rounds_are_compressed_compressed_sync():
    """The mesh cell's stream seed puts a sync round third, at p = 1/128."""
    import jax

    from bench.run import load_spec
    from repro.core.marina import _round_keys

    traffic = load_spec(ROOT, "qwen15-l24.mesh4.randk.n4.t256")["traffic"]
    p = traffic["wire"]["level"] / traffic["wire"]["block"]
    assert p == 1 / 128
    base = jax.random.PRNGKey(traffic["stream_seed"])
    coins = [bool(_round_keys(jax.random.fold_in(base, k), p)[0]) for k in range(3)]
    assert coins == [False, False, True]


def test_train_cli_mesh_backend_runs_two_steps(tmp_path):
    r = _run([sys.executable, "-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
              "--backend", "mesh", "--reduced", "--steps", "2"],
             XLA_FLAGS="--xla_force_host_platform_device_count=4",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "mesh: 4 workers" in r.stdout
    rows = [line.split() for line in r.stdout.splitlines()
            if line.split()[:1] in (["0"], ["1"])]
    assert [row[0] for row in rows] == ["0", "1"], r.stdout
    assert all(float(row[1]) > 0 for row in rows)
