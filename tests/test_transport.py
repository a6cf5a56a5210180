"""``Transport.uplink_mean(..., into=g)`` on four fake CPU devices: the sum
``g + mean`` to the bit, with the same booked bits, for every wire family.
The device count must be set before JAX starts, so the cases run in one
subprocess and report per case."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CASES = ["randk", "randk_packed", "shared_mask", "permk", "qsgd",
         "randk_sharded_dst"]
BACKENDS = ["ref", "pallas_interpret"]

_PROG = textwrap.dedent(
    """
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{src!r}]
    import json
    import jax
    import jax.extend
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.topology import detect_topology, make_mesh
    from repro.launch.transport import make_transport

    OPTIONS = {{
        "randk": {{}}, "randk_packed": {{"packed_payload": True}},
        "shared_mask": {{"shared_mask": True}},
        "permk": {{"compression": "permk"}},
        "qsgd": {{"compression": "qsgd", "qsgd_s": 7}},
        "randk_sharded_dst": {{}},
    }}
    SHAPES = {{"b": (128,), "emb": (40, 256), "w": (2, 8, 384)}}

    def scatter_operands(jaxpr):
        # operand count of every scatter_accum kernel the trace calls
        out = []
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call" and str(
                    e.params["name"]).startswith("scatter_accum"):
                out.append(len(e.invars))
            for v in e.params.values():
                for j in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(j, jax.extend.core.ClosedJaxpr):
                        out += scatter_operands(j.jaxpr)
                    elif isinstance(j, jax.extend.core.Jaxpr):
                        out += scatter_operands(j)
        return out

    out = {{}}
    for backend in {backends!r}:
        for case in {cases!r}:
            sharded = case == "randk_sharded_dst"
            # a sharded destination: "emb" cut over a model axis of 2
            mesh = make_mesh((2, 2) if sharded else (4, 1), ("data", "model"))
            n = mesh.shape["data"]
            key = jax.random.PRNGKey(3)
            osh = {{k: NamedSharding(mesh, P(None, "model") if sharded and k == "emb"
                                     else P()) for k in SHAPES}}
            diffs = jax.device_put(
                {{k: jax.random.normal(jax.random.fold_in(key, i), (n, *s))
                  for i, (k, s) in enumerate(SHAPES.items())}},
                {{k: NamedSharding(mesh, P("data")) for k in SHAPES}})
            g = jax.device_put(
                {{k: jax.random.normal(jax.random.fold_in(key, 10 + i), s)
                  for i, (k, s) in enumerate(SHAPES.items())}}, osh)

            def run(into):
                t = make_transport(mesh, detect_topology(mesh), waxes=("data",),
                                   n=n, backend=backend, **OPTIONS[case])

                def f(k, d, gg):
                    with t.scope("compressed_step"):
                        if into:
                            return t.uplink_mean(k, d, out_shardings=osh, into=gg)
                        mean = t.uplink_mean(k, d, out_shardings=osh)
                        return jax.tree.map(jnp.add, gg, mean)

                args = (jax.random.PRNGKey(1), diffs, g)
                got = jax.jit(f)(*args)
                ops = scatter_operands(jax.make_jaxpr(f)(*args).jaxpr)
                bits = [t.ledger.total_bits(direction=d) for d in ("up", "down")]
                return got, ops, bits

            (a, ops_into, bits_into), (b, ops_plain, bits_plain) = run(True), run(False)
            out[f"{{backend}}-{{case}}"] = {{
                "equal": {{k: bool(np.array_equal(np.asarray(a[k]), np.asarray(b[k])))
                           for k in SHAPES}},
                "ops_into": ops_into, "ops_plain": ops_plain,
                "bits_into": bits_into, "bits_plain": bits_plain,
            }}
    print(json.dumps(out))
    """
)


@pytest.fixture(scope="module")
def readings():
    prog = _PROG.format(src=str(ROOT / "src"), backends=BACKENDS, cases=CASES)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, timeout=600, cwd=ROOT, env=env)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("backend", BACKENDS)
def test_uplink_mean_into_is_g_plus_the_mean(readings, backend, case):
    """``uplink_mean(key, diffs, into=g)`` == ``g + uplink_mean(key, diffs)``
    bit for bit, the ledger's up and down totals unchanged. With the Pallas
    kernels, each randk or shared-mask leaf whose destination is whole on
    every device takes g as the scatter kernel's third operand; the leaf cut
    over the model axis, and the permk and qsgd wires, add after the mean."""
    got = readings[f"{backend}-{case}"]
    assert all(got["equal"].values()), got["equal"]
    assert got["bits_into"] == got["bits_plain"], got
    assert got["bits_into"][0] > 0
    scatters = backend == "pallas_interpret" and case not in ("permk", "qsgd")
    assert got["ops_plain"] == ([2, 2, 2] if scatters else [])
    if not scatters:
        assert got["ops_into"] == []
    elif case == "randk_sharded_dst":
        assert sorted(got["ops_into"]) == [2, 3, 3]  # "emb" adds after
    else:
        assert got["ops_into"] == [3, 3, 3]
