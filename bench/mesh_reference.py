"""Plain reference for the mesh cells: MARINA's gradient-carry round on the
mesh's per-leaf RandK wire, one worker a chip.

The model, its loss and gradients (at ``HIGHEST`` in float32), the weights
and the token stream are ``bench.reference``'s; nothing of the program under
test is imported. What this file adds is the mesh's wire, in the program's
leaf order (the weight tree's flatten order):

* one key per leaf, split from the round's compression key;
* one ``randint`` of shape (n, R, kb) per leaf, where a leaf of shape
  (..., L) has R rows of L and keeps kb = max(1, L // 128) offsets a row;
* each worker's kept values scaled by L / kb and scattered back into its
  rows; the workers' mean is the round's update of g.

A sync round sets g to the workers' mean gradient; every round sets h_i to
worker i's gradient. The wire's bits: 32 a coordinate in a sync round; in a
compressed round an f32 value and an int32 offset for each kept coordinate.

Memory plan, for a state too large for one chip: worker i's gradient and
h_i live on chip i (``shard_map`` over the workers' chips), x and g are
replicated, and the workers' sum is a ``psum`` of one leaf at a time. Only
per-leaf norms go to the host.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bench import reference as ref

#: a row of L coordinates keeps max(1, L // WIRE) of them
WIRE = 128

#: logits one block of a worker's rows may hold (positions x vocabulary)
REFERENCE_LOGITS = 10 * 2**20


def kept_per_row(L: int) -> int:
    return max(1, L // WIRE)


def leaf_rows(shape: tuple) -> tuple[int, int]:
    """(R, L): a leaf as R rows of its last dimension."""
    L = int(shape[-1])
    return (int(np.prod(shape[:-1])) if len(shape) > 1 else 1), L


def wire_bits(shapes, sync: bool) -> float:
    """Bits one worker uploads in a round of the mesh wire, for the leaves'
    ``shapes``."""
    if sync:
        return 32.0 * sum(int(np.prod(s)) for s in shapes)
    return sum(64.0 * R * kept_per_row(L) for R, L in map(leaf_rows, shapes))


@jax.jit
def _row_norms(tree):
    """(leaves, n) norms of a worker-stacked tree."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(t.reshape(t.shape[0], -1).astype(jnp.float32)),
                         axis=1))
        for t in jax.tree.leaves(tree)
    ])


@dataclasses.dataclass
class MeshMarina:
    """Lookahead gradient-carry MARINA on the mesh wire: the state is
    (x^{k+1}, g^k, h_i = ∇f_i at x^k), as ``bench.reference.Marina`` holds
    it; round k takes ∇f_i(x^{k+1}; step k's rows), flips c_k ~ Be(p) from
    the round key, sets g^{k+1}, then steps x.

    ``dtype=bfloat16`` runs it all one precision lower (the control).
    ``fault="half_batch"`` leaves out half of the global batch: half of each
    worker's rows, or with one row a worker the second half of the workers,
    and takes the mean over the rest."""

    m: ref.Model
    n: int
    batch: int
    seq: int
    gamma: float
    stream_seed: int
    p: float
    dtype: object = jnp.float32
    fault: str | None = None

    def __post_init__(self):
        devs = jax.devices()
        if len(devs) < self.n:
            raise ValueError(f"{self.n} workers need {self.n} chips, JAX sees {len(devs)}")
        self.mesh = Mesh(np.array(devs[:self.n]), ("w",))
        self.repl = NamedSharding(self.mesh, P())
        self.rows_of = NamedSharding(self.mesh, P("w"))
        rows = max(1, REFERENCE_LOGITS // ((self.seq + self.m.prefix_len) * self.m.vocab_size))
        half = self.fault == "half_batch"
        self.kept = np.int32(self.n // 2 if half and self.batch == 1 else self.n)
        b = self.batch // 2 if half and self.batch > 1 else self.batch
        self._grads, self._mean, self._compressed_mean, self._weights = _programs(
            self.m, self.mesh, self.dtype, rows, b)

    def compile(self):
        """Compile the round's gradient, mean and compressed-mean programs
        for the shapes and placements their calls take, without running
        them: a host thread may do this while the chips run the program, and
        the first calls then load them from the persistent cache."""
        shapes = jax.tree.leaves(ref.weight_shapes(self.m))
        treedef = jax.tree.structure(ref.weight_shapes(self.m))
        spec = lambda lead, sh: jax.tree.unflatten(treedef, [
            jax.ShapeDtypeStruct((*lead, *s.shape), self.dtype, sharding=sh)
            for s in shapes])
        x, stack = spec((), self.repl), spec((self.n,), self.rows_of)
        tokens = jax.ShapeDtypeStruct((self.n, self.batch, self.seq), jnp.int32,
                                      sharding=self.rows_of)
        prefix = None if not self.m.prefix_len else jax.ShapeDtypeStruct(
            (self.n, self.batch, self.m.prefix_len, self.m.hidden_size),
            jnp.float32, sharding=self.rows_of)
        kept = jax.ShapeDtypeStruct((), jnp.int32)
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        self._grads.lower(x, tokens, prefix, kept).compile()
        self._mean.lower(stack, kept).compile()
        self._compressed_mean.lower(stack, stack, key, kept).compile()

    def _inputs(self, step):
        tokens, prefix = ref.round_inputs(
            step, self.stream_seed, self.n, self.m.vocab_size, self.seq,
            self.batch, self.m.prefix_len, self.m.hidden_size)
        put = lambda t: None if t is None else jax.device_put(t, self.rows_of)
        return put(tokens), put(prefix)

    def worker_grads(self, x, step):
        return self._grads(x, *self._inputs(step), self.kept)

    def weights(self, seed: int):
        """The seeded weights (``bench.reference.make_weights``), replicated."""
        return self._weights(np.uint32(seed))

    def init(self, x0):
        x0 = ref._cast(x0, self.dtype)
        h = self.worker_grads(x0, 0)
        g = self._mean(h, self.kept)
        return {"x": _step_x(x0, g, self.gamma), "g": g, "h": h}

    def keys(self, step):
        """(c_k, the compression key) of round ``step``."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.stream_seed), step)
        k_bern, k_q = jax.random.split(key)
        return bool(jax.random.bernoulli(k_bern, self.p)), k_q

    def round(self, state, step):
        grads = self.worker_grads(state["x"], step)
        sync, k_q = self.keys(step)
        if sync:
            g = self._mean(grads, self.kept)
        else:
            g = _add(state["g"], self._compressed_mean(grads, state["h"], k_q, self.kept))
        return {"x": _step_x(state["x"], g, self.gamma), "g": g, "h": grads}


@functools.lru_cache(maxsize=None)
def _programs(m: ref.Model, mesh: Mesh, dtype, rows: int, b: int):
    """The round's programs, each worker's part on its own chip: gradients of
    ``b`` rows a worker (``rows`` at a time), the sync mean, the compressed
    mean, and the weights; workers from ``kept`` on (an argument) are left
    out. Made once a process for each such set, so that later seeds and the
    half batch of one row a worker reuse what compiled."""
    n = mesh.shape["w"]
    per_worker = functools.partial(jax.shard_map, mesh=mesh, check_vma=False)

    def mine(t, kept):
        """A worker's row of a local block, zero for workers left out."""
        keep = jax.lax.axis_index("w") < kept
        return jnp.where(keep, t, jnp.zeros_like(t))

    @functools.partial(per_worker, in_specs=(P(), P("w"), P("w"), P()),
                       out_specs=P("w"))
    def grads(x, tokens, prefix, kept):
        pre = None if prefix is None else prefix[0, :b]
        g = ref.worker_grad(x, tokens[0, :b], pre, m, dtype, rows)
        return jax.tree.map(lambda t: mine(t, kept)[None], g)

    @functools.partial(per_worker, in_specs=(P("w"), P()), out_specs=P())
    def mean(stack, kept):
        return jax.tree.map(
            lambda t: (jax.lax.psum(mine(t[0], kept), "w") / kept).astype(dtype), stack)

    @functools.partial(per_worker, in_specs=(P("w"), P("w"), P(), P()), out_specs=P())
    def compressed_mean(new, old, key, kept):
        i = jax.lax.axis_index("w")
        leaves, treedef = jax.tree.flatten(new)
        out = []
        for lk, a, o in zip(jax.random.split(key, len(leaves)), leaves,
                            jax.tree.leaves(old)):
            R, L = leaf_rows(a.shape[1:])
            kb = kept_per_row(L)
            idx = jax.random.randint(lk, (n, R, kb), 0, L, jnp.int32)[i]
            diff = (a[0] - o[0]).reshape(R, L)
            vals = jnp.take_along_axis(diff, idx, axis=1) * (L / kb)
            dense = jnp.zeros_like(diff).at[jnp.arange(R)[:, None], idx].add(vals)
            total = jax.lax.psum(mine(dense, kept), "w") / kept
            out.append(total.astype(dtype).reshape(a.shape[1:]))
        return jax.tree.unflatten(treedef, out)

    weights = jax.jit(lambda seed: ref.make_weights(m, seed),
                      out_shardings=NamedSharding(mesh, P()))
    return jax.jit(grads), jax.jit(mean), jax.jit(compressed_mean), weights


@functools.partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _step_x(x, g, gamma: float):
    return jax.tree.map(lambda xi, gi: (-gamma) * gi + xi, x, g)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def worker_norms(stack) -> np.ndarray:
    """(leaves, n) norms of a worker-stacked tree, as float64 on the host."""
    return np.asarray(_row_norms(stack), np.float64)
