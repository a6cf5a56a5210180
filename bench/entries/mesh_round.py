"""Entry ``mesh_round``: MARINA's sharded round, one worker a chip, through
``repro.train.mesh.MeshTrainer``, the trainer that ``python -m
repro.launch.train --backend mesh`` runs.

Set-up builds the trainer on a (chips, 1) mesh, makes the weights from the
seed where the round keeps them (replicated, never whole on one chip alone),
runs the trainer's initialisation (a sync round from g = 0) and its first
``CHECK_ROUNDS`` calls, reading after each what the round produced: every
worker's gradient (the carry h), the estimator g and the uplink ledger. That
same state then feeds the window. After it the readings are held against
``bench.mesh_reference``, which follows the same rounds with each worker's
part on its own chip.

The traffic states the job: MARINA with gradient carry and no non-finite
guard (the sharded round has none), on the mesh's per-leaf ``randk`` wire,
which keeps ``level`` = 1 of every ``block`` = 128 coordinates: L / 128 of
each row of L of a leaf. That share is also the sync probability p.
"""

from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import mesh_reference as mref
from bench import reference as ref
from bench.entries import trainer_chunk

#: calls of the window's entry that set-up drives and the reference follows
CHECK_ROUNDS = trainer_chunk.CHECK_ROUNDS

#: what the sharded round runs, whatever the traffic says
JOB = {"method": "marina", "carry_grads": True, "nonfinite_guard": False}


def _program_config(arch: str, m: ref.Model):
    """The program's model configuration at the file's depth and widths."""
    return trainer_chunk._program_config(arch, m)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _lookahead_change(x, g, gamma: float, m: ref.Model, seed):
    """Per-leaf norms of x − γ·g − x^0: the next round's point, where the
    reference's lookahead state stands, less the seeded weights."""
    w0 = ref.make_weights(m, seed)
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square((-gamma) * gi + xi - wi)))
        for xi, gi, wi in zip(jax.tree.leaves(x), jax.tree.leaves(g),
                              jax.tree.leaves(w0))
    ])


@jax.jit
def _round_done(g):
    """One coordinate of g: ready when the round that made g is done, and
    not donated to the next round as the state is."""
    return jax.tree.leaves(g)[-1].reshape(-1)[:1]


class Session:
    """One mesh trainer, its state, and the readings of its first rounds."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        for key, want in JOB.items():
            if traffic[key] != want:
                raise ValueError(f"the sharded round runs {key}={want!r}, "
                                 f"the traffic asks for {traffic[key]!r}")
        wire = traffic["wire"]
        if (wire["sampler"], wire["level"], wire["block"]) != ("randk", 1, 128):
            raise ValueError("the sharded round's wire is randk keeping 1 of "
                             f"every 128 coordinates of a row, not {wire!r}")
        self.m = ref.Model.from_config(config)
        # weights from the run's seed; the token stream and the round coins
        # from the traffic's stream seed, as in trainer_chunk
        self.seed = seed % 2**32
        self.stream_seed = traffic["stream_seed"]
        self.n = traffic["n_workers"]
        self.batch = traffic["batch_per_worker"]
        self.seq = traffic["seq_len"]
        self.rounds_per_call = traffic["rounds_per_call"]
        self.gamma = traffic["gamma"]
        self.p = wire["level"] / wire["block"]
        self.arch = config["program_arch"]
        self.sizes = tuple(int(np.prod(s.shape))
                           for s in jax.tree.leaves(ref.weight_shapes(self.m)))
        self.calls = 0
        self._text = None

    # -- set-up -----------------------------------------------------------
    def build(self):
        """The trainer, and MARINA's state made in place from the seed."""
        import dataclasses

        from repro.configs import get_arch
        from repro.models import init_params
        from repro.train.mesh import MeshTrainer

        cfg = _program_config(self.arch, self.m)
        want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        got = jax.eval_shape(lambda: ref.make_weights(self.m, self.seed))
        if jax.tree.structure(want) != jax.tree.structure(got) or [
            (t.shape, t.dtype) for t in jax.tree.leaves(want)
        ] != [(t.shape, t.dtype) for t in jax.tree.leaves(got)]:
            raise ValueError("the weight tree does not match the program's")
        tr = MeshTrainer(
            dataclasses.replace(get_arch(self.arch), model=cfg),
            batch_per_worker=self.batch, seq_len=self.seq, gamma=self.gamma,
            seed=self.stream_seed, p=self.p)
        if tr.n != self.n:
            raise ValueError(f"the mesh has {tr.n} workers, the traffic {self.n}")
        self.tr = tr
        self.carry = tr.init(lambda: ref.make_weights(self.m, self.seed))

    def restart(self, seed: int):
        """Start again from the weights of ``seed``, on the same compiled
        round (what ``bench/tools/mesh_readings.py`` does between seeds)."""
        self.seed = seed % 2**32
        self.calls = 0
        del self.carry
        self.carry = self.tr.init(lambda: ref.make_weights(self.m, self.seed))

    def dispatch(self):
        """One call of the window's entry; returns what to wait on."""
        R = self.rounds_per_call
        for k in range(self.calls * R, (self.calls + 1) * R):
            self.carry = self.tr.step(self.carry, k)
        self.calls += 1
        return _round_done(self.carry[1])

    def first_rounds(self) -> trainer_chunk.Readings:
        """Drive the first ``CHECK_ROUNDS`` calls and read each one."""
        r = trainer_chunk.Readings()
        bits = self.tr.uplink_bits()
        R = self.rounds_per_call
        sync = self.tr.sync_rounds(np.arange(CHECK_ROUNDS * R))
        booked = 0.0
        for c in range(CHECK_ROUNDS):
            jax.block_until_ready(self.dispatch())
            _, g, h = self.carry
            r.grad.append(mref.worker_norms(h))
            r.estimator.append(ref.leaf_norms(g))
            booked += sum(bits["sync" if s else "compressed"]
                          for s in sync[c * R:(c + 1) * R])
            r.bits.append(booked)
        x, g, _ = self.carry
        r.change = np.asarray(_lookahead_change(
            x, g, self.gamma, self.m, np.uint32(self.seed)), np.float64)
        return r

    @property
    def tokens_per_call(self) -> int:
        return self.rounds_per_call * self.n * self.batch * self.seq

    @property
    def positions_per_call(self) -> int:
        return self.rounds_per_call * self.n * self.batch * (self.seq + self.m.prefix_len)

    def params_count(self) -> int:
        return int(sum(self.sizes))

    def skipped(self) -> float:
        """Rounds refused: none, the sharded round has no guard."""
        return 0.0

    def compiled_text(self) -> str:
        """The compiled text of the window's round program (asked once)."""
        if self._text is None:
            self._text = self.tr.compiled_text(self.carry)
        return self._text

    def release(self):
        """Free the program's state and its compiled programs."""
        del self.carry, self.tr
        gc.collect()
        jax.clear_caches()

    # -- the reference ----------------------------------------------------
    def marina(self, dtype=jnp.float32, fault=None) -> mref.MeshMarina:
        """The plain reference of this cell's rounds."""
        return mref.MeshMarina(self.m, self.n, self.batch, self.seq, self.gamma,
                               self.stream_seed, self.p, dtype=dtype, fault=fault)

    def reference(self, dtype=jnp.float32, fault=None) -> trainer_chunk.Readings:
        """The same first rounds from the plain reference."""
        mar = self.marina(dtype, fault)
        r = trainer_chunk.Readings()
        st = mar.init(mar.weights(self.seed))
        r.first_grad = ref.leaf_norms(st["g"])
        shapes = [s.shape for s in jax.tree.leaves(ref.weight_shapes(self.m))]
        bits = 0.0
        for k in range(CHECK_ROUNDS * self.rounds_per_call):
            st = mar.round(st, k)
            bits += mref.wire_bits(shapes, mar.keys(k)[0])
            if (k + 1) % self.rounds_per_call == 0:
                r.grad.append(mref.worker_norms(st["h"]))
                r.estimator.append(ref.leaf_norms(st["g"]))
                r.bits.append(bits)
        w0 = mar.weights(self.seed)
        r.change = np.asarray(trainer_chunk._change_norms(st["x"], w0), np.float64)
        del st, w0
        gc.collect()
        return r
