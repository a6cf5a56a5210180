"""MARINA's sharded round as a training loop: one worker per device.

:class:`MeshTrainer` drives the gradient-carry round that
``launch/distributed.py::build_train_steps`` assembles. It builds the step
bundle on a ``(devices, 1)`` mesh (the worker axis over the devices, a model
axis of 1), materialises x, g and the worker-sharded carry h in place so that
no device holds the whole state, draws each round's tokens from
``data/pipeline.py``'s stream and its key from the stream seed, and
dispatches ``train_step``, whose ``lax.cond`` on Bernoulli(p) picks the sync
or the compressed round. ``python -m repro.launch.train --backend mesh`` and
the benchmark's mesh cell both run it.

The state is (x^k, g^k, h^k) with h_i^k = ∇f_i(x^k). Round k descends
x^{k+1} = x^k − γ·g^k, takes every worker's gradient there on step k's
tokens, and sets g^{k+1} (the workers' mean, or g^k plus the compressed mean
of ∇f_i − h_i) and h^{k+1}. Initialisation is a sync round from g = 0 on
step 0's tokens, as ``Marina.init`` takes its first gradients.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig
from repro.core.marina import _round_keys
from repro.data import HeterogeneousLMData, make_prefix_embeddings, worker_batches
from repro.launch.distributed import BLOCK, KB, build_train_steps
from repro.launch.topology import make_mesh, num_workers
from repro.models import lm_loss
from repro.tracing import op_stages, stage

PyTree = Any


class MeshTrainer:
    """The sharded MARINA round: build once, ``init`` the state, ``step``."""

    def __init__(self, arch: ArchConfig, *, batch_per_worker: int, seq_len: int,
                 gamma: float, seed: int = 0, p: float | None = None,
                 backend: str = "auto"):
        """``seq_len`` counts the tokens of a row (a frontend's prefix comes
        on top); ``p`` is the sync probability (the mesh wire's ζ/d when
        None); ``seed`` fixes the token stream and the round keys."""
        self.mesh = make_mesh((jax.device_count(), 1), ("data", "model"))
        self.n = num_workers(self.mesh, False, arch.worker_axes)
        self.batch = batch_per_worker
        self.seed = seed
        self.prefix_len = arch.prefix_len
        self.cfg = arch.model
        self.p = KB / BLOCK if p is None else p
        self.bundle = build_train_steps(
            arch, self.mesh, multi_pod=False, global_batch=self.n * batch_per_worker,
            seq_len=seq_len + arch.prefix_len, gamma=gamma, dtype=jnp.float32,
            grad_carry=True, compression_backend=backend, p=self.p,
        )
        self.data = HeterogeneousLMData(
            n_workers=self.n, vocab_size=self.cfg.vocab_size, seq_len=seq_len,
            seed=seed)
        self._sync = self.bundle.fns["sync_step"][0]
        self._train = self.bundle.fns["train_step"][0]
        repl = NamedSharding(self.mesh, P())
        self._inputs = jax.jit(
            self._round_inputs, out_shardings=(self.bundle.batch_shardings, repl))
        self._coins = jax.jit(jax.vmap(lambda s: _round_keys(
            jax.random.fold_in(jax.random.PRNGKey(seed), s), self.p)[0]))
        self._mean_loss = jax.jit(self._loss)
        self._uplink_bits = None

    @stage("trainer.data")
    def _round_inputs(self, step):
        """Step ``step``'s batch (every worker's rows) and round key."""
        batch = {"tokens": worker_batches(self.data, step, self.batch)}
        if self.prefix_len:
            batch["prefix"] = make_prefix_embeddings(
                jax.random.fold_in(jax.random.PRNGKey(self.seed + 7), step),
                self.n, self.batch, self.prefix_len, self.cfg.d_model)
        return batch, jax.random.fold_in(jax.random.PRNGKey(self.seed), step)

    def init(self, make_params: Callable[[], PyTree]) -> tuple:
        """(x^0, g^0, h^0) from the parameters ``make_params()`` returns,
        each made where the round keeps it."""
        x_sh, g_sh, h_sh = self.bundle.state_shardings
        n = self.n
        x = jax.jit(make_params, out_shardings=x_sh)()
        g = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t), out_shardings=g_sh)(x)
        h = jax.jit(lambda t: jax.tree.map(
            lambda leaf: jnp.zeros((n, *leaf.shape), leaf.dtype), t),
            out_shardings=h_sh)(x)
        batch, _ = self._inputs(np.int32(0))
        return self._sync(x, g, h, batch)

    def step(self, state: tuple, step: int) -> tuple:
        """Round ``step`` on ``state`` (donated): the new (x, g, h)."""
        batch, key = self._inputs(np.int32(step))
        return self._train(*state, batch, key)

    def sync_rounds(self, steps) -> np.ndarray:
        """Whether each of ``steps`` is a sync round: the round's own coin."""
        return np.asarray(self._coins(jnp.asarray(steps, jnp.int32)))

    def uplink_bits(self) -> dict:
        """Bits one worker uploads in a sync and in a compressed round, as
        the transport's ledger books them when each round is traced."""
        if self._uplink_bits is None:
            ledger = self.bundle.transport.ledger
            self._uplink_bits = {}
            for kind in ("sync", "compressed"):
                fn, args = self.bundle.fns[f"{kind}_step"]
                before = ledger.total_bits(scope=f"{kind}_step", direction="up")
                jax.eval_shape(fn, *args)
                self._uplink_bits[kind] = ledger.total_bits(
                    scope=f"{kind}_step", direction="up") - before
        return self._uplink_bits

    def _loss(self, x, batch):
        one = lambda tokens, prefix: lm_loss(x, self.cfg, tokens, prefix)
        return jnp.mean(jax.vmap(one)(batch["tokens"], batch.get("prefix")))

    def loss(self, state: tuple, step: int) -> float:
        """Mean over workers of the loss at x on step ``step``'s rows."""
        batch, _ = self._inputs(np.int32(step))
        return float(self._mean_loss(state[0], batch))

    def compiled_text(self, state: tuple) -> str:
        """The compiled text of the round program that ``step`` runs."""
        batch, key = self._inputs(np.int32(0))
        return self._train.lower(*state, batch, key).compile().as_text()

    def chunk_stages(self, state: tuple, steps=None) -> dict:
        """``{instruction: stage}`` of the round program
        (:func:`repro.tracing.op_stages`), as ``Trainer.chunk_stages`` gives
        it for the trainer's chunk; a dispatch runs one round, so ``steps``
        changes nothing."""
        return op_stages(self.compiled_text(state))
