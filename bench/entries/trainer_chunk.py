"""Entry ``trainer_chunk``: MARINA rounds through ``Trainer._jitted_chunk``.

Set-up builds one trainer from the configuration and the traffic, makes its
weights from the seed, runs the program's own MARINA initialisation, and
drives the compiled chunk through the first ``CHECK_ROUNDS`` calls, reading
after each call what the round produced: every worker's gradient, the
server's estimator and the bit ledger. That same carry then feeds the
window. After the window the readings are held against the plain reference
(``bench.reference``) following the same rounds.
"""

from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as ref

#: the trainer's wire names for the traffic's samplers
COMPRESSOR = {"qsgd": "block_qsgd", "randk": "block_randk"}

#: calls of the window's entry that set-up drives and the reference follows
CHECK_ROUNDS = 3

#: logits one block of the reference's rows may hold (positions x vocabulary),
#: so that its backward pass fits the chip once the program's state is freed
REFERENCE_LOGITS = 10 * 2**20


def _program_config(arch: str, m: ref.Model):
    """The program's model configuration at the file's depth, refused where
    it differs from the file in any size the reference reads."""
    from repro.configs import get_arch
    from repro.models.config import dense_stack

    cfg = get_arch(arch).model
    cfg = dataclasses.replace(cfg, segments=dense_stack(m.num_hidden_layers))
    pairs = {
        "hidden_size": (cfg.d_model, m.hidden_size),
        "num_attention_heads": (cfg.num_heads, m.num_attention_heads),
        "num_key_value_heads": (cfg.num_kv_heads, m.num_key_value_heads),
        "head_dim": (cfg.resolved_head_dim, m.head_dim),
        "intermediate_size": (cfg.d_ff, m.intermediate_size),
        "vocab_size": (cfg.vocab_size, m.vocab_size),
        "rms_norm_eps": (cfg.norm_eps, m.rms_norm_eps),
        "position_embedding": (cfg.pos_emb, m.position_embedding),
        "qkv_bias": (cfg.qkv_bias, m.qkv_bias),
        "tie_word_embeddings": (cfg.tie_embeddings, m.tie_word_embeddings),
    }
    if m.position_embedding == "rope":
        pairs["rope_theta"] = (cfg.rope_theta, m.rope_theta)
    bad = {k: v for k, v in pairs.items() if v[0] != v[1]}
    if bad:
        raise ValueError(f"program config {arch!r} differs from the file: {bad}")
    return cfg


@jax.jit
def _worker_leaf_norms(h):
    """(leaves, n) norms of a worker-stacked tree."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(t.reshape(t.shape[0], -1)), axis=1))
        for t in jax.tree.leaves(h)
    ])


def _buffer_leaf_norms(buf, sizes: tuple):
    """Norms of the leaves packed row-major, in flatten order, into ``buf``."""
    def f(b):
        flat, out, off = b.reshape(-1), [], 0
        for s in sizes:
            out.append(jnp.sqrt(jnp.sum(jnp.square(flat[off:off + s]))))
            off += s
        return jnp.stack(out)
    return jax.jit(f)(buf)


@jax.jit
def _change_norms(x, x0):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a - b)))
                      for a, b in zip(jax.tree.leaves(x), jax.tree.leaves(x0))])


@dataclasses.dataclass
class Readings:
    """What the first rounds produced, per round: gradient norms (leaves, n),
    estimator norms (leaves,), the bit ledger; the change after them; and,
    from the reference, the norms of the first full gradient (leaves,)."""

    grad: list = dataclasses.field(default_factory=list)
    estimator: list = dataclasses.field(default_factory=list)
    bits: list = dataclasses.field(default_factory=list)
    change: np.ndarray | None = None
    first_grad: np.ndarray | None = None


class Session:
    """One trainer, its carry, and the readings of its first rounds."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.m = ref.Model.from_config(config)
        self.traffic = traffic
        # the weights come from the run's seed (PRNGKey takes 32 bits; a
        # larger seed maps to one below 2**32). The token stream and the
        # round coins come from the traffic's stream seed: the trainer bakes
        # that seed into its compiled round, so a seed of the run's own would
        # recompile the round in every run.
        self.seed = seed % 2**32
        self.stream_seed = traffic["stream_seed"]
        wire = traffic["wire"]
        self.n = traffic["n_workers"]
        self.batch = traffic["batch_per_worker"]
        self.seq = traffic["seq_len"]
        self.rounds_per_call = traffic["rounds_per_call"]
        self.gamma = traffic["gamma"]
        self.sampler, self.block, self.level = (
            wire["sampler"], wire["block"], wire["level"])
        self.arch = config["program_arch"]
        self.calls = 0

    # -- set-up -----------------------------------------------------------
    def build(self):
        """Make the weights and the trainer, and initialise MARINA's state."""
        from repro.models import init_params
        from repro.train import TrainConfig, Trainer

        cfg = _program_config(self.arch, self.m)
        self.weights = ref.make_weights(self.m, self.seed)
        want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        got = jax.tree.map(lambda t: (t.shape, t.dtype), self.weights)
        if jax.tree.structure(want) != jax.tree.structure(self.weights) or (
            jax.tree.leaves(jax.tree.map(lambda t: (t.shape, t.dtype), want),
                            is_leaf=lambda t: isinstance(t, tuple))
            != jax.tree.leaves(got, is_leaf=lambda t: isinstance(t, tuple))
        ):
            raise ValueError("the weight tree does not match the program's")
        kw = {"s": self.level} if self.sampler == "qsgd" else {"kb": self.level}
        tc = TrainConfig(
            method=self.traffic["method"], compressor=COMPRESSOR[self.sampler],
            comp_kwargs={**kw, "block": self.block},
            carry_grads=self.traffic["carry_grads"], n_workers=self.n,
            batch_per_worker=self.batch, gamma=self.gamma, seed=self.stream_seed,
            log_every=self.rounds_per_call, steps=self.rounds_per_call,
            nonfinite_guard=self.traffic["nonfinite_guard"],
        )
        tr = Trainer(cfg, tc, self.weights, prefix_len=self.m.prefix_len)
        if tr.data.seq_len != self.seq:
            raise ValueError(f"trainer rows have {tr.data.seq_len} tokens, "
                             f"the traffic {self.seq}")
        self.tr = tr
        b0 = jax.jit(lambda: tr._batches(0, self.batch))()
        state = jax.jit(tr.method.init)(self.weights, b0)
        del b0
        zeros = [jnp.zeros((), jnp.float32) for _ in range(4)]
        self.carry = (state, *zeros)
        self.sizes = tuple(int(np.prod(s.shape)) for s in jax.tree.leaves(self.weights))

    def dispatch(self):
        """One call of the window's entry; returns what to wait on."""
        R = self.rounds_per_call
        steps = np.arange(self.calls * R, (self.calls + 1) * R, dtype=np.int32)
        self.carry, met = self.tr._jitted_chunk(self.carry, steps)
        self.calls += 1
        return met

    def first_rounds(self) -> Readings:
        """Drive the first ``CHECK_ROUNDS`` calls and read each one."""
        r = Readings()
        for _ in range(CHECK_ROUNDS):
            jax.block_until_ready(self.dispatch())
            st = self.carry[0]
            r.grad.append(np.asarray(_worker_leaf_norms(st.h), np.float64))
            r.estimator.append(np.asarray(
                _buffer_leaf_norms(st.g, self.sizes), np.float64))
            r.bits.append(np.float32(self.carry[1]))
        r.change = np.asarray(
            _change_norms(self.carry[0].params, self.weights), np.float64)
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
        return float(self.carry[4])

    def uplink_bits(self) -> float:
        """Bits the program's ledger books for one compressed round."""
        return float(self.tr.engine.payload_bits(self.n))

    def release(self):
        """Free the program's state; the weights stay for the reference."""
        del self.carry, self.tr
        gc.collect()

    # -- the reference ----------------------------------------------------
    def reference(self, dtype=jnp.float32, fault=None) -> Readings:
        """The same first rounds from the plain reference."""
        mar = ref.Marina(
            self.m, self.sampler, self.block, self.level, self.n, self.batch,
            self.seq, self.gamma, self.stream_seed, dtype=dtype, fault=fault,
            rows=max(1, REFERENCE_LOGITS // ((self.seq + self.m.prefix_len)
                                             * self.m.vocab_size)),
        )
        r = Readings()
        st = mar.init(self.weights)
        r.first_grad = ref.leaf_norms(st["g"])
        bits = np.float32(0.0)
        for k in range(CHECK_ROUNDS):
            st = mar.round(st, k)
            r.grad.append(np.stack([ref.leaf_norms(h) for h in st["h"]], axis=1))
            r.estimator.append(ref.leaf_norms(st["g"]))
            bits = bits + np.float32(32.0 * mar.d if mar.coin(k) else ref.wire_bits(
                self.sampler, mar.d, self.block, self.level))
            r.bits.append(bits)
        r.change = np.asarray(_change_norms(st["x"], self.weights), np.float64)
        del st
        gc.collect()
        return r
