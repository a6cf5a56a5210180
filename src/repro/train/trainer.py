"""Training loop wiring the MARINA family into LM training.

The trainer runs the *simulation backend* (worker-stacked trees on one device;
the same algorithm code as the mesh path — see launch/distributed.py for the
sharded production step). It owns:

* method construction (MARINA / VR-MARINA / PP-MARINA / DIANA / DCGD / EC-SGD /
  GD) with compressor + stepsize policy — ``block_randk``/``flat_randk`` and
  ``permk`` compressors additionally get the fused flat-buffer engine
  (DESIGN.md §4; correlated collections are sized to ``n_workers``),
* the per-step data plumbing (full-round batches vs b′ minibatches — the
  Alg. 3 online case), generated *inside the jitted scan* from the step index
  (the synthetic pipeline is a pure function of (seed, step)),
* a communication ledger in *bits actually uplinked* (the paper's x-axis in
  Figs. 1–2), accumulated on device,
* periodic eval loss, checkpointing, metrics history.

Hot-path discipline: the loop is a ``jax.lax.scan`` over chunks of
``log_every`` steps with the carry donated (``donate_argnums``), so the host
dispatches one fused computation — and syncs exactly once — per log interval
instead of every step.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import load_checkpoint, latest_step, save_checkpoint
from repro.core import (
    DCGD,
    Diana,
    ECSGD,
    BlockNatural,
    BlockQSGD,
    BlockRandK,
    CorrelatedCompressor,
    FaultSpec,
    Marina,
    PermK,
    PPMarina,
    ServerAggregator,
    VRMarina,
    diana_alpha,
    make_compressor,
    make_downlink,
    make_engine,
    tree_dim,
    tree_omega,
)
from repro.data import HeterogeneousLMData, make_prefix_embeddings, worker_batches
from repro.models import lm_loss
from repro.models.config import ModelConfig
from repro.tracing import op_stages, span, stage

PyTree = Any


@dataclasses.dataclass
class TrainConfig:
    method: str = "vr_marina"          # marina|vr_marina|pp_marina|diana|dcgd|ec_sgd|gd
    compressor: str = "randk"
    comp_kwargs: dict = dataclasses.field(default_factory=lambda: {"k": 0.01})
    gamma: float = 0.05
    p: Optional[float] = None          # None → ζ_Q/d (Cor. 2.1)
    n_workers: int = 4
    batch_per_worker: int = 8          # b  (sync rounds / full batches)
    mb_per_worker: int = 2             # b' (compressed rounds)
    r_participating: int = 2           # PP-MARINA cohort size r
    # PP-MARINA federated dials (DESIGN.md §4.8): cohort scheme (Alg. 4
    # samples with replacement; False = the experiments' distinct-client
    # variant) and optional client weights for unbalanced local datasets
    # (array-like of length n_workers; raw sample counts are fine —
    # PPMarina normalizes to Σw_i = 1 at construction).
    pp_replace: bool = True
    pp_weights: Optional[Any] = None
    # Dirichlet non-IID dial for the LM data (None → legacy heterogeneity
    # scalar): alpha=0.1 gives near-single-region clients, np.inf iid —
    # so any config can run the federated scenario, e.g.
    # TrainConfig(method="pp_marina", n_workers=64, r_participating=8,
    # alpha=0.1).
    alpha: Optional[float] = None
    steps: int = 100
    seed: int = 0
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0
    diana_alpha: Optional[float] = None
    flat_backend: str = "auto"         # kernel backend for the flat engine
    # gradient-carry rounds (DESIGN.md §4.7): one backprop per round; with a
    # flat engine the round ends in the fused epilogue kernel. marina /
    # vr_marina only.
    carry_grads: bool = False
    # compressed downlink: compressor/sampler name for Q_down(g^{k+1} − g^k)
    # ("qsgd" | "randk" | "natural" | None = dense broadcast). With a flat
    # engine the name selects the downlink engine's sampler; on the per-leaf
    # tree path it is a make_compressor name.
    downlink: Optional[str] = None
    downlink_kwargs: dict = dataclasses.field(default_factory=dict)
    # Byzantine-robust server aggregation + client fault injection
    # (DESIGN.md §4.9). aggregator is a GAR name (repro.core.aggregators.RULES)
    # with aggregator_f the assumed Byzantine count; faults/faults_frac/
    # faults_scale build a FaultSpec. marina-family only; "mean"/"none" keep
    # the seed trajectory bit-identical.
    aggregator: str = "mean"
    aggregator_f: int = 0
    faults: str = "none"
    faults_frac: float = 0.0
    faults_scale: float = 1.0
    # Non-finite round guard: when a step produces any NaN/inf in the new
    # state (params or estimator), revert the whole state to the pre-step
    # value and count the round in TrainMetrics.skipped_cum. Bits are still
    # booked (the wire traffic happened; the server just refused the update).
    nonfinite_guard: bool = True


@dataclasses.dataclass
class TrainMetrics:
    step: list = dataclasses.field(default_factory=list)
    loss: list = dataclasses.field(default_factory=list)
    grad_est_norm: list = dataclasses.field(default_factory=list)
    bits_cum: list = dataclasses.field(default_factory=list)
    down_cum: list = dataclasses.field(default_factory=list)
    oracle_cum: list = dataclasses.field(default_factory=list)
    skipped_cum: list = dataclasses.field(default_factory=list)


@stage("trainer.guard")
def _state_finite(state: PyTree) -> jax.Array:
    """Scalar bool: every floating leaf of the optimizer state (params,
    estimator g, carried h, …) is all-finite. The non-finite round guard's
    predicate — one traced reduction, no host sync."""
    checks = [
        jnp.all(jnp.isfinite(leaf))
        for leaf in jax.tree.leaves(state)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact)
    ]
    if not checks:
        return jnp.asarray(True)
    return jnp.all(jnp.stack(checks))


class Trainer:
    def __init__(
        self,
        model_cfg: ModelConfig,
        train_cfg: TrainConfig,
        init_params: PyTree,
        prefix_len: int = 0,
    ):
        self.mcfg = model_cfg
        self.tcfg = train_cfg
        self.prefix_len = prefix_len
        self.data = HeterogeneousLMData(
            n_workers=train_cfg.n_workers,
            vocab_size=model_cfg.vocab_size,
            seq_len=128 if model_cfg.num_layers <= 4 else 256,
            seed=train_cfg.seed,
            alpha=train_cfg.alpha,
        )
        self._prefix_key = jax.random.PRNGKey(train_cfg.seed + 7)

        def loss_fn(params, batch):
            tokens = batch["tokens"]
            prefix = batch.get("prefix")
            return lm_loss(params, model_cfg, tokens, prefix)

        self.loss_fn = loss_fn
        grad_fn = jax.grad(loss_fn)

        d = tree_dim(init_params)
        comp = make_compressor(train_cfg.compressor, **train_cfg.comp_kwargs)
        if isinstance(comp, CorrelatedCompressor) and comp.n == 0:
            # correlated collections are sized by the worker fleet
            comp = dataclasses.replace(comp, n=train_cfg.n_workers)
        p = train_cfg.p if train_cfg.p is not None else comp.default_p(d)
        self.p = p
        self.comp = comp
        # block_randk / permk / block_qsgd / block_natural rounds run fused
        # over the packed flat buffer (the quantized ones on the bit-packed
        # wire, so the bits ledger books the packed accounting — wire.py);
        # every other compressor keeps the per-leaf tree path.
        if isinstance(comp, BlockRandK):
            self.engine = make_engine(
                init_params, kb=comp.kb, block=comp.block,
                backend=train_cfg.flat_backend,
            )
        elif isinstance(comp, PermK):
            self.engine = make_engine(
                init_params, block=comp.block,
                backend=train_cfg.flat_backend, sampler="permk",
            )
        elif isinstance(comp, BlockQSGD):
            self.engine = make_engine(
                init_params, block=comp.block,
                backend=train_cfg.flat_backend, sampler="qsgd", s=comp.s,
            )
        elif isinstance(comp, BlockNatural):
            self.engine = make_engine(
                init_params, block=comp.block,
                backend=train_cfg.flat_backend, sampler="natural",
            )
        else:
            self.engine = None

        # compressed downlink (DESIGN.md §4.7): with a flat engine the
        # downlink is a second engine sharing the uplink layout (the name is
        # the sampler); on the per-leaf path it is a tree compressor. Either
        # way the ledger books wire.py accounting for the broadcast.
        self.down_engine = None
        self.down_comp = None
        if train_cfg.downlink is not None:
            dkw = dict(train_cfg.downlink_kwargs)
            if self.engine is not None:
                name = train_cfg.downlink.removeprefix("block_")
                assert name in ("randk", "qsgd", "natural"), (
                    f"downlink {train_cfg.downlink!r} is not broadcastable "
                    "(permk partitions across receivers)"
                )
                self.down_engine = make_downlink(
                    self.engine, sampler=name,
                    kb=dkw.get("kb"), s=dkw.get("s"),
                )
            else:
                self.down_comp = make_compressor(train_cfg.downlink, **dkw)

        m = train_cfg.method
        # robust aggregation / fault dials (DESIGN.md §4.9): None when the
        # config is the honest default so the seed trajectory stays
        # bit-identical (the optimizers also guarantee this for the explicit
        # "mean"/"none" instances, but None skips the dial entirely).
        agg = (
            ServerAggregator(train_cfg.aggregator, f=train_cfg.aggregator_f)
            if train_cfg.aggregator != "mean"
            else None
        )
        fspec = (
            FaultSpec(
                train_cfg.faults,
                frac=train_cfg.faults_frac,
                scale=train_cfg.faults_scale,
            )
            if train_cfg.faults != "none"
            else None
        )
        if (agg is not None or fspec is not None) and m not in (
            "marina", "vr_marina", "pp_marina"
        ):
            raise ValueError(
                f"aggregator/faults are marina-family dials, not {m!r}"
            )
        if train_cfg.carry_grads and m not in (
            "marina", "vr_marina", "pp_marina"
        ):
            raise ValueError(f"carry_grads is a marina-family mode, not {m!r}")
        if train_cfg.downlink is not None and m not in (
            "marina", "vr_marina", "pp_marina"
        ):
            # refuse rather than silently broadcast dense while the user
            # believes the downlink is compressed
            raise ValueError(
                f"downlink is a marina-family mode, not {m!r}"
            )
        if m == "marina":
            self.method = Marina(
                grad_fn, comp, train_cfg.gamma, p, self.engine,
                carry=train_cfg.carry_grads,
                down_compressor=self.down_comp, down_engine=self.down_engine,
                aggregator=agg, faults=fspec,
            )
        elif m == "gd":
            from repro.core import make_gd

            self.method = make_gd(grad_fn, train_cfg.gamma)
        elif m == "vr_marina":
            self.method = VRMarina(
                grad_fn, grad_fn, comp, train_cfg.gamma, p, self.engine,
                carry=train_cfg.carry_grads,
                down_compressor=self.down_comp, down_engine=self.down_engine,
                aggregator=agg, faults=fspec,
            )
        elif m == "pp_marina":
            self.method = PPMarina(
                grad_fn, comp, train_cfg.gamma, p, train_cfg.r_participating,
                self.engine,
                down_compressor=self.down_comp, down_engine=self.down_engine,
                replace=train_cfg.pp_replace,
                weights=(
                    None if train_cfg.pp_weights is None
                    else jnp.asarray(train_cfg.pp_weights, jnp.float32)
                ),
                carry=train_cfg.carry_grads,
                aggregator=agg, faults=fspec,
            )
        elif m == "diana":
            alpha = train_cfg.diana_alpha
            if alpha is None:
                # the per-leaf lifted compressor's worst-leaf ω, NOT ω of the
                # total tree dimension: for absolute-k compressors (RandK(64))
                # the true per-leaf ω is far below d/k − 1, and an α from the
                # inflated ω would be needlessly tiny (slow shift learning).
                alpha = (
                    diana_alpha(max(tree_omega(comp, init_params), 1e-9))
                    if comp.unbiased
                    else 0.5
                )
            self.method = Diana(
                grad_fn, comp, train_cfg.gamma, alpha, train_cfg.n_workers
            )
        elif m == "dcgd":
            self.method = DCGD(grad_fn, comp, train_cfg.gamma, train_cfg.n_workers)
        elif m == "ec_sgd":
            self.method = ECSGD(grad_fn, comp, train_cfg.gamma, train_cfg.n_workers)
        else:
            raise ValueError(f"unknown method {m!r}")

        self.params0 = init_params
        self._jitted_step = jax.jit(self._step)
        # chunked hot loop: one dispatch + one host sync per log interval.
        # carry = (state, bits, down, oracle); donated so params/g (and the
        # carried h) update in place.
        self._jitted_chunk = jax.jit(self._chunk, donate_argnums=(0,))

    # ------------------------------------------------------------------
    @stage("trainer.data")
    def _batches(self, step: int, per_worker: int):
        toks = worker_batches(self.data, step, per_worker)
        batch = {"tokens": toks}
        if self.prefix_len:
            batch["prefix"] = make_prefix_embeddings(
                jax.random.fold_in(self._prefix_key, step),
                self.tcfg.n_workers,
                per_worker,
                self.prefix_len,
                self.mcfg.d_model,
            )
        return batch

    def _step(self, state, key, full_b, mb_b):
        m = self.tcfg.method
        if m in ("marina", "gd", "pp_marina", "diana", "dcgd", "ec_sgd"):
            return self.method.step(state, key, full_b)
        return self.method.step(state, key, full_b, mb_b)

    def _chunk(self, carry, steps):
        """Scan `len(steps)` optimizer steps on device.

        Batches are regenerated inside the trace from the step index (the
        data pipeline is a pure function of (seed, step)), and the bits /
        down-bits / oracle ledgers accumulate in the carry — no per-step host
        sync. Returns the final carry and the last step's metrics.

        With ``nonfinite_guard`` (the default), a step whose new state holds
        any NaN/inf — e.g. a ``nan``-attack round hitting a mean aggregator —
        is *skipped*: the whole state reverts to its pre-step value (one bad
        round must not poison the MARINA recursion forever) and the skipped
        ledger increments. Bits/oracle still accumulate: the traffic and the
        compute happened; only the server-side update was refused.
        """
        base_key = jax.random.PRNGKey(self.tcfg.seed)

        def body(c, step):
            state, bits, down, oracle, skipped = c
            with stage("trainer.data"):
                key = jax.random.fold_in(base_key, step)
            full_b = self._batches(step, self.tcfg.batch_per_worker)
            mb_b = self._batches(10**7 + step, self.tcfg.mb_per_worker)
            new_state, met = self._step(state, key, full_b, mb_b)
            if self.tcfg.nonfinite_guard:
                ok = _state_finite(new_state)
                # revert the ENTIRE state on a bad round — a finite-looking
                # h/g paired with reverted params would desynchronize the
                # estimator recursion.
                with stage("trainer.guard"):
                    new_state = jax.tree.map(
                        lambda new, old: jnp.where(ok, new, old), new_state,
                        state,
                    )
                    met = met._replace(
                        grad_est_norm=jnp.where(ok, met.grad_est_norm, 0.0)
                    )
                    skipped = skipped + jnp.where(ok, 0.0, 1.0)
            return (
                new_state,
                bits + met.bits_per_worker,
                down + met.down_bits,
                oracle + met.oracle_calls,
                skipped,
            ), met

        carry, mets = jax.lax.scan(body, carry, steps)
        last_met = jax.tree.map(lambda a: a[-1], mets)
        return carry, last_met

    def chunk_stages(self, carry, steps) -> dict:
        """``{instruction: stage}`` of the chunk compiled for these
        arguments (:func:`repro.tracing.op_stages`): names every device
        operation of a traced chunk by its stage. Where the chunk already ran
        with the persistent compilation cache on, this is a cache load."""
        return op_stages(
            self._jitted_chunk.lower(carry, steps).compile().as_text()
        )

    def eval_loss(self, params, step: int = 10**6) -> float:
        b = self._batches(step, self.tcfg.batch_per_worker)
        losses = jax.vmap(self.loss_fn, in_axes=(None, 0))(params, b)
        return float(jnp.mean(losses))

    # ------------------------------------------------------------------
    def _boundaries(self, start: int) -> list:
        """Host-sync points: steps after which we must look at the state
        (log/eval) or serialize it (checkpoint). The device runs free
        between consecutive boundaries."""
        tc = self.tcfg
        # log after every log_every-th step and always after the final step.
        # Chunks between consecutive log points are uniform (log_every steps)
        # so the scan compiles once for them; a ragged final chunk — and any
        # ckpt point not aligned to the log grid — adds one extra compile per
        # distinct length.
        log_pts = {
            s for s in range(start, tc.steps) if (s + 1) % tc.log_every == 0
        }
        log_pts.add(tc.steps - 1)
        ckpt_pts = set()
        if tc.ckpt_dir and tc.ckpt_every:
            ckpt_pts = {
                s for s in range(start, tc.steps) if (s + 1) % tc.ckpt_every == 0
            }
        pts = sorted(p for p in log_pts | ckpt_pts if start <= p < tc.steps)
        return [(p, p in log_pts, p in ckpt_pts) for p in pts]

    def run(self) -> tuple[PyTree, TrainMetrics]:
        tc = self.tcfg
        b0 = self._batches(0, tc.batch_per_worker)
        if tc.method in ("diana", "dcgd", "ec_sgd"):
            state = self.method.init(self.params0)
        else:
            state = self.method.init(self.params0, b0)

        start = 0
        bits = 0.0
        down = 0.0
        oracle = 0.0
        skipped = 0.0
        if tc.ckpt_dir:
            s = latest_step(tc.ckpt_dir)
            if s is not None:
                with span("trainer.restore"):
                    # the communication/oracle ledgers resume WITH the state
                    # (which includes the carried h_i^k in carry mode): a restart
                    # that zeroes them silently shifts every resumed loss-vs-bits
                    # curve (the Fig. 1/2 x-axis) left. A corrupt file raises
                    # CheckpointCorruptionError from load_checkpoint — NOT caught
                    # by the KeyError format tiers below.
                    like = {
                        "state": state,
                        "bits": np.zeros((), np.float32),
                        "down": np.zeros((), np.float32),
                        "oracle": np.zeros((), np.float32),
                        "skipped": np.zeros((), np.float32),
                    }
                    try:
                        ck = load_checkpoint(tc.ckpt_dir, s, like)
                        state = ck["state"]
                        bits = float(ck["bits"])
                        down = float(ck["down"])
                        oracle = float(ck["oracle"])
                        skipped = float(ck["skipped"])
                    except KeyError:
                        try:
                            # pre-guard checkpoint: no skipped-rounds ledger.
                            del like["skipped"]
                            ck = load_checkpoint(tc.ckpt_dir, s, like)
                            state = ck["state"]
                            bits = float(ck["bits"])
                            down = float(ck["down"])
                            oracle = float(ck["oracle"])
                        except KeyError:
                            try:
                                # pre-downlink checkpoint: bits/oracle only.
                                del like["down"]
                                ck = load_checkpoint(tc.ckpt_dir, s, like)
                                state = ck["state"]
                                bits = float(ck["bits"])
                                oracle = float(ck["oracle"])
                            except KeyError:
                                # pre-ledger checkpoint (bare state tree): resume
                                # the iterates and accept zeroed ledgers rather
                                # than refuse the directory outright.
                                state = load_checkpoint(tc.ckpt_dir, s, state)
                    start = s + 1

        # the chunk carry is donated; copy so self.params0 (aliased into the
        # initial state) survives for eval or a second run().
        state = jax.tree.map(jnp.array, state)

        hist = TrainMetrics()

        # anchor the loss-vs-bits curve at the pre-training state (step
        # start−1, 0 bits uplinked): the uniform chunking below only logs
        # after full log intervals, and the Fig. 1/2-style curves need the
        # initial point.
        from repro.core.tree_util import tree_norm

        hist.step.append(start - 1)
        with span("trainer.eval"):
            hist.loss.append(self.eval_loss(state.params, start))
        hist.grad_est_norm.append(
            float(tree_norm(state.g)) if hasattr(state, "g") else 0.0
        )
        hist.bits_cum.append(bits)
        hist.down_cum.append(down)
        hist.oracle_cum.append(oracle)
        hist.skipped_cum.append(skipped)

        prev = start
        for bound, is_log, is_ckpt in self._boundaries(start):
            # one fused device dispatch for steps [prev, bound]; the bits /
            # down-bits / oracle / skipped ledgers accumulate on device, read
            # back once per chunk.
            with span("trainer.chunk", step_num=bound):
                steps_arr = jnp.arange(prev, bound + 1, dtype=jnp.int32)
                # four distinct zero buffers: the chunk carry is donated, and
                # donating one buffer several times is an XLA error
                zeros = [jnp.zeros((), jnp.float32) for _ in range(4)]
                (state, chunk_bits, chunk_down, chunk_oracle, chunk_skip), met = (
                    self._jitted_chunk((state, *zeros), steps_arr)
                )
                bits += float(chunk_bits)
                down += float(chunk_down)
                oracle += float(chunk_oracle)
                skipped += float(chunk_skip)
            prev = bound + 1

            if is_log:
                with span("trainer.eval"):
                    loss = self.eval_loss(state.params, bound)
                hist.step.append(bound)
                hist.loss.append(loss)
                hist.grad_est_norm.append(float(met.grad_est_norm))
                hist.bits_cum.append(bits)
                hist.down_cum.append(down)
                hist.oracle_cum.append(oracle)
                hist.skipped_cum.append(skipped)
            if is_ckpt:
                with span("trainer.checkpoint"):
                    save_checkpoint(
                        tc.ckpt_dir,
                        bound,
                        {
                            "state": state,
                            "bits": np.float32(bits),
                            "down": np.float32(down),
                            "oracle": np.float32(oracle),
                            "skipped": np.float32(skipped),
                        },
                    )
        return state, hist
