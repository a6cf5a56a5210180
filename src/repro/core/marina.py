"""MARINA, VR-MARINA and PP-MARINA (Algorithms 1–4 of the paper).

The algorithms are written against *worker-stacked* pytrees: every per-worker
quantity (minibatch, payload, shift) carries a leading axis of size ``n``. On a
single device this leading axis is a plain vmap dimension; on a mesh the launcher
shards it over the worker mesh axes, so the same code runs in both the CPU
simulation used by tests/examples and the multi-pod production path
(see launch/distributed.py for the sharded LM instantiation that additionally
annotates model-parallel dimensions).

Faithfulness notes
------------------
* ``c_k ~ Be(p)`` is shared across workers (Alg. 1 line 4): a scalar drawn from the
  step key, applied through ``lax.cond``.
* ``g^0 = ∇f(x^0)`` exactly (Alg. 1 line 2) — init computes the full gradient.
* Compressed rounds evaluate gradients at *both* points on the *same* minibatch
  (Alg. 2 line 8); we recompute at the old point instead of storing a second full
  gradient (PAGE-style; DESIGN.md §3).
* Compressor randomness is independent across workers (the n-fold key split),
  which is what gives the 1/n variance averaging in Thm 2.1's proof (eq. 21).
  ``SharedRandK`` deliberately breaks this for the §Perf communication experiment.

Beyond-paper round engineering (DESIGN.md §4.7)
-----------------------------------------------
* ``carry=True`` — *gradient-carry rounds*: the state additionally carries the
  per-worker gradients ``h_i^k = ∇f_i(x^k)`` that the previous round already
  computed, so a compressed round runs ONE backprop (at x^{k+1}) instead of
  two; the difference Δ_i = ∇f_i(x^{k+1}) − h_i^k is bit-identical to the
  recompute-at-the-old-point path whenever the local gradient oracle is
  deterministic in the iterate (fixed local datasets — the Alg. 1/2 regime).
  In the online Alg. 3 regime (fresh minibatch per round) the carry replaces
  the same-minibatch correlation with last round's realization; this is a
  different (higher-variance) estimator, so the flag is opt-in. Carry states
  are *lookahead*: the stored params are already stepped (x^{k+1} after init,
  x^{k+2} after step k), which is what lets the fused epilogue finish
  ``g += δ`` and ``x −= γ·g`` in one sweep; ``g`` sequences coincide with the
  seed estimator step for step, and params lead by exactly one step.
* With an engine, a carry round ends in the fused epilogue kernel
  (kernels/epilogue.py): dequant/scatter-mean of the payloads, the estimator
  update and the iterate update in a single (nblk, B)-tile HBM sweep, and the
  carried ``h`` / estimator ``g`` live as packed flat buffers
  ((n, nblk, B) / (nblk, B)) rather than trees.
* ``down_compressor`` / ``down_engine`` — *compressed downlink* (Gruntkowska
  et al. 2024's bidirectional program on DIANA-style shifts): on compressed
  rounds the server broadcasts Q_down(g^{k+1} − g^k) = Q_down(δ_up) instead
  of the dense estimator, and every worker decompress-accumulates; since the
  recursion runs on the single broadcast estimator, unbiased Q_down composes
  with the uplink as (1+ω_down)(1+ω_up/n) − 1. Sync rounds broadcast dense
  (32d down-bits), mirroring the Bernoulli structure in both directions.
  ``StepMetrics.down_bits`` books the per-worker received bits every round —
  the dense 32d broadcast that the seed ledger silently ignored is now
  counted even when no downlink compressor is configured.
* ``PPMarina`` (Alg. 4) additionally carries the federated scenario dials
  (DESIGN.md §4.8): without-replacement cohorts, arbitrary client weights,
  and an opt-in *server-side carry table* (h per client, refreshed only for
  sampled clients) that lets PP rounds run one backprop per sampled client
  and end in the fused epilogue; its ledger books the fleet totals n·32d /
  r·ζ_Q from :mod:`repro.core.wire`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.tracing import stage

from .compressors import (
    Compressor,
    CorrelatedCompressor,
    Identity,
    SharedRandK,
    tree_compress,
    tree_compress_worker,
    tree_decompress,
    tree_dim,
    tree_payload_bits,
)
from . import faults as fault_lib
from .flat import FlatEngine, pack, pack_stacked, unpack
from .tree_util import (
    tree_axpy,
    tree_mean_axis0,
    tree_norm,
    tree_scale,
    tree_sub,
)

PyTree = Any
GradFn = Callable[[PyTree, PyTree], PyTree]  # (params, batch) -> grad tree

#: fold_in constant deriving the downlink key from the step key WITHOUT
#: perturbing the (k_bern, k_q) split — carry/downlink rounds must draw the
#: same uplink randomness as the seed estimator for bit-exact trajectories.
_DOWN_FOLD = 0x0D0C

#: fold_in constant deriving the fault-injection key (garbage payload noise)
#: from the step key — like _DOWN_FOLD, it must not perturb the
#: (k_bern, k_sel, k_q) split so faulted and honest runs share their
#: Bernoulli/cohort/compressor randomness (only the payloads differ).
_FAULT_FOLD = 0xFA17

class StepMetrics(NamedTuple):
    grad_est_norm: jax.Array      # ‖g^k‖ (the estimator driving the step)
    bits_per_worker: jax.Array    # bits uplinked by one worker this round
    sync_round: jax.Array         # c_k (1 = dense round)
    oracle_calls: jax.Array       # stochastic first-order oracle calls per worker
    down_bits: jax.Array = 0.0    # bits each worker RECEIVES this round


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MarinaState:
    params: PyTree
    g: PyTree          # server estimator g^k, replicated ((nblk, B) flat
                       # buffer in the fused carry path, tree otherwise)
    step: jax.Array
    h: Optional[PyTree] = None  # carry mode: per-worker ∇f_i(x^k), a
                                # worker-stacked tree, in tree form even on
                                # the fused path (pack_stacked still
                                # materializes the (n, rows, B) difference)


def _round_keys(key: jax.Array, p: float, parts: int = 2):
    """The round's randomness from its key: the coin c_k ~ Bernoulli(p)
    (1 = sync round) from the first of ``parts`` split keys, the other split
    keys (``k_q``, or ``k_sel, k_q``), and the downlink and fault keys folded
    from ``key`` without perturbing the split."""
    with stage("marina.coin"):
        k_bern, *rest = jax.random.split(key, parts)
        c_k = jax.random.bernoulli(k_bern, p)
        return (c_k, rest, jax.random.fold_in(key, _DOWN_FOLD),
                jax.random.fold_in(key, _FAULT_FOLD))


@stage("marina.backprop")
def _per_worker_grads(grad_fn: GradFn, params: PyTree, batches: PyTree) -> PyTree:
    """∇f_i at params for every worker: vmap over the leading worker axis."""
    return jax.vmap(grad_fn, in_axes=(None, 0))(params, batches)


def _compress_workers(
    comp: Compressor, key: jax.Array, diffs: PyTree, n: int
) -> PyTree:
    """Compress each worker's difference tree. Independent keys per worker,
    except SharedRandK which reuses one key (correlated masks by design) and
    CorrelatedCompressor collections (PermK, CorrelatedQ), where ALL workers
    share the round key and receive their index — the shared randomness is
    what buys the (A, B) constants (Szlendak et al. 2021)."""
    if isinstance(comp, CorrelatedCompressor):
        # a mismatched fleet is not an error the math survives: extra wids
        # alias back onto the first shards (mask wraparound) and the mean
        # silently double-counts them — refuse loudly instead.
        assert n == comp.n, (
            f"{comp.name} collection sized for n={comp.n} but the round has "
            f"{n} workers"
        )
        wids = jnp.arange(n, dtype=jnp.int32)
        return jax.vmap(
            lambda w, t: tree_compress_worker(comp, key, t, w)
        )(wids, diffs)
    if isinstance(comp, SharedRandK):
        keys = jnp.broadcast_to(key, (n, *key.shape))
    else:
        keys = jax.random.split(key, n)
    return jax.vmap(partial(tree_compress, comp))(keys, diffs)


def _decompress_mean(comp: Compressor, payloads: PyTree, like: PyTree, n: int) -> PyTree:
    """Server aggregation: decompress all n payloads, average (Alg. 1 line 10).

    Per-leaf reference path: densifies all n payloads to an (n, d) tree before
    averaging. The production compressed round goes through the flat engine
    (:func:`_compressed_delta`), which aggregates by scatter-accumulate and
    never materializes the (n, d) trees (DESIGN.md §4)."""
    dense = jax.vmap(lambda p: tree_decompress(comp, p, like))(payloads)
    return tree_mean_axis0(dense)


def _compressed_delta(
    comp: Compressor,
    engine: "FlatEngine | None",
    key: jax.Array,
    diffs: PyTree,
    like: PyTree,
    n: int,
    aggregator=None,
) -> PyTree:
    """One compressed uplink round: (1/n) Σ_i Q(Δ_i).

    With an engine: the fused flat-buffer pipeline (pack → sampler →
    aggregate → unpack), cost ∝ ζ_Q. The sampler is the engine's: seeded
    RandK / PermK with scatter- or concat-mean, or the packed quantization
    wire (blockwise QSGD / natural / RandK∘QSGD, DESIGN.md §4.6) whose
    aggregation is the fused dequantize-and-mean at int8 input bandwidth.
    Without: the per-leaf tree path (reference semantics, cost ∝ n·d).
    A robust ``aggregator`` (DESIGN.md §4.9) replaces the mean with its GAR
    over the per-worker decompressed payloads on either path."""
    if engine is not None:
        return engine.fused_delta(key, diffs, n, aggregator=aggregator)
    payloads = _compress_workers(comp, key, diffs, n)
    if _robust(aggregator):
        dense = jax.vmap(lambda p: tree_decompress(comp, p, like))(payloads)
        return aggregator.combine_stacked(dense)
    return _decompress_mean(comp, payloads, like, n)


def _down_roundtrip(
    down_comp: "Compressor | None",
    down_engine: "FlatEngine | None",
    key: jax.Array,
    delta: PyTree,
    like: PyTree,
) -> PyTree:
    """Compressed downlink on the aggregated round delta: the server
    broadcasts Q_down(δ_up) and every worker decompress-accumulates — since
    g^{k+1} − g^k = δ_up, this IS broadcasting the compressed estimator
    difference. Identity when no downlink is configured (dense broadcast)."""
    if down_engine is not None:
        return down_engine.roundtrip_worker(key, delta)
    if down_comp is not None:
        payload = tree_compress(down_comp, key, delta)
        return tree_decompress(down_comp, payload, like)
    return delta


def _round_bits(
    comp: Compressor, engine: "FlatEngine | None", like: PyTree, n: int = 1
):
    """Per-worker uplink bits of one compressed round (the paper's ζ_Q axis).

    ``n`` matters only for partition compressors (PermK): the per-worker
    payload is the d/n share, so the ledger needs the collection size."""
    if engine is not None:
        return jnp.asarray(engine.payload_bits(n))
    return jnp.asarray(tree_payload_bits(comp, like))


def _down_round_bits(
    down_comp: "Compressor | None",
    down_engine: "FlatEngine | None",
    like: PyTree,
    d: int,
):
    """Per-worker downlink bits of one compressed round: the compressed
    broadcast payload, or the dense 32d estimator when no downlink
    compression is configured (counted either way — DESIGN.md §4.7)."""
    from . import wire

    if down_engine is not None:
        return jnp.asarray(down_engine.payload_bits(1))
    if down_comp is not None:
        return jnp.asarray(tree_payload_bits(down_comp, like))
    return jnp.asarray(wire.downlink_dense_bits(d))


def _check_downlink_config(m) -> None:
    """The fused carry round consumes the downlink payload inside the
    epilogue kernel, which only speaks the flat wire formats — a per-leaf
    tree ``down_compressor`` cannot slot in there, and silently skipping it
    would book compressed down-bits for a dense broadcast. Refuse loudly."""
    if m.carry and m.engine is not None and (
        m.down_compressor is not None and m.down_engine is None
    ):
        raise ValueError(
            "carry=True with a flat engine needs a down_engine for the "
            "compressed downlink (make_downlink(engine, ...)); a per-leaf "
            "down_compressor only fits the tree paths"
        )


def _flat_sync_mean(engine: FlatEngine, grads: PyTree) -> PyTree:
    """Sync rounds ride the flat buffer: ONE fused mean over the packed
    (n, nblk, B) gradient buffer instead of a per-leaf tree exchange."""
    bufs = pack_stacked(engine.layout, grads)
    return unpack(engine.layout, jnp.mean(bufs, axis=0))


# ---------------------------------------------------------------------------
# Robust aggregation + fault injection plumbing (DESIGN.md §4.9)
# ---------------------------------------------------------------------------


def _robust(aggregator) -> bool:
    """True when a ServerAggregator with a non-mean rule is configured."""
    return aggregator is not None and aggregator.robust


def _check_robust_config(m) -> None:
    """Refuse GAR/wire/fault combinations whose semantics are undefined.

    Coordinate-wise (and row-score) GARs need per-worker payloads that are
    comparable coordinate by coordinate: correlated partition compressors
    (PermK et al.) give each coordinate to exactly ONE worker, so there is
    nothing to trim, median, score or clip — refuse rather than silently
    aggregate structure. Dropped clients are only recoverable when the
    server holds an anchor to substitute (``carry=True``'s h table: Δ̂_i = 0
    ⇔ reuse h_i); without a carry the recompute round would silently treat
    the drop as a zero *gradient*, which is a different (wrong) estimator.
    Client weights are a mean-specific concept (robust rules select/trim,
    they don't form convex combinations) — reject the pairing."""
    agg = getattr(m, "aggregator", None)
    if _robust(agg):
        if isinstance(m.compressor, CorrelatedCompressor):
            raise ValueError(
                f"robust rule {agg.rule!r} is undefined on the correlated "
                f"partition compressor {m.compressor.name}: each coordinate "
                "reaches the server from exactly one worker (DESIGN.md §4.9)"
            )
        if m.engine is not None and m.engine.sampler == "permk":
            raise ValueError(
                f"robust rule {agg.rule!r} is undefined on the permk engine "
                "wire: the workers partition the coordinates (DESIGN.md §4.9)"
            )
        if getattr(m, "weights", None) is not None:
            raise ValueError(
                "client weights only make sense for mean aggregation; "
                "robust GARs select/trim rows instead of weighting them"
            )
    flt = getattr(m, "faults", None)
    if flt is not None and flt.attack == "drop" and not m.carry:
        raise ValueError(
            "faults='drop' substitutes the server-side carry row h_i for "
            "the missing upload — carry=True is required (DESIGN.md §4.9); "
            f"construct {type(m).__name__}(..., carry=True) or drop the "
            "FaultSpec"
        )
    if flt is not None and flt.attack == "drop" and _robust(agg):
        # a zero payload row stands in for h_i ONLY under mean aggregation
        # (it contributes exactly h_i/n to the recursion); a GAR treats the
        # zero rows as candidate payloads and trims/medians/scores them —
        # a different, silently wrong estimator. Refuse at construction.
        raise ValueError(
            "faults='drop' relies on mean aggregation: the zero-row carry "
            f"substitution is not defined under the {agg.rule!r} GAR "
            "(DESIGN.md §4.9/§4.10) — use aggregator=None/mean with drop"
        )


def _sync_aggregate(engine, aggregator, grads, weights=None):
    """Sync-round server aggregation over the worker-stacked gradient tree:
    the GAR when a robust aggregator is configured, else the (flat-buffer)
    mean — weighted when client weights are set (mean only)."""
    if _robust(aggregator):
        return aggregator.combine_stacked(grads)
    if engine is not None and weights is None:
        return _flat_sync_mean(engine, grads)
    return _weighted_mean_axis0(grads, weights)


def _uplink_faults(faults, key, trees, ids, n):
    """Compressed-round payload faults on the worker-stacked diff tree:
    Byzantine attacks rewrite their rows; dropped rows zero (Δ̂_i = 0 is the
    carry-row substitution — the server's anchor h_i stands in)."""
    if faults is None:
        return trees
    if faults.attack == "drop":
        return fault_lib.zero_rows(trees, faults.byz_mask(ids, n))
    return fault_lib.inject(faults, key, trees, ids, n)


@stage("marina.diff")
def _uplink_diff(faults, key, new, old, ids, n, row_scale=None):
    """The compressed round's uplink difference ``new − old`` per worker
    (``grads − h`` on carry rounds), rows scaled by ``row_scale`` where
    given, with the round's payload faults."""
    diffs = tree_sub(new, old)
    if row_scale is not None:
        diffs = _scale_rows(diffs, row_scale)
    return _uplink_faults(faults, key, diffs, ids, n)


def _sync_faults(faults, key, trees, ids, n):
    """Sync-round payload faults: Byzantine attacks apply (liars lie on
    dense rounds too); ``drop`` does not — the sync round is the rendezvous
    every client attends (DESIGN.md §4.9 ledger rules)."""
    if faults is None:
        return trees
    return fault_lib.inject(faults, key, trees, ids, n)


def _uplink_bits_scale(faults, n) -> float:
    """Fraction of the fleet whose compressed upload actually arrived: the
    ledger books only real uploads, so drop rounds cost (n−f)/n of ζ_Q."""
    if faults is not None and faults.attack == "drop":
        return (n - faults.n_faulty(n)) / n
    return 1.0


@stage("marina.metrics")
def _step_metrics(g, c_k, bits_dense, bits_q, down_q, oracle_sync, oracle_q):
    """The round's :class:`StepMetrics`: the new estimator's norm and the
    bit and oracle ledgers, dense on sync rounds (``c_k``)."""
    return StepMetrics(
        grad_est_norm=tree_norm(g),
        bits_per_worker=jnp.where(c_k, bits_dense, bits_q),
        sync_round=c_k.astype(jnp.int32),
        oracle_calls=jnp.where(c_k, oracle_sync, oracle_q),
        down_bits=jnp.where(c_k, bits_dense, down_q),
    )


@stage("marina.carry")
def _carry_refresh(h_old, grads, faults, c_k, n):
    """Next-round carry h: this round's local gradients — except dropped
    rows on compressed rounds, whose upload the server never consumed: their
    anchor must stay the last value both sides agree on (sync rounds are the
    rendezvous where everyone refreshes)."""
    if faults is None or faults.attack != "drop" or faults.n_faulty(n) == 0:
        return grads
    dm = faults.byz_mask(jnp.arange(n), n)
    keep_old = jnp.logical_and(jnp.logical_not(c_k), dm)
    return jax.tree.map(
        lambda ho, gn: jnp.where(
            keep_old.reshape((n,) + (1,) * (gn.ndim - 1)),
            ho.astype(gn.dtype), gn,
        ),
        h_old, grads,
    )


# ---------------------------------------------------------------------------
# MARINA — Algorithm 1
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Marina:
    """Algorithm 1. ``grad_fn(params, batch)`` must return the *local full*
    gradient ∇f_i (the trainer passes each worker's full data shard — or, in the
    online LM setting, the round's large batch, matching Alg. 3 line 8 c_k=1).

    ``carry=True`` enables single-backprop lookahead rounds; ``down_*`` add
    the compressed downlink — see the module docstring for both contracts.
    ``aggregator`` swaps the server mean for a Byzantine-robust GAR
    (:class:`repro.core.aggregators.ServerAggregator`); ``faults`` injects
    per-round client faults (:class:`repro.core.faults.FaultSpec`) — both
    default off and leave every honest path untouched (DESIGN.md §4.9)."""

    grad_fn: GradFn
    compressor: Compressor
    gamma: float
    p: float
    engine: FlatEngine | None = None  # fused flat path when set (DESIGN.md §4)
    carry: bool = False
    down_compressor: Compressor | None = None
    down_engine: FlatEngine | None = None
    aggregator: Any = None  # ServerAggregator | None (DESIGN.md §4.9)
    faults: Any = None      # FaultSpec | None

    def __post_init__(self):
        _check_downlink_config(self)
        _check_robust_config(self)

    def init(self, params: PyTree, batches: PyTree) -> MarinaState:
        grads = _per_worker_grads(self.grad_fn, params, batches)
        if not self.carry:
            g0 = tree_mean_axis0(grads)
            return MarinaState(params=params, g=g0, step=jnp.zeros((), jnp.int32))
        g0 = tree_mean_axis0(grads)
        x1 = tree_axpy(-self.gamma, g0, params)
        if self.engine is not None:
            # lookahead fused state: estimator lives as the packed buffer
            return MarinaState(
                params=x1, g=pack(self.engine.layout, g0),
                step=jnp.zeros((), jnp.int32), h=grads,
            )
        return MarinaState(params=x1, g=g0, step=jnp.zeros((), jnp.int32), h=grads)

    # -- seed-shaped rounds (two backprops on compressed rounds) ------------
    def _step_recompute(self, state: MarinaState, key: jax.Array, batches: PyTree):
        n = jax.tree.leaves(batches)[0].shape[0]
        c_k, (k_q,), k_down, k_f = _round_keys(key, self.p)
        ids = jnp.arange(n)

        x_old = state.params
        x_new = tree_axpy(-self.gamma, state.g, x_old)  # Alg.1 line 7

        def sync_branch(_):
            grads = _per_worker_grads(self.grad_fn, x_new, batches)
            grads = _sync_faults(self.faults, k_f, grads, ids, n)
            return _sync_aggregate(self.engine, self.aggregator, grads)

        def compressed_branch(_):
            g_new = _per_worker_grads(self.grad_fn, x_new, batches)
            g_prev = _per_worker_grads(self.grad_fn, x_old, batches)
            diffs = _uplink_diff(self.faults, k_f, g_new, g_prev, ids, n)
            delta = _compressed_delta(
                self.compressor, self.engine, k_q, diffs, state.params, n,
                self.aggregator,
            )
            delta = _down_roundtrip(
                self.down_compressor, self.down_engine,
                k_down, delta, state.params,
            )
            return jax.tree.map(jnp.add, state.g, delta)

        g_next = jax.lax.cond(c_k, sync_branch, compressed_branch, None)

        d = tree_dim(state.params)
        bits_dense = jnp.asarray(32.0 * d)
        bits_q = _round_bits(self.compressor, self.engine, state.params, n)
        up_scale = _uplink_bits_scale(self.faults, n)
        if up_scale != 1.0:
            bits_q = bits_q * up_scale
        down_q = _down_round_bits(
            self.down_compressor, self.down_engine, state.params, d
        )
        metrics = _step_metrics(g_next, c_k, bits_dense, bits_q, down_q, 1.0, 2.0)
        return MarinaState(params=x_new, g=g_next, step=state.step + 1), metrics

    # -- gradient-carry lookahead rounds (one backprop, fused epilogue) -----
    def _step_carry(self, state: MarinaState, key: jax.Array, batches: PyTree):
        n = jax.tree.leaves(batches)[0].shape[0]
        c_k, (k_q,), k_down, k_f = _round_keys(key, self.p)
        ids = jnp.arange(n)
        d = tree_dim(state.params)

        # the ONE backprop of the round, shared by both branches: state.params
        # is already the evaluation point x^{k+1} (lookahead state).
        grads = _per_worker_grads(self.grad_fn, state.params, batches)
        # h keeps the HONEST local gradients (a Byzantine client lies on the
        # wire, not to itself; a dropped client's row is pinned by
        # _carry_refresh) — only the uplinked payloads are faulted.
        h_new = _carry_refresh(state.h, grads, self.faults, c_k, n)

        if self.engine is not None:
            lay = self.engine.layout
            x2d = pack(lay, state.params)

            def sync_branch(_):
                g_up = _sync_faults(self.faults, k_f, grads, ids, n)
                return self.engine.fused_sync(
                    pack_stacked(lay, g_up), x2d, self.gamma,
                    aggregator=self.aggregator,
                )

            def compressed_branch(_):
                diffs = _uplink_diff(self.faults, k_f, grads, state.h, ids, n)
                return self.engine.fused_round(
                    k_q, pack_stacked(lay, diffs), n, state.g, x2d, self.gamma,
                    down=self.down_engine, down_key=k_down,
                    aggregator=self.aggregator,
                )

            g2d, x_new2d = jax.lax.cond(c_k, sync_branch, compressed_branch, None)
            new_state = MarinaState(
                params=unpack(lay, x_new2d), g=g2d, step=state.step + 1,
                h=h_new,
            )
        else:
            def sync_branch(_):
                g_up = _sync_faults(self.faults, k_f, grads, ids, n)
                return _sync_aggregate(None, self.aggregator, g_up)

            def compressed_branch(_):
                diffs = _uplink_diff(self.faults, k_f, grads, state.h, ids, n)
                delta = _compressed_delta(
                    self.compressor, None, k_q, diffs, state.params, n,
                    self.aggregator,
                )
                delta = _down_roundtrip(
                    self.down_compressor, self.down_engine, k_down, delta,
                    state.params,
                )
                return jax.tree.map(jnp.add, state.g, delta)

            g_next = jax.lax.cond(c_k, sync_branch, compressed_branch, None)
            x_next = tree_axpy(-self.gamma, g_next, state.params)
            new_state = MarinaState(
                params=x_next, g=g_next, step=state.step + 1, h=h_new
            )

        bits_dense = jnp.asarray(32.0 * d)
        bits_q = _round_bits(self.compressor, self.engine, state.params, n)
        up_scale = _uplink_bits_scale(self.faults, n)
        if up_scale != 1.0:
            bits_q = bits_q * up_scale
        down_q = _down_round_bits(
            self.down_compressor, self.down_engine, state.params, d
        )
        metrics = _step_metrics(
            new_state.g, c_k, bits_dense, bits_q, down_q, 1.0, 1.0
        )
        return new_state, metrics

    def step(self, state: MarinaState, key: jax.Array, batches: PyTree):
        if self.carry:
            return self._step_carry(state, key, batches)
        return self._step_recompute(state, key, batches)


# ---------------------------------------------------------------------------
# VR-MARINA — Algorithms 2 (finite-sum) and 3 (online)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class VRMarina:
    """Algorithms 2/3. Two oracles:

    * ``full_grad_fn(params, full_batch)`` — ∇f_i (finite-sum, Alg. 2) or the
      b-minibatch gradient (online, Alg. 3) used on c_k = 1 rounds.
    * ``mb_grad_fn(params, mb_batch)`` — the b′-minibatch gradient used at *both*
      points on compressed rounds.

    The trainer samples the batches; this keeps the algorithm agnostic to the
    dataset layout (and identical between the finite-sum and online cases, which
    differ only in what the oracles receive — exactly the Alg. 2 vs Alg. 3 delta).

    ``carry=True`` carries the minibatch recursion: h_i holds whatever local
    gradient the previous round evaluated (full on sync rounds, b′-minibatch
    on compressed rounds) and the compressed difference is
    ∇̂(x^{k+1}; ξ_k) − h_i — one oracle sweep per round instead of two.
    Bit-exact vs. the recompute path when the oracles and batches are
    deterministic per round (e.g. b′ = m); in the fresh-minibatch regime it
    trades the same-ξ correlation for the halved oracle cost (opt-in)."""

    full_grad_fn: GradFn
    mb_grad_fn: GradFn
    compressor: Compressor
    gamma: float
    p: float
    engine: FlatEngine | None = None
    carry: bool = False
    down_compressor: Compressor | None = None
    down_engine: FlatEngine | None = None
    aggregator: Any = None  # ServerAggregator | None (DESIGN.md §4.9)
    faults: Any = None      # FaultSpec | None

    def __post_init__(self):
        _check_downlink_config(self)
        _check_robust_config(self)

    def init(self, params: PyTree, full_batches: PyTree) -> MarinaState:
        grads = _per_worker_grads(self.full_grad_fn, params, full_batches)
        if not self.carry:
            g0 = tree_mean_axis0(grads)
            return MarinaState(params=params, g=g0, step=jnp.zeros((), jnp.int32))
        g0 = tree_mean_axis0(grads)
        x1 = tree_axpy(-self.gamma, g0, params)
        if self.engine is not None:
            return MarinaState(
                params=x1, g=pack(self.engine.layout, g0),
                step=jnp.zeros((), jnp.int32), h=grads,
            )
        return MarinaState(params=x1, g=g0, step=jnp.zeros((), jnp.int32), h=grads)

    def _step_recompute(self, state, key, full_batches, mb_batches):
        n = jax.tree.leaves(full_batches)[0].shape[0]
        c_k, (k_q,), k_down, k_f = _round_keys(key, self.p)
        ids = jnp.arange(n)

        x_old = state.params
        x_new = tree_axpy(-self.gamma, state.g, x_old)

        def sync_branch(_):
            grads = _per_worker_grads(self.full_grad_fn, x_new, full_batches)
            grads = _sync_faults(self.faults, k_f, grads, ids, n)
            return _sync_aggregate(self.engine, self.aggregator, grads)

        def compressed_branch(_):
            # Alg. 2 line 8: same minibatch at x^{k+1} and x^k.
            g_new = _per_worker_grads(self.mb_grad_fn, x_new, mb_batches)
            g_prev = _per_worker_grads(self.mb_grad_fn, x_old, mb_batches)
            diffs = _uplink_diff(self.faults, k_f, g_new, g_prev, ids, n)
            delta = _compressed_delta(
                self.compressor, self.engine, k_q, diffs, state.params, n,
                self.aggregator,
            )
            delta = _down_roundtrip(
                self.down_compressor, self.down_engine,
                k_down, delta, state.params,
            )
            return jax.tree.map(jnp.add, state.g, delta)

        g_next = jax.lax.cond(c_k, sync_branch, compressed_branch, None)

        d = tree_dim(state.params)
        m_full = jax.tree.leaves(full_batches)[0].shape[1]
        b_prime = jax.tree.leaves(mb_batches)[0].shape[1]
        bits_q = _round_bits(self.compressor, self.engine, state.params, n)
        up_scale = _uplink_bits_scale(self.faults, n)
        if up_scale != 1.0:
            bits_q = bits_q * up_scale
        down_q = _down_round_bits(
            self.down_compressor, self.down_engine, state.params, d
        )
        metrics = _step_metrics(
            g_next, c_k, jnp.asarray(32.0 * d), bits_q, down_q,
            float(m_full), 2.0 * b_prime,
        )
        return MarinaState(params=x_new, g=g_next, step=state.step + 1), metrics

    def _step_carry(self, state, key, full_batches, mb_batches):
        n = jax.tree.leaves(full_batches)[0].shape[0]
        c_k, (k_q,), k_down, k_f = _round_keys(key, self.p)
        ids = jnp.arange(n)
        d = tree_dim(state.params)

        if self.engine is not None:
            lay = self.engine.layout
            x2d = pack(lay, state.params)

            # each branch runs its ONE oracle sweep (the two branches use
            # different oracles, so the backprop cannot hoist out of the cond
            # as in plain MARINA — but each round still runs exactly one).
            def sync_branch(_):
                grads = _per_worker_grads(
                    self.full_grad_fn, state.params, full_batches
                )
                g_up = _sync_faults(self.faults, k_f, grads, ids, n)
                g2d, x_new2d = self.engine.fused_sync(
                    pack_stacked(lay, g_up), x2d, self.gamma,
                    aggregator=self.aggregator,
                )
                return g2d, x_new2d, grads

            def compressed_branch(_):
                grads = _per_worker_grads(
                    self.mb_grad_fn, state.params, mb_batches
                )
                diffs = _uplink_diff(self.faults, k_f, grads, state.h, ids, n)
                g2d, x_new2d = self.engine.fused_round(
                    k_q, pack_stacked(lay, diffs), n, state.g, x2d, self.gamma,
                    down=self.down_engine, down_key=k_down,
                    aggregator=self.aggregator,
                )
                return g2d, x_new2d, grads

            g2d, x_new2d, h_new = jax.lax.cond(
                c_k, sync_branch, compressed_branch, None
            )
            new_state = MarinaState(
                params=unpack(lay, x_new2d), g=g2d, step=state.step + 1,
                h=_carry_refresh(state.h, h_new, self.faults, c_k, n),
            )
        else:
            def sync_branch(_):
                grads = _per_worker_grads(
                    self.full_grad_fn, state.params, full_batches
                )
                g_up = _sync_faults(self.faults, k_f, grads, ids, n)
                return _sync_aggregate(None, self.aggregator, g_up), grads

            def compressed_branch(_):
                grads = _per_worker_grads(
                    self.mb_grad_fn, state.params, mb_batches
                )
                diffs = _uplink_diff(self.faults, k_f, grads, state.h, ids, n)
                delta = _compressed_delta(
                    self.compressor, None, k_q, diffs, state.params, n,
                    self.aggregator,
                )
                delta = _down_roundtrip(
                    self.down_compressor, self.down_engine, k_down, delta,
                    state.params,
                )
                return jax.tree.map(jnp.add, state.g, delta), grads

            g_next, h_new = jax.lax.cond(
                c_k, sync_branch, compressed_branch, None
            )
            x_next = tree_axpy(-self.gamma, g_next, state.params)
            new_state = MarinaState(
                params=x_next, g=g_next,
                step=state.step + 1,
                h=_carry_refresh(state.h, h_new, self.faults, c_k, n),
            )

        m_full = jax.tree.leaves(full_batches)[0].shape[1]
        b_prime = jax.tree.leaves(mb_batches)[0].shape[1]
        bits_q = _round_bits(self.compressor, self.engine, state.params, n)
        up_scale = _uplink_bits_scale(self.faults, n)
        if up_scale != 1.0:
            bits_q = bits_q * up_scale
        down_q = _down_round_bits(
            self.down_compressor, self.down_engine, state.params, d
        )
        metrics = _step_metrics(
            new_state.g, c_k, jnp.asarray(32.0 * d), bits_q, down_q,
            float(m_full), 1.0 * b_prime,
        )
        return new_state, metrics

    def step(
        self,
        state: MarinaState,
        key: jax.Array,
        full_batches: PyTree,
        mb_batches: PyTree,
    ):
        if self.carry:
            return self._step_carry(state, key, full_batches, mb_batches)
        return self._step_recompute(state, key, full_batches, mb_batches)


# ---------------------------------------------------------------------------
# PP-MARINA — Algorithm 4
# ---------------------------------------------------------------------------


def pp_sample_cohort(
    k_sel: jax.Array, n: int, r: int, replace: bool
) -> jax.Array:
    """Draw PP-MARINA's cohort I'_k (Alg. 4 line 5): r i.i.d. uniform client
    ids (``replace=True``, the analyzed variant) or r distinct ids
    (``replace=False``, the experiments' variant). THE single sampling
    definition — ``PPMarina`` and the mesh prefetch
    (``launch.distributed.pp_cohort_schedule``) both call it, so a schedule
    can never drift from the algorithm."""
    if replace:
        return jax.random.randint(k_sel, (r,), 0, n)
    return jax.random.permutation(k_sel, n)[:r]


def _weighted_mean_axis0(trees: PyTree, weights: "jax.Array | None") -> PyTree:
    """Σ_i w_i t_i over the leading client axis (plain mean when w is None)."""
    if weights is None:
        return tree_mean_axis0(trees)
    return jax.tree.map(
        lambda t: jnp.tensordot(weights.astype(t.dtype), t, axes=1), trees
    )


def _scale_rows(trees: PyTree, row_scale: jax.Array) -> PyTree:
    """Scale each leading-axis row of every leaf by ``row_scale`` (r,)."""
    return jax.tree.map(
        lambda t: t * row_scale.reshape((-1,) + (1,) * (t.ndim - 1)).astype(
            t.dtype
        ),
        trees,
    )


@stage("marina.carry")
def _pp_carry_refresh(h_old, sel, grads_sel, faults, n):
    """PP server carry-table refresh: h.at[sel] ← ∇f_i for the sampled rows —
    except dropped clients, whose row the server never received, so their
    anchor h_i stays what the server last saw (matching the Δ̂_i = 0 uplink
    substitution of :func:`repro.core.faults.zero_rows`)."""
    if faults is None or faults.attack != "drop" or faults.n_faulty(n) == 0:
        return jax.tree.map(
            lambda ht, gt: ht.at[sel].set(gt.astype(ht.dtype)),
            h_old, grads_sel,
        )
    keep_old = faults.byz_mask(sel, n)

    def refresh(ht, gt):
        mask = keep_old.reshape((-1,) + (1,) * (gt.ndim - 1))
        vals = jnp.where(mask, ht[sel].astype(ht.dtype), gt.astype(ht.dtype))
        return ht.at[sel].set(vals)

    return jax.tree.map(refresh, h_old, grads_sel)


@dataclasses.dataclass
class PPMarina:
    """Algorithm 4 plus the federated-scenario extensions (DESIGN.md §4.8):

    * ``replace`` — Alg. 4 line 5 samples the cohort I'_k as r i.i.d. uniform
      clients (``replace=True``, the analyzed variant); ``replace=False``
      samples r *distinct* clients (the variant the paper's experiments run).
      Both keep the 1/r server scaling: each client lands in the cohort with
      the same marginal, so (1/r)·Σ_{i∈I'} Q(Δ_i) stays an unbiased estimate
      of the mean difference — without replacement only lowers its variance.
    * ``weights`` — arbitrary client weights w_i for unbalanced local
      datasets (raw sample counts are fine — normalized to Σw_i = 1 at
      construction): f(x) = Σ_i w_i f_i(x). Sync rounds average gradients
      with w; compressed rounds pre-scale the sampled differences by n·w_i
      before compression, so (1/r)·Σ Q(n·w_i·Δ_i) is unbiased for Σ w_i Δ_i
      under uniform sampling and the wire/engine path is unchanged.
    * ``carry`` — the *server-side carry table*: the server stores
      h_i = ∇f_i(x) from the last round client i participated in (all n rows
      refresh on sync rounds, only the sampled rows on compressed rounds), so
      a compressed round runs ONE backprop per sampled client — against the
      table instead of recomputing at x^k — and with an engine ends in the
      fused epilogue kernel (the PR-4 path). Beyond-paper and opt-in: for
      clients that sat rounds out the anchor is stale (a lazy-anchor
      estimator à la DIANA shifts); with r = n, replace=False it coincides
      with the recompute estimator step for step (tested). Carry states are
      lookahead, exactly like :class:`Marina` ``carry=True``.

    Bits: the ledger books the fleet totals from :mod:`repro.core.wire` —
    n·32d on sync rounds, exactly r·ζ_Q on compressed rounds — divided by n
    for the per-client ``bits_per_worker`` average. The compressed downlink
    applies unchanged (the broadcast reaches all n clients)."""

    grad_fn: GradFn
    compressor: Compressor
    gamma: float
    p: float
    r: int
    engine: FlatEngine | None = None
    down_compressor: Compressor | None = None
    down_engine: FlatEngine | None = None
    replace: bool = True
    weights: "jax.Array | None" = None
    carry: bool = False
    aggregator: Any = None  # ServerAggregator | None (DESIGN.md §4.9)
    faults: Any = None      # FaultSpec | None

    def __post_init__(self):
        _check_downlink_config(self)
        _check_robust_config(self)
        if self.weights is not None:
            # accept raw sample counts: normalize to Σw_i = 1 so the
            # weighted objective is a convex combination of the f_i
            w = jnp.asarray(self.weights, jnp.float32)
            self.weights = w / jnp.sum(w)

    def _cohort(self, k_sel: jax.Array, n: int) -> jax.Array:
        """I'_k via the shared sampler (:func:`pp_sample_cohort`)."""
        return pp_sample_cohort(k_sel, n, self.r, self.replace)

    def _cohort_diff_scale(self, sel: jax.Array, n: int) -> "jax.Array | None":
        """Pre-compression row scaling making the 1/r cohort mean unbiased
        for the w-weighted full mean: n·w_i (None when weights are uniform —
        n·(1/n) = 1 and the scaling is the identity)."""
        if self.weights is None:
            return None
        return n * self.weights[sel]

    def init(self, params: PyTree, batches: PyTree) -> MarinaState:
        grads = _per_worker_grads(self.grad_fn, params, batches)
        g0 = _weighted_mean_axis0(grads, self.weights)
        if not self.carry:
            return MarinaState(params=params, g=g0, step=jnp.zeros((), jnp.int32))
        # lookahead carry state: the server seeds the full carry table with
        # every client's ∇f_i(x^0) (the one round where all n backprop).
        x1 = tree_axpy(-self.gamma, g0, params)
        if self.engine is not None:
            return MarinaState(
                params=x1, g=pack(self.engine.layout, g0),
                step=jnp.zeros((), jnp.int32), h=grads,
            )
        return MarinaState(params=x1, g=g0, step=jnp.zeros((), jnp.int32), h=grads)

    # -- seed-shaped rounds (two backprops per sampled client) --------------
    def _step_recompute(self, state: MarinaState, key: jax.Array, batches: PyTree):
        n = jax.tree.leaves(batches)[0].shape[0]
        c_k, (k_sel, k_q), k_down, k_f = _round_keys(key, self.p, 3)

        x_old = state.params
        x_new = tree_axpy(-self.gamma, state.g, x_old)

        def sync_branch(_):
            grads = _per_worker_grads(self.grad_fn, x_new, batches)
            grads = _sync_faults(self.faults, k_f, grads, jnp.arange(n), n)
            return _sync_aggregate(
                self.engine, self.aggregator, grads, self.weights
            )

        def compressed_branch(_):
            sel = self._cohort(k_sel, n)
            take = lambda t: t[sel]
            sel_batches = jax.tree.map(take, batches)
            g_new = _per_worker_grads(self.grad_fn, x_new, sel_batches)
            g_prev = _per_worker_grads(self.grad_fn, x_old, sel_batches)
            diffs = _uplink_diff(
                self.faults, k_f, g_new, g_prev, sel, n,
                self._cohort_diff_scale(sel, n),
            )
            delta = _compressed_delta(
                self.compressor, self.engine, k_q, diffs, state.params, self.r,
                self.aggregator,
            )
            delta = _down_roundtrip(
                self.down_compressor, self.down_engine,
                k_down, delta, state.params,
            )
            return jax.tree.map(jnp.add, state.g, delta)

        g_next = jax.lax.cond(c_k, sync_branch, compressed_branch, None)
        new_state = MarinaState(params=x_new, g=g_next, step=state.step + 1)
        metrics = self._metrics(
            c_k, g_next, state.params, n, oracle_factor=2.0
        )
        return new_state, metrics

    # -- carry rounds: ONE backprop per sampled client vs the server table --
    def _step_carry(self, state: MarinaState, key: jax.Array, batches: PyTree):
        n = jax.tree.leaves(batches)[0].shape[0]
        c_k, (k_sel, k_q), k_down, k_f = _round_keys(key, self.p, 3)

        # the cohort is hoisted out of the cond so the ledger can count the
        # uploads that actually happened (dropped sampled clients don't bill)
        sel = self._cohort(k_sel, n)
        uploaded = None
        if self.faults is not None and self.faults.attack == "drop":
            uploaded = self.r - jnp.sum(
                self.faults.byz_mask(sel, n).astype(jnp.int32)
            )

        if self.engine is not None:
            lay = self.engine.layout
            x2d = pack(lay, state.params)

            def sync_branch(_):
                grads = _per_worker_grads(self.grad_fn, state.params, batches)
                g_up = _sync_faults(self.faults, k_f, grads, jnp.arange(n), n)
                if self.weights is None:
                    g2d, x_new2d = self.engine.fused_sync(
                        pack_stacked(lay, g_up), x2d, self.gamma,
                        aggregator=self.aggregator,
                    )
                else:
                    g_new = _weighted_mean_axis0(g_up, self.weights)
                    g2d = pack(lay, g_new)
                    x_new2d = x2d - self.gamma * g2d
                # the table keeps the HONEST gradients — liars lie on the
                # wire, the simulated clients still know their own state
                return g2d, x_new2d, grads

            def compressed_branch(_):
                sel_batches = jax.tree.map(lambda t: t[sel], batches)
                grads_sel = _per_worker_grads(
                    self.grad_fn, state.params, sel_batches
                )
                h_sel = jax.tree.map(lambda t: t[sel], state.h)
                diffs = _uplink_diff(
                    self.faults, k_f, grads_sel, h_sel, sel, n,
                    self._cohort_diff_scale(sel, n),
                )
                # the table keeps the RAW client gradients (weights apply at
                # aggregation): refresh only the sampled rows — minus drops.
                h_new = _pp_carry_refresh(
                    state.h, sel, grads_sel, self.faults, n
                )
                g2d, x_new2d = self.engine.fused_round(
                    k_q, pack_stacked(lay, diffs), self.r, state.g, x2d,
                    self.gamma, down=self.down_engine, down_key=k_down,
                    aggregator=self.aggregator,
                )
                return g2d, x_new2d, h_new

            g2d, x_new2d, h_new = jax.lax.cond(
                c_k, sync_branch, compressed_branch, None
            )
            new_state = MarinaState(
                params=unpack(lay, x_new2d), g=g2d, step=state.step + 1,
                h=h_new,
            )
        else:
            def sync_branch(_):
                grads = _per_worker_grads(self.grad_fn, state.params, batches)
                g_up = _sync_faults(self.faults, k_f, grads, jnp.arange(n), n)
                return (
                    _sync_aggregate(None, self.aggregator, g_up, self.weights),
                    grads,
                )

            def compressed_branch(_):
                sel_batches = jax.tree.map(lambda t: t[sel], batches)
                grads_sel = _per_worker_grads(
                    self.grad_fn, state.params, sel_batches
                )
                h_sel = jax.tree.map(lambda t: t[sel], state.h)
                diffs = _uplink_diff(
                    self.faults, k_f, grads_sel, h_sel, sel, n,
                    self._cohort_diff_scale(sel, n),
                )
                h_new = _pp_carry_refresh(
                    state.h, sel, grads_sel, self.faults, n
                )
                delta = _compressed_delta(
                    self.compressor, None, k_q, diffs, state.params, self.r,
                    self.aggregator,
                )
                delta = _down_roundtrip(
                    self.down_compressor, self.down_engine, k_down, delta,
                    state.params,
                )
                return jax.tree.map(jnp.add, state.g, delta), h_new

            (g_next, h_new) = jax.lax.cond(
                c_k, sync_branch, compressed_branch, None
            )
            x_next = tree_axpy(-self.gamma, g_next, state.params)
            new_state = MarinaState(
                params=x_next, g=g_next, step=state.step + 1, h=h_new
            )

        metrics = self._metrics(
            c_k, new_state.g, state.params, n, oracle_factor=1.0,
            uploaded=uploaded,
        )
        return new_state, metrics

    @stage("marina.metrics")
    def _metrics(self, c_k, g, like, n, oracle_factor, uploaded=None):
        """The estimator's norm, and the fleet-total uplink from the wire
        helpers, divided by n: sync rounds cost n·32d, compressed rounds
        exactly r·ζ_Q (wire.py) — or uploaded·ζ_Q when dropped cohort members
        never delivered theirs."""
        from . import wire

        d = tree_dim(like)
        up = self.r if uploaded is None else uploaded
        bits_total = jnp.where(
            c_k,
            jnp.asarray(wire.pp_sync_total_bits(n, d)),
            wire.pp_uplink_total_bits(
                up, _round_bits(self.compressor, self.engine, like, self.r)
            ),
        )
        down_q = _down_round_bits(
            self.down_compressor, self.down_engine, like, d
        )
        return StepMetrics(
            grad_est_norm=tree_norm(g),
            bits_per_worker=bits_total / n,
            sync_round=c_k.astype(jnp.int32),
            oracle_calls=jnp.where(c_k, 1.0, oracle_factor * self.r / n),
            down_bits=jnp.where(c_k, jnp.asarray(32.0 * d), down_q),
        )

    def step(self, state: MarinaState, key: jax.Array, batches: PyTree):
        if self.carry:
            return self._step_carry(state, key, batches)
        return self._step_recompute(state, key, batches)


def make_gd(grad_fn: GradFn, gamma: float) -> Marina:
    """GD = MARINA with identity quantization (paper §2)."""
    return Marina(grad_fn=grad_fn, compressor=Identity(), gamma=gamma, p=1.0)
