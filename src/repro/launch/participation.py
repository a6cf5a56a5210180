"""Federated round assembly: PP-MARINA cohort rounds on the mesh.

The partial-participation round path (Alg. 4, DESIGN.md §4.8) split out of
launch/distributed.py by the ISSUE 7 layering: ``build_train_steps`` calls
:func:`build_pp_steps` to override its compressed/train steps when
``participation=(r, scheme)`` is set. Sync rounds are untouched (all n
clients ship dense gradients); compressed rounds take the cohort row
``sel`` from :func:`pp_cohort_schedule`, respread the r sampled clients'
batch rows over all n worker shards, and put exactly r payload rows on the
wire through the transport interface (flat-PP engine bookings included).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import flat as flat_engine
from repro.core.marina import _FAULT_FOLD, _pp_carry_refresh, _uplink_diff
from repro.launch import sharding as shd
from repro.launch.topology import cohort_group_size


def pp_cohort_schedule(
    base_key: jax.Array, n_steps: int, n: int, r: int,
    scheme: str = "without",
) -> jax.Array:
    """Precompute the (n_steps, r) PP cohort table — the prefetch side of the
    participation wire (DESIGN.md §4.8).

    Row k is EXACTLY the cohort the core ``PPMarina`` step draws from the
    step key ``fold_in(base_key, k)`` (the same 3-way ``(bern, sel, q)``
    split), so a precomputed schedule keeps distributed rounds
    trajectory-equal to the single-process reference while hoisting the
    sampling off the round's critical path: the k+1 batch-row gather can be
    issued while round k's epilogue is still in flight.
    """
    from repro.core.marina import pp_sample_cohort

    assert scheme in ("with", "without"), scheme

    def one(step):
        k = jax.random.fold_in(base_key, step)
        _, k_sel, _ = jax.random.split(k, 3)
        return pp_sample_cohort(k_sel, n, r, replace=(scheme == "with"))

    return jax.vmap(one)(jnp.arange(n_steps, dtype=jnp.int32))


def build_pp_steps(
    participation,
    *,
    n: int,
    per_worker: int,
    p: float,
    block: int,
    kb: int,
    shared_mask: bool,
    compression: str,
    compression_backend: str,
    qsgd_s: int,
    replicate_params: bool,
    inner: tuple,
    param_shapes,
    p_shard,
    batch_shard,
    mesh,
    transport,
    downlink: str,
    robust: bool,
    aggregator,
    faults,
    grad_carry: bool,
    sync_step,
    worker_grads,
    descend,
    robust_delta,
):
    """Build the PP compressed/train steps over the shared round plumbing.

    Everything numeric is the caller's: ``sync_step`` / ``worker_grads`` /
    ``descend`` / ``robust_delta`` close over the model and transport built
    in ``build_train_steps``; this function only assembles the cohort
    compute and the r-row wire around them. Returns
    ``(compressed_step, train_step, meta)`` where ``meta`` records the
    participation mode, cohort-compute vs masked fallback, and flat-PP
    decisions.
    """
    r_part, scheme = participation
    assert scheme in ("with", "without"), scheme
    assert 1 <= r_part <= n, f"cohort r={r_part} vs n={n} workers"
    assert not shared_mask, (
        "participation composes with randk/permk/qsgd, not shared_mask "
        "(a shared mask already correlates the whole fleet)"
    )
    # cohort-mapped compute needs the r clients' rows to respread evenly
    # over the n worker shards in whole tokens-per-shard units
    grp = cohort_group_size(n, r_part)
    cohort_compute = grp is not None and (per_worker * r_part) % n == 0
    # flat-PP: where packing cannot force a reshard (same predicate as
    # flat_sync auto), the r-row payload pipeline IS the core engine —
    # pack → sampler → aggregate with the identical key/seed derivation,
    # which is what makes mesh rounds trajectory-equal to core PPMarina.
    flat_pp = replicate_params or not inner
    pp_eng = None
    if flat_pp and compression in ("randk", "permk", "qsgd"):
        if compression == "permk" and block % r_part != 0:
            flat_pp = False
        else:
            # seed_constraint pins the threefry seed derivation
            # replicated: the SPMD partitioner otherwise re-partitions
            # the split→bits chain and yields different seed VALUES
            # than one device — the silent killer of core↔mesh
            # trajectory equality (core/flat.py).
            pp_eng = flat_engine.make_engine(
                param_shapes, kb=kb, block=block,
                backend=compression_backend, sampler=compression,
                s=qsgd_s,
            )
            pp_eng = dataclasses.replace(
                pp_eng, seed_constraint=shd.replicated(mesh)
            )
    else:
        flat_pp = False

    def cohort_grads(x, batch, sel):
        """Per-client gradients of the r sampled clients.

        Cohort-mapped: gather the r clients' batch rows, respread them
        over all n shards (each shard backprops per_worker·r/n tokens —
        compute is r/n of a full round), then group-mean the n shard
        grads back to r client grads (equal sub-batch sizes make the
        mean of means exact). Masked fallback: every shard backprops its
        own full batch and only the r sampled rows are kept."""
        if cohort_compute:
            sub = (per_worker * r_part) // n
            sel_b = jax.tree.map(
                lambda t: t[sel].reshape(n, sub, *t.shape[2:]), batch
            )
            sel_b = jax.tree.map(
                jax.lax.with_sharding_constraint, sel_b, batch_shard
            )
            wg = worker_grads(x, sel_b)
            return jax.tree.map(
                lambda t: jnp.mean(
                    t.reshape(r_part, grp, *t.shape[1:]), axis=1
                ),
                wg,
            )
        wg = worker_grads(x, batch)
        return jax.tree.map(lambda t: t[sel], wg)

    def pp_delta(key, diffs):
        """(1/r)·Σ Q(Δ_i) over the r cohort payload rows (the GAR over
        the cohort's decoded rows when robust) + downlink."""
        k_up, k_down = jax.random.split(key)
        k_up = k_up if downlink != "none" else key
        if flat_pp:
            # the flat engine stages this exchange itself, so the
            # transport can't see it — book the r·ζ_Q uplink explicitly
            # from the engine's own wire accounting
            transport.book(
                "up",
                "all-to-all" if compression == "permk" else "all-gather",
                r_part * pp_eng.payload_bits(r_part) / n,
            )
            bufs = flat_engine.pack_stacked(pp_eng.layout, diffs)
            delta = flat_engine.unpack(
                pp_eng.layout,
                pp_eng.aggregate(k_up, bufs, r_part, aggregator),
            )
            delta = jax.tree.map(
                jax.lax.with_sharding_constraint, delta, p_shard
            )
        elif robust:
            delta = robust_delta(k_up, diffs, r_part)
        else:
            # sharded fallback: the per-leaf staged wire on the r-row
            # payload stack (cohort rows replicate — r·ζ, not n·ζ)
            delta = transport.uplink_mean(
                k_up, diffs, rows_n=r_part, rows_sharded=False,
                out_shardings=p_shard,
            )
        return transport.downlink(k_down, delta)

    if grad_carry:
        # h is the SERVER-SIDE CARRY TABLE: all n rows live on the mesh,
        # compressed rounds refresh only the sampled ones.
        def compressed_step(params, g, h, batch, key, sel):
            x_new = descend(params, g)
            cg = cohort_grads(x_new, batch, sel)
            h_sel = jax.tree.map(lambda t: t[sel], h)
            diffs = _uplink_diff(
                faults, jax.random.fold_in(key, _FAULT_FOLD), cg, h_sel,
                sel, n,
            )
            g_new = jax.tree.map(jnp.add, g, pp_delta(key, diffs))
            # sampled rows refresh — except dropped clients, whose row
            # the server never received (core _pp_carry_refresh)
            h_new = _pp_carry_refresh(h, sel, cg, faults, n)
            return x_new, g_new, h_new

        def train_step(params, g, h, batch, key, sel):
            k_b, _, k_q = jax.random.split(key, 3)
            c_k = jax.random.bernoulli(k_b, p)
            return jax.lax.cond(
                c_k,
                lambda _: sync_step(params, g, h, batch),
                lambda _: compressed_step(params, g, h, batch, k_q, sel),
                None,
            )
    else:
        def compressed_step(params, g, batch, key, sel):
            x_new = descend(params, g)
            g_plus = cohort_grads(x_new, batch, sel)
            g_minus = cohort_grads(params, batch, sel)
            diffs = _uplink_diff(
                faults, jax.random.fold_in(key, _FAULT_FOLD), g_plus, g_minus,
                sel, n,
            )
            g_new = jax.tree.map(jnp.add, g, pp_delta(key, diffs))
            return x_new, g_new

        def train_step(params, g, batch, key, sel):
            # the core PPMarina key discipline: (bern, sel, q) 3-way
            # split; the sel slot is consumed by pp_cohort_schedule.
            k_b, _, k_q = jax.random.split(key, 3)
            c_k = jax.random.bernoulli(k_b, p)
            return jax.lax.cond(
                c_k,
                lambda _: sync_step(params, g, batch),
                lambda _: compressed_step(params, g, batch, k_q, sel),
                None,
            )

    meta = {
        "participation": participation,
        "cohort_compute": cohort_compute,
        "flat_pp": flat_pp,
    }
    return compressed_step, train_step, meta
