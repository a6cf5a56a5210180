"""Round assembly: MARINA train rounds composed on the mesh.

This is the mesh instantiation of the algorithm in core/marina.py (the
simulation backend and this file share the update equations; the difference
is explicit GSPMD shardings and payload collectives — DESIGN.md §3). Since
ISSUE 7 the launch stack is three layers (DESIGN.md §7):

* **topology** (`launch/topology.py`) — the device fabric: mesh
  construction, worker axes, link tiers (loopback / ici / dcn), and
  multi-process bring-up;
* **transport** (`launch/transport.py`) — the collective primitives: the
  dense sync exchange, the compressed uplink (randk / shared-mask / permk /
  qsgd), the per-worker robust decode, and the compressed downlink, each
  booking its wire bits into the bytes-by-link-tier ledger
  (`core/wire.TierLedger`);
* **round assembly** (this file) — composition only: step bodies wire
  gradients, carries, cohorts and faults through the transport interface,
  and never call raw collectives or stage payload shardings themselves.

Steps built here: ``sync_step`` (the probability-p dense round —
``Transport.sync_aggregate``), ``compressed_step`` (the probability-(1−p)
round: two-point gradient differences through ``Transport.uplink_mean`` +
``Transport.downlink``), and ``train_step`` (Bernoulli(p) `lax.cond` over
the two; the dry-run lowers sync/compressed separately so §Roofline can
attribute costs per round type). Serving assembly lives in
launch/serve_steps.py. The exchange semantics of every wire family and the
round-pipeline overrides (grad_carry, flat_sync, downlink, participation,
aggregator, faults) are documented on the transport methods and the
``build_train_steps`` flags below.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig
from repro.core import flat as flat_engine
from repro.core.marina import (
    _FAULT_FOLD,
    _carry_refresh,
    _per_worker_grads,
    _round_keys,
    _sync_faults,
    _uplink_diff,
)
from repro.models import init_params, lm_loss
from repro.launch import sharding as shd
from repro.launch.participation import build_pp_steps, pp_cohort_schedule  # noqa: F401
from repro.launch.topology import detect_topology, num_workers, worker_axis_names
from repro.launch.transport import make_transport
from repro.tracing import stage

PyTree = Any

BLOCK = 1024   # compression block width (8×128 VMEM tile)
KB = 8         # retained coords per block → ζ/d = 1/128, ω = 127


@dataclasses.dataclass(frozen=True)
class StepBundle:
    """Everything the dry-run needs for one (arch × mesh) combination."""

    mesh: Any
    n_workers: int
    param_shapes: PyTree
    param_shardings: PyTree
    fns: dict  # name -> (jitted fn, example abstract args)
    meta: dict = dataclasses.field(default_factory=dict)  # builder decisions
    # (participation mode, cohort-compute vs masked fallback, flat-PP path)
    transport: Any = None  # the Transport whose ledger priced this bundle
    # where the entries take their arguments: (params, g[, h]) and the batch
    state_shardings: tuple = ()
    batch_shardings: Any = None


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_train_steps(
    arch: ArchConfig,
    mesh,
    multi_pod: bool,
    *,
    global_batch: int,
    seq_len: int,
    gamma: float = 1e-3,
    p: float = KB / BLOCK,
    dtype=jnp.bfloat16,
    shared_mask: bool = False,
    remat: bool = True,
    packed_payload: bool = False,
    replicate_params: bool = False,
    staged_payload: bool = True,
    compression_backend: str = "auto",
    compression: str = "randk",
    qsgd_s: int = 15,
    grad_carry: bool = False,
    flat_sync: "bool | None" = None,
    downlink: str = "none",
    downlink_s: int = 7,
    participation: "tuple[int, str] | None" = None,
    aggregator: "Any | None" = None,
    faults: "Any | None" = None,
    topology: "Any | None" = None,
):
    """Returns (fns, abstract_args) for sync_step / compressed_step / train_step.

    §Perf overrides (wire policy freezes into the transport —
    launch/transport.py documents each family's exchange semantics):
    * shared_mask      — SharedRandK: K-value psum instead of n·K all-gather
    * packed_payload   — bf16 values + int16 indices on the wire; with
      compression="qsgd" and s ≤ 7, 4-bit nibble packing instead
    * compression      — "randk" | "permk" | "qsgd" (DESIGN.md §4.2–§4.6)
    * qsgd_s           — quantization levels for compression="qsgd"
    * topology         — modeled fabric for the wire ledger (default: the
      runtime fabric via detect_topology; perf/dryrun pass the production
      topology so bits book under the tiers the mesh MODELS)
    * replicate_params — small-model mode: no tensor parallelism; the model
      axis becomes within-worker data parallelism
    * grad_carry       — single-backprop compressed rounds: the step carry
      grows per-worker h_i^k = ∇f_i(x^k) (sharded like the grads, donated);
      signatures become (params, g, h, batch[, key]) → (params, g, h)
    * flat_sync        — sync rounds exchange ONE packed (n, nblk, B) buffer
      instead of one collective per leaf. Default (None) auto-enables it
      only when packing cannot force a reshard of model-parallel leaves
      (replicated params, or a mesh whose axes are all worker axes) —
      otherwise GSPMD must all-gather the dense grads to assemble the flat
      buffer (~4× sync-step memory on the qwen 0.5B dryrun)
    * downlink         — "none" (dense estimator broadcast) or
      "qsgd"/"randk": broadcast Q_down(g^{k+1} − g^k) and
      decompress-accumulate worker-side (downlink_s levels)
    * participation    — (r, "with"|"without"): PP-MARINA on the mesh
      (DESIGN.md §4.8). Compressed rounds sample a cohort of r clients from
      ``pp_cohort_schedule`` (steps gain a trailing (r,) int32 ``sel``
      argument), respread the r clients' batch rows over ALL n shards (the
      genuine r/n compute saving) and put exactly r payload rows on the
      wire; falls back to masked dense compute when r doesn't divide
      n·per_worker evenly (recorded in ``bundle.meta``). With ``grad_carry``
      the step's h becomes the server-side carry table: only sampled rows
      refresh. Composes with randk/permk/qsgd but not shared_mask. On
      packing-legal meshes PP rounds are trajectory-equal to core
      ``PPMarina`` for ``downlink="none"`` — see DESIGN.md §4.8
    * aggregator       — a ``repro.core.ServerAggregator``: swap the server
      mean for a robust GAR on decoded per-worker rows
      (``Transport.worker_rows``; DESIGN.md §4.9). Refused with permk and
      shared_mask (payloads aren't per-coordinate comparable)
    * faults           — a ``repro.core.FaultSpec``: per-round client fault
      injection on the uplinked payloads (repro.core.faults); ``drop``
      requires ``grad_carry`` (the carried h row substitutes the missing
      upload)
    """
    cfg = dataclasses.replace(arch.model, remat=remat)
    robust = aggregator is not None and aggregator.robust
    if robust:
        if compression == "permk":
            raise ValueError(
                f"robust rule {aggregator.rule!r} is undefined on the permk "
                "wire: workers partition the coordinates (DESIGN.md §4.9)"
            )
        if shared_mask:
            raise ValueError(
                f"robust rule {aggregator.rule!r} is undefined with "
                "shared_mask: one correlated mask spans the whole fleet "
                "(DESIGN.md §4.9)"
            )
    if faults is not None and faults.attack == "drop" and not grad_carry:
        raise ValueError(
            "faults='drop' substitutes the carried h row for the missing "
            "upload — grad_carry=True is required (DESIGN.md §4.9)"
        )
    waxes = worker_axis_names(multi_pod, arch.worker_axes)
    fsdp = arch.fsdp and not any(a in waxes for a in ("data",))
    n = num_workers(mesh, multi_pod, arch.worker_axes)
    per_worker = global_batch // n
    inner_axis = "data" if (fsdp and "data" not in waxes) else None
    if replicate_params:
        inner_axis = "model"

    param_shapes = jax.eval_shape(
        lambda k: init_params(k, cfg, dtype), jax.random.PRNGKey(0)
    )
    if replicate_params:
        p_shard = jax.tree.map(lambda _: shd.replicated(mesh), param_shapes)
    else:
        p_shard = shd.param_sharding_tree(param_shapes, mesh, fsdp)

    # total positions = seq_len; frontend archs spend prefix_len of them on
    # stub embeddings so S stays chunk-aligned
    tok_len = seq_len - arch.prefix_len
    tok_shape = (n, per_worker, tok_len)
    batch = {"tokens": jax.ShapeDtypeStruct(tok_shape, jnp.int32)}
    batch_shard = {
        "tokens": NamedSharding(mesh, shd.batch_spec(waxes, inner_axis, 3))
    }
    if arch.prefix_len:
        pshape = (n, per_worker, arch.prefix_len, cfg.d_model)
        batch["prefix"] = jax.ShapeDtypeStruct(pshape, dtype)
        batch_shard["prefix"] = NamedSharding(
            mesh, shd.batch_spec(waxes, inner_axis, 4)
        )

    def loss_fn(params, one_batch):
        return lm_loss(
            params, cfg, one_batch["tokens"], one_batch.get("prefix")
        )

    # remat is per-layer inside the model (cfg.remat above)
    grad_one = jax.grad(loss_fn)

    def worker_grads(params, batch):
        return _per_worker_grads(grad_one, params, batch)

    # sync rounds ride the flat buffer: one fused mean over the packed
    # (n, nblk, B) buffer — a single worker-axis psum of d — instead of one
    # collective per leaf. The buffer's block dim is pinned to the
    # non-worker mesh axes (when they divide nblk) so the dense grads never
    # replicate, and the unpacked mean is pinned back to the parameter
    # shardings.
    lay = flat_engine.make_layout(param_shapes, block=BLOCK)
    wlead = waxes if len(waxes) > 1 else (waxes[0] if waxes else None)
    # size-1 axes cannot shard anything, so they neither disqualify the
    # packed exchange nor are worth pinning block rows to
    inner = tuple(
        a for a in mesh.shape
        if a not in set(waxes) and mesh.shape[a] > 1
    )
    if flat_sync is None:
        flat_sync = replicate_params or not inner
    blk_axes = inner if (
        inner and lay.rows % int(np.prod([mesh.shape[a] for a in inner])) == 0
    ) else None
    buf_shard = NamedSharding(
        mesh,
        P(wlead, blk_axes if blk_axes and len(blk_axes) > 1
          else (blk_axes[0] if blk_axes else None), None),
    )

    # the transport owns all wire policy + the bytes-by-tier ledger; the
    # topology classifies which link tier the worker axes cross. Callers
    # modeling a production fabric on fake devices (perf/dryrun) pass the
    # modeled topology; by default the RUNTIME fabric is detected.
    topo = topology if topology is not None else detect_topology(
        mesh, multi_pod=multi_pod
    )
    transport = make_transport(
        mesh, topo, waxes, n,
        backend=compression_backend, compression=compression, qsgd_s=qsgd_s,
        packed_payload=packed_payload, staged_payload=staged_payload,
        shared_mask=shared_mask, downlink=downlink, downlink_s=downlink_s,
        flat_sync=flat_sync, sync_layout=lay, sync_buf_shard=buf_shard,
        param_shardings=p_shard,
    )

    # mesh sync steps are keyless by design, so the (rare) sync-round
    # garbage noise draws from a fixed key — every other attack is
    # deterministic and unaffected
    sync_fault_key = jax.random.PRNGKey(_FAULT_FOLD)

    def sync_uplink(grads):
        return _sync_faults(faults, sync_fault_key, grads, jnp.arange(n), n)

    @stage("marina.update")
    def descend(params, g):
        return jax.tree.map(
            lambda w, gg: w - gamma * gg.astype(w.dtype), params, g
        )

    @stage("marina.update")
    def add_delta(g, delta):
        return jax.tree.map(jnp.add, g, delta)

    def robust_delta(key, diffs, rows_n):
        """Robust compressed-round delta: per-worker dense payload rows →
        GAR → parameter-sharding pins (replaces the fused mean)."""
        rows = transport.worker_rows(key, diffs, rows_n)
        delta = aggregator.combine_stacked(rows)
        return jax.tree.map(
            jax.lax.with_sharding_constraint, delta, p_shard
        )

    # dropped/crashed clients ride the collective as zero rows (shape
    # stability across the fleet), but only the surviving uploads bill:
    # booked uplink == (n − f)·ζ_Q, mirroring the PP r·ζ_Q convention
    # (DESIGN.md §4.10). drop+GAR is refused at construction, so the
    # robust path never sees dropped rows.
    drop_uploaded = (
        n - faults.n_faulty(n)
        if faults is not None and faults.attack == "drop" else None
    )

    def compressed_g(key, g, diffs):
        """g^{k+1}: g^k plus the round's compressed mean δ."""
        k_up, k_down = jax.random.split(key)
        k_up = k_up if downlink != "none" else key
        if robust:
            delta = robust_delta(k_up, diffs, n)
        elif downlink == "none":
            # the scatter kernels add δ straight into g, so no dense δ is
            # written; the downlink books the same dense broadcast
            return transport.downlink(k_down, transport.uplink_mean(
                k_up, diffs, out_shardings=p_shard,
                uploaded_rows=drop_uploaded, into=g,
            ))
        else:
            delta = transport.uplink_mean(
                k_up, diffs, out_shardings=p_shard,
                uploaded_rows=drop_uploaded,
            )
        return add_delta(g, transport.downlink(k_down, delta))

    if grad_carry:
        # single-backprop rounds: the carry holds h_i^k = ∇f_i(x^k), so the
        # compressed round differences against it instead of re-running the
        # second vmapped backprop at the old point.
        def sync_step(params, g, h, batch):
            x_new = descend(params, g)
            grads = worker_grads(x_new, batch)
            # h keeps the HONEST gradients: liars lie on the wire, the
            # simulated clients still know their own state
            return (
                x_new,
                transport.sync_aggregate(sync_uplink(grads), aggregator),
                grads,
            )

        def compressed_step(params, g, h, batch, key):
            x_new = descend(params, g)
            g_plus = worker_grads(x_new, batch)
            diffs = _uplink_diff(
                faults, jax.random.fold_in(key, _FAULT_FOLD), g_plus, h,
                jnp.arange(n), n,
            )
            g_new = compressed_g(key, g, diffs)
            # dropped rows keep their old h (the server never heard from
            # them); c_k=False — this IS the compressed branch
            h_new = _carry_refresh(h, g_plus, faults, jnp.asarray(False), n)
            return x_new, g_new, h_new

        def train_step(params, g, h, batch, key):
            c_k, (k_q,), _, _ = _round_keys(key, p)
            return jax.lax.cond(
                c_k,
                lambda _: sync_step(params, g, h, batch),
                lambda _: compressed_step(params, g, h, batch, k_q),
                None,
            )
    else:
        def sync_step(params, g, batch):
            x_new = descend(params, g)
            grads = worker_grads(x_new, batch)
            return x_new, transport.sync_aggregate(
                sync_uplink(grads), aggregator
            )

        def compressed_step(params, g, batch, key):
            x_new = descend(params, g)
            g_plus = worker_grads(x_new, batch)
            g_minus = worker_grads(params, batch)
            diffs = _uplink_diff(
                faults, jax.random.fold_in(key, _FAULT_FOLD), g_plus, g_minus,
                jnp.arange(n), n,
            )
            g_new = compressed_g(key, g, diffs)
            return x_new, g_new

        def train_step(params, g, batch, key):
            c_k, (k_q,), _, _ = _round_keys(key, p)
            return jax.lax.cond(
                c_k,
                lambda _: sync_step(params, g, batch),
                lambda _: compressed_step(params, g, batch, k_q),
                None,
            )

    pp_meta = {}
    if participation is not None:
        # federated PP-MARINA cohort rounds override compressed/train
        # (launch/participation.py — sync rounds stay as built above)
        compressed_step, train_step, pp_meta = build_pp_steps(
            participation, n=n, per_worker=per_worker, p=p, block=BLOCK,
            kb=KB, shared_mask=shared_mask, compression=compression,
            compression_backend=compression_backend, qsgd_s=qsgd_s,
            replicate_params=replicate_params, inner=inner,
            param_shapes=param_shapes, p_shard=p_shard,
            batch_shard=batch_shard, mesh=mesh, transport=transport,
            downlink=downlink, robust=robust, aggregator=aggregator,
            faults=faults, grad_carry=grad_carry, sync_step=sync_step,
            worker_grads=worker_grads, descend=descend,
            robust_delta=robust_delta,
        )

    g_shard = p_shard  # estimator g^k lives like the params
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    repl = shd.replicated(mesh)

    # one fns construction for both carries: grad_carry threads the h slot
    # (worker axes on the leading dim, the leaf's own parameter sharding
    # behind it; donated with params/g) through every entry.
    if grad_carry:
        h_in = (jax.tree.map(
            lambda ns: NamedSharding(mesh, P(wlead, *ns.spec)), p_shard
        ),)
        h_args = (jax.tree.map(
            lambda sh: jax.ShapeDtypeStruct((n, *sh.shape), sh.dtype),
            param_shapes,
        ),)
    else:
        h_in = h_args = ()
    state_out = (p_shard, g_shard, *h_in)
    donate = tuple(range(2 + len(h_in)))

    pp = participation is not None
    sel_spec = (
        jax.ShapeDtypeStruct((participation[0],), jnp.int32) if pp else None
    )

    def entry(name, fn, needs_key, needs_sel=False):
        key_in = (repl,) if needs_key else ()
        key_arg = (key_spec,) if needs_key else ()
        sel_in = (repl,) if needs_sel else ()
        sel_arg = (sel_spec,) if needs_sel else ()

        def scoped(*step_args):
            # ledger bookings from this trace land under the entry's name
            # (train_step traces both cond branches → books sync +
            # compressed together; read per-round-type numbers from the
            # dedicated sync/compressed scopes)
            with transport.scope(name):
                return fn(*step_args)

        return (
            jax.jit(
                scoped,
                in_shardings=(
                    p_shard, g_shard, *h_in, batch_shard, *key_in, *sel_in
                ),
                out_shardings=state_out,
                donate_argnums=donate,
            ),
            (param_shapes, param_shapes, *h_args, batch, *key_arg, *sel_arg),
        )

    fns = {
        "sync_step": entry("sync_step", sync_step, needs_key=False),
        "compressed_step": entry(
            "compressed_step", compressed_step, needs_key=True, needs_sel=pp
        ),
        "train_step": entry(
            "train_step", train_step, needs_key=True, needs_sel=pp
        ),
    }
    return StepBundle(
        mesh=mesh,
        n_workers=n,
        param_shapes=param_shapes,
        param_shardings=p_shard,
        fns=fns,
        meta={
            **pp_meta,
            **({"aggregator": aggregator.rule} if robust else {}),
            **({"faults": faults.attack} if faults is not None else {}),
        },
        transport=transport,
        state_shardings=state_out,
        batch_shardings=batch_shard,
    )


# Serving assembly moved to launch/serve_steps.py (ISSUE 7 split); re-export
# so existing callers (dryrun, perf, check_api_docs) keep one import site.
from repro.launch.serve_steps import build_serve_steps  # noqa: E402,F401
