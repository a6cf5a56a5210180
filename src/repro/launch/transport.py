"""Transport layer — the collective primitives every mesh round rides.

Round-assembly code (`launch/distributed.py`) never calls raw collectives
or stages payload shardings itself: it composes a :class:`Transport`,
which owns

* the **sync exchange** — the dense worker-axis mean (one fused psum over
  the packed (nblk, B) flat buffer where packing cannot force a reshard,
  the per-leaf tree exchange otherwise — the PR-4 `flat_sync` policy), and
  the robust GAR variant on the worker gradient stack;
* the **compressed uplink** — per-leaf Block-RandK / Perm-K / QSGD payload
  staging and exchange across the worker axes (`uplink_mean`), plus the
  per-worker dense decode robust GARs aggregate (`worker_rows`);
* the **compressed downlink** — the Q_down(g^{k+1} − g^k) broadcast
  roundtrip (`downlink`);

and a **bytes-by-link-tier ledger** (`repro.core.wire.TierLedger`): every
exchange books its per-worker wire bits under (jit scope, direction, link
tier, collective kind) AT TRACE TIME — the booking is a Python-side effect
of staging the payload, so whatever a step actually lowers is exactly what
the ledger prices, tier-classified by the topology layer
(`launch/topology.py`). Ledger semantics (DESIGN.md §7):

* values are bits per worker per round — the fleet-total divided by the
  worker count, matching the `StepMetrics.bits_per_worker` convention the
  trainer and benchmarks already use (PP rounds with r < n uploaders book
  r·ζ_Q/n);
* a jit step books once per TRACE, not per call (re-executions of the
  compiled step do not re-book); `train_step` traces both `lax.cond`
  branches, so its scope holds sync + compressed bits together — read the
  per-round-type numbers from the dedicated `sync_step`/`compressed_step`
  scopes;
* the tier is the slowest link the exchange's worker axes cross
  (`Topology.tier_for_axes`) — ici inside a pod, dcn across pods or
  across the processes of a local cluster, loopback on single-process
  fake devices.

The numeric semantics of every method are bit-identical to the pre-split
`distributed.py` monolith (the subprocess trajectory tests in
tests/test_sharding.py, tests/test_pp.py and tests/test_multiproc.py are
the safety net).
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import flat as flat_engine
from repro.core import wire
from repro.kernels import ref as kref
from repro.launch.topology import Topology
from repro.tracing import stage

PyTree = Any


def _qsgd_quantize_rows(key: jax.Array, x, s: int):
    """Per-row ℓ2-norm s-level stochastic quantization over the LAST axis:
    levels = sign(x)·⌊s|x|/‖row‖ + u⌋ as int8, norms f32 (kept-dims). The
    one quantize formula both wire directions share — uplink and downlink
    must never drift apart."""
    assert 1 <= s <= 127, f"s={s} does not fit the int8 wire"
    xf = x.astype(jnp.float32)
    norm = jnp.sqrt(jnp.sum(xf * xf, axis=-1, keepdims=True))
    safe = jnp.where(norm > 0, norm, 1.0)
    u = jax.random.uniform(key, x.shape)
    q = (jnp.sign(xf) * jnp.floor(s * jnp.abs(xf) / safe + u)).astype(jnp.int8)
    return q, norm.astype(jnp.float32)


def _nibble_roundtrip_rows(q: jax.Array) -> jax.Array:
    """Push int8 levels through the genuine 4-bit wire (|level| ≤ 7): pack
    eight two's-complement nibbles per uint32 lane word, unpack back."""
    L = q.shape[-1]
    lead = q.shape[:-1]
    flat = q.reshape(-1, L)
    return kref.nibble_unpack_ref(kref.nibble_pack_ref(flat), L).reshape(
        *lead, L
    )


def _gather_along_last(x3d, idx3d, scale, backend):
    """(n, R, L) gather via the backend-switched flat primitive."""
    n_, R, L = x3d.shape
    kb = idx3d.shape[-1]
    out = flat_engine.block_gather(
        x3d.reshape(n_ * R, L), idx3d.reshape(n_ * R, kb), scale, backend
    )
    return out.reshape(n_, R, kb)


def _scatter_mean_last(vals3d, idx3d, L, backend):
    """(n_eff, R, kb) scatter-accumulate mean over workers → (R, L) f32."""
    return flat_engine.block_scatter_mean(
        vals3d.astype(jnp.float32), idx3d, L, backend
    )


def _arr_bits(*arrays) -> float:
    """Total wire bits of the staged payload arrays (dtype-exact)."""
    return float(sum(a.size * a.dtype.itemsize * 8 for a in arrays))


# -- retry/timeout/backoff (DESIGN.md §4.10) ---------------------------------
#
# Real-cluster transport operations — gloo bring-up, worker spawn, the
# coordinator rendezvous — fail transiently (port races, slow container
# start). The policy below is the one knob both the launch layer
# (topology.spawn_local_cluster / run_resilient_cluster) and CI share:
# bounded attempts, exponential backoff, a per-attempt timeout the caller
# threads into whatever blocking call it wraps.


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Timeout/retry-with-backoff dial for flaky transport operations.

    ``timeout_s`` bounds a single attempt (callers pass it to their
    blocking primitive — ``Popen.communicate``, socket connect, …);
    ``retries`` is the number of RE-tries after the first attempt (0 =
    fail fast); the sleep before retry ``i`` (0-based) is
    ``backoff_s · backoff_mult**i``. Frozen/hashable: safe as static
    config on step bundles and CI env."""

    timeout_s: float = 120.0
    retries: int = 1
    backoff_s: float = 1.0
    backoff_mult: float = 2.0

    def __post_init__(self):
        if self.timeout_s <= 0.0:
            raise ValueError("timeout_s must be positive")
        if self.retries < 0:
            raise ValueError("retries must be non-negative")
        if self.backoff_s < 0.0:
            raise ValueError("backoff_s must be non-negative")
        if self.backoff_mult < 1.0:
            raise ValueError("backoff_mult must be >= 1 (backoff never shrinks)")

    def backoff(self, attempt: int) -> float:
        """Sleep before retry ``attempt`` (0-based): backoff_s·mult^attempt."""
        return self.backoff_s * self.backoff_mult ** attempt


def retry_call(
    fn: Callable,
    policy: RetryPolicy,
    *,
    retryable: tuple = (Exception,),
    on_retry: Optional[Callable] = None,
    sleep: Callable = time.sleep,
):
    """Run ``fn()`` under ``policy``: up to ``1 + policy.retries`` attempts,
    exponential backoff between them, re-raising the last error when the
    budget is spent. Only ``retryable`` exception types trigger a retry —
    anything else propagates immediately (a config error must not burn the
    backoff budget). ``on_retry(attempt, exc)`` observes each failure
    before the sleep; ``sleep`` is injectable for tests."""
    for attempt in range(policy.retries + 1):
        try:
            return fn()
        except retryable as exc:
            if attempt >= policy.retries:
                raise
            if on_retry is not None:
                on_retry(attempt, exc)
            sleep(policy.backoff(attempt))


@dataclasses.dataclass
class Transport:
    """Worker-axis collective interface + bytes-by-tier ledger (module doc).

    Built once per step bundle by :func:`make_transport`; frozen wire
    policy (compression family, quantization levels, payload packing,
    staging, downlink mode) lives here so round assembly passes trees and
    keys, never wire flags.
    """

    mesh: Any
    topology: Topology
    waxes: tuple
    n: int
    backend: str = "auto"
    compression: str = "randk"
    qsgd_s: int = 15
    packed_payload: bool = False
    staged_payload: bool = True
    shared_mask: bool = False
    downlink_mode: str = "none"
    downlink_s: int = 7
    # sync-exchange policy (configured by make_transport)
    flat_sync: bool = False
    sync_layout: Any = None
    sync_buf_shard: Any = None
    param_shardings: Any = None
    ledger: wire.TierLedger = dataclasses.field(
        default_factory=wire.TierLedger
    )
    _scope: str = "unscoped"

    # -- ledger -------------------------------------------------------------

    @contextlib.contextmanager
    def scope(self, name: str):
        """Tag ledger bookings with the jit step being traced. Round
        assembly wraps each step body so one shared transport attributes
        collectives to sync_step / compressed_step / train_step."""
        prev = self._scope
        self._scope = name
        try:
            yield
        finally:
            self._scope = prev

    def book(self, direction: str, kind: str, bits: float,
             axes: Optional[tuple] = None) -> None:
        """Book per-worker wire bits under the current scope, tiered by the
        worker axes the exchange crosses (defaults to this transport's).
        Public so round assembly can account exchanges the transport does
        not stage itself (the flat-PP engine aggregate)."""
        t = self.topology.tier_for_axes(
            self.waxes if axes is None else axes
        )
        self.ledger.book(self._scope, direction, t, kind, bits)

    def wire_by_tier(self) -> dict:
        """{scope: {tier: {direction: bits}}} ledger summary (JSON-ready)."""
        scopes = {s for (s, _d, _t, _k) in self.ledger.bits}
        return {s: self.ledger.by_tier(s) for s in sorted(scopes)}

    # -- shardings ----------------------------------------------------------

    @property
    def worker_sharding(self) -> NamedSharding:
        """Payload rows sharded across the worker axes."""
        wspec = (
            P(self.waxes if len(self.waxes) != 1 else self.waxes[0])
            if self.waxes else P()
        )
        return NamedSharding(self.mesh, wspec)

    @property
    def replicated(self) -> NamedSharding:
        """Replicated across the whole mesh (the payload collective's
        destination layout)."""
        return NamedSharding(self.mesh, P())

    def _row_spec(self, rows: int) -> P:
        """Spec of an (rows, …) payload stack: worker-sharded when the rows
        split evenly over the worker axes, else replicated."""
        size = int(np.prod([self.mesh.shape[a] for a in self.waxes]))
        if not self.waxes or rows % size:
            return P()
        return P(self.waxes if len(self.waxes) != 1 else self.waxes[0])

    def _on_devices(self, fn, in_specs, out_specs):
        """``fn`` calls the backend-switched block kernels. GSPMD cannot
        partition a Mosaic kernel, so on a multi-device mesh a Pallas
        backend runs ``fn`` per device under ``shard_map`` (every mesh
        axis manual) on the shards ``in_specs`` name; the jnp oracles
        partition like any other op."""
        if self.mesh.size == 1 or flat_engine.resolve_backend(self.backend) == "ref":
            return fn
        return jax.shard_map(
            fn, mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )

    def _gather(self, x3d, idx3d, scale: float, spec: P):
        """Per-row gather of an (n, R, L) payload stack laid out by spec."""
        return self._on_devices(
            lambda x, i: _gather_along_last(x, i, scale, self.backend),
            (spec, spec), spec,
        )(x3d, idx3d)

    def _scatter_mean(self, vals3d, idx3d, L: int):
        """Replicated scatter-accumulate mean of (n, R, kb) payloads."""
        return self._on_devices(
            lambda v, i: _scatter_mean_last(v, i, L, self.backend),
            (P(), P()), P(),
        )(vals3d, idx3d)

    # -- sync exchange ------------------------------------------------------

    @stage("transport.sync")
    def sync_mean(self, grads: PyTree) -> PyTree:
        """Dense worker-axis mean of the stacked gradients: one fused psum
        over the packed (nblk, B) flat buffer when ``flat_sync`` (packing
        cannot force a reshard), else the per-leaf tree exchange. Books the
        n dense f32 uploads (32d/worker up) + the dense estimator broadcast
        (32d down)."""
        d = sum(
            int(np.prod(t.shape[1:])) for t in jax.tree.leaves(grads)
        )
        self.book("up", "psum", wire.dense_f32_bits(d))
        self.book("down", "broadcast", wire.downlink_dense_bits(d))
        if self.flat_sync:
            lay = self.sync_layout
            bufs = flat_engine.pack_stacked(lay, grads)
            bufs = jax.lax.with_sharding_constraint(bufs, self.sync_buf_shard)
            g_new = flat_engine.unpack(lay, jnp.mean(bufs, axis=0))
            return jax.tree.map(
                jax.lax.with_sharding_constraint, g_new, self.param_shardings
            )
        return jax.tree.map(lambda t: jnp.mean(t, axis=0), grads)

    def sync_aggregate(self, grads: PyTree, aggregator=None) -> PyTree:
        """Sync-round server aggregation: the robust GAR on the worker
        gradient stack when one is configured (combine_stacked, pinned back
        to the parameter shardings), else :meth:`sync_mean`. The wire cost
        is identical either way — n dense uploads — and is booked here."""
        if aggregator is not None and aggregator.robust:
            d = sum(
                int(np.prod(t.shape[1:])) for t in jax.tree.leaves(grads)
            )
            self.book("up", "psum", wire.dense_f32_bits(d))
            self.book("down", "broadcast", wire.downlink_dense_bits(d))
            g_new = aggregator.combine_stacked(grads)
            return jax.tree.map(
                jax.lax.with_sharding_constraint, g_new, self.param_shardings
            )
        return self.sync_mean(grads)

    # -- compressed uplink --------------------------------------------------

    @stage("transport.uplink")
    def uplink_mean(
        self,
        key: jax.Array,
        diffs: PyTree,
        *,
        rows_n: Optional[int] = None,
        out_shardings: Optional[PyTree] = None,
        rows_sharded: bool = True,
        uploaded_rows: Optional[int] = None,
    ) -> PyTree:
        """Per-leaf compressed exchange across workers → dense mean update.

        Layout: each leaf (rows, *shape) is treated as (rows, R, L) with L
        its last dimension — gathers and scatters act along L only, so they
        stay local to whatever sharding the leaf has on its leading dims,
        and scatter indices never exceed L (no int64 pressure at
        10^10-parameter scale).

        Families (policy fixed at construction — DESIGN.md §4):

        * ``randk`` independent masks (paper-faithful): kb ≈ L/128 indices
          per row with replacement (unbiased, ω ≈ L/kb); the n·K payload
          replicates across the mesh — the all-gather the paper prices at
          ζ_Q. ``packed_payload`` ships bf16 values + int16 indices (int32
          when L > 32767).
        * ``shared_mask`` (beyond-paper MARINA-SM): all workers share one
          mask, so the worker mean commutes with the gather — a ζ-sized
          psum replaces the n·ζ all-gather; forfeits the 1/n variance
          averaging (ω instead of ω/√n in Thm 2.1).
        * ``permk`` (Szlendak et al. 2021): one shared permutation
          partitions each leaf's lane dimension; the exchange is an exact
          all-to-all of disjoint d/n shards — values only, the permutation
          regenerates from the replicated round key; inverse-perm gather,
          no scatter. Leaves with L % n != 0 fall back to independent
          masks.
        * ``qsgd`` (the packed quantization wire — DESIGN.md §4.6):
          workers quantize dense diff rows against per-row ℓ2 norms under
          worker-local staged constraints; the collective carries int8
          levels (4-bit nibbles in uint32 with ``packed_payload`` and
          s ≤ 7) + f32 norms, and every device runs the worker-indexed
          dequantize-and-mean — no (n, d) f32 buffer materializes.

        ``rows_n`` overrides the row count (PP cohorts upload r < n rows);
        ``rows_sharded=False`` marks a row stack that is NOT worker-sharded
        (cohort rows replicate — the staging constraints are skipped).
        Books the staged payload's dtype-exact bits: fleet-total / n per
        round under the worker-axis tier. ``uploaded_rows`` scales the
        booking when some of the staged rows never crossed the wire —
        dropped/crashed clients ride the collective as zero rows for shape
        stability, but only the surviving uploads bill (DESIGN.md §4.10:
        booked uplink == arrived·ζ_Q, mirroring the PP r·ζ_Q convention).
        """
        n = self.n if rows_n is None else rows_n
        if uploaded_rows is not None and not 0 <= uploaded_rows <= n:
            raise ValueError(
                f"uploaded_rows={uploaded_rows} outside [0, {n}] staged rows"
            )
        up_frac = 1.0 if uploaded_rows is None else uploaded_rows / n

        def book_up(kind: str, bits: float) -> None:
            self.book("up", kind, bits * up_frac)
        waxes = self.waxes if rows_sharded else ()
        staged = self.staged_payload if rows_sharded else False
        packed = self.packed_payload

        leaves, treedef = jax.tree.flatten(diffs)
        out_shard_leaves = (
            jax.tree.leaves(out_shardings) if out_shardings is not None
            else [None] * len(leaves)
        )
        keys = jax.random.split(key, len(leaves))
        outs = []
        for lk, leaf, osh in zip(keys, leaves, out_shard_leaves):
            shape = leaf.shape[1:]
            L = int(shape[-1])
            R = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
            kb = max(1, L // 128)
            scale = L / kb
            x = leaf.reshape(n, R, L)

            wspec = P(waxes if len(waxes) != 1 else waxes[0]) if waxes else P()
            worker_sharded = NamedSharding(self.mesh, wspec)
            repl = self.replicated

            if self.compression == "permk" and L % n == 0:
                C = L // n
                perm = jax.random.permutation(lk, L)  # shared across workers
                idx = jnp.broadcast_to(perm.reshape(n, 1, C), (n, R, C))
                vals = self._gather(x, idx, float(n), self._row_spec(n) if staged else P())
                if staged:
                    vals = jax.lax.with_sharding_constraint(
                        vals, worker_sharded
                    )
                # the exact all-to-all of d/n shards: VALUES ONLY ride the
                # wire (bf16 when packed); the permutation regenerates from
                # the replicated round key on every device — no index
                # payload, no scatter on arrival.
                sent = vals.astype(jnp.bfloat16) if packed else vals
                book_up("all-to-all", _arr_bits(sent) / self.n)
                sent = jax.lax.with_sharding_constraint(sent, repl)
                by_slot = jnp.moveaxis(
                    sent.astype(jnp.float32), 0, 1
                ).reshape(R, L)
                inv = jnp.argsort(perm)
                dense = (jnp.take(by_slot, inv, axis=1) / n).astype(leaf.dtype)
            elif self.compression == "qsgd":
                # shared row-quantize formula (int8-wire bound asserted
                # inside); norm is (n, R, 1) f32
                q, norm = _qsgd_quantize_rows(lk, x, int(self.qsgd_s))
                s = int(self.qsgd_s)
                if staged:
                    # quantize under the worker-sharded layout: the dense
                    # f32 diffs never leave their worker
                    q = jax.lax.with_sharding_constraint(q, worker_sharded)
                    norm = jax.lax.with_sharding_constraint(
                        norm, worker_sharded
                    )
                if packed and s <= 7 and L % 8 == 0:
                    # genuine 4-bit wire: eight signed nibbles per uint32
                    # lane word cross the collective (0.5 B/coord)
                    words = kref.nibble_pack_ref(q.reshape(n * R, L))
                    words = words.reshape(n, R, L // 8)
                    book_up("all-gather", _arr_bits(words, norm) / self.n)
                    words = jax.lax.with_sharding_constraint(words, repl)
                    q = kref.nibble_unpack_ref(
                        words.reshape(n * R, L // 8), L
                    ).reshape(n, R, L)
                else:
                    book_up("all-gather", _arr_bits(q, norm) / self.n)
                    q = jax.lax.with_sharding_constraint(q, repl)
                norm = jax.lax.with_sharding_constraint(norm, repl)

                # fused dequantize-and-mean: worker-indexed accumulation
                # into one (R, L) f32 buffer — input bandwidth stays int8
                def dq_body(w, acc):
                    qw = jax.lax.dynamic_index_in_dim(q, w, 0, keepdims=False)
                    nw = jax.lax.dynamic_index_in_dim(
                        norm, w, 0, keepdims=False
                    )
                    return acc + qw.astype(jnp.float32) * (nw / s)

                acc = jax.lax.fori_loop(
                    0, n, dq_body, jnp.zeros((R, L), jnp.float32)
                )
                dense = (acc / n).astype(leaf.dtype)
            elif self.shared_mask:
                idx = jax.random.randint(lk, (R, kb), 0, L, jnp.int32)
                vals = self._gather(
                    x, jnp.broadcast_to(idx, (n, R, kb)), scale,
                    self._row_spec(n) if staged else P(),
                )
                if staged:
                    # pin the gather to the worker-sharded layout so the
                    # partitioner cannot replicate the dense diffs instead
                    vals = jax.lax.with_sharding_constraint(
                        vals, worker_sharded
                    )
                # ζ-sized psum over the worker axis; stays sharded on R
                book_up("psum", _arr_bits(vals) / self.n)
                vals_mean = jnp.mean(vals, axis=0)                # (R, kb)
                dense = self._scatter_mean(
                    vals_mean[None], idx[None], L
                ).astype(leaf.dtype)
            else:
                idx = jax.random.randint(lk, (n, R, kb), 0, L, jnp.int32)
                vals = self._gather(
                    x, idx, scale, self._row_spec(n) if staged else P()
                )
                if staged:
                    # stage 1: gather under the worker-sharded layout
                    # (local); stage 2 (below): all-gather only the K-sized
                    # payload
                    vals = jax.lax.with_sharding_constraint(
                        vals, worker_sharded
                    )
                if packed:
                    # §Perf: bf16 values + int16 indices on the wire — 8 →
                    # 4 B/coord, degrading to int32 indices (8 → 6 B/coord)
                    # when L > 32767 (int16 can't address the lane)
                    idx_wire = idx if L > 32767 else idx.astype(jnp.int16)
                    book_up(
                        "all-gather",
                        _arr_bits(vals.astype(jnp.bfloat16), idx_wire)
                        / self.n,
                    )
                    vals = jax.lax.with_sharding_constraint(
                        vals.astype(jnp.bfloat16), repl
                    ).astype(leaf.dtype)
                    idx = jax.lax.with_sharding_constraint(
                        idx_wire, repl
                    ).astype(jnp.int32)
                else:
                    book_up("all-gather", _arr_bits(vals, idx) / self.n)
                    vals = jax.lax.with_sharding_constraint(vals, repl)
                    idx = jax.lax.with_sharding_constraint(idx, repl)
                dense = self._scatter_mean(vals, idx, L).astype(leaf.dtype)

            out = dense.reshape(shape)
            if osh is not None and staged:
                # pin the decompressed accumulator to the destination
                # leaf's sharding — otherwise the partitioner may
                # materialize the scatter replicated (a 435 GB buffer for
                # the 671B expert stack)
                out = jax.lax.with_sharding_constraint(out, osh)
            outs.append(out)
        return jax.tree.unflatten(treedef, outs)

    def worker_rows(
        self,
        key: jax.Array,
        diffs: PyTree,
        rows_n: int,
        *,
        uploaded_rows: Optional[int] = None,
    ) -> PyTree:
        """Per-worker DENSE payload rows — what the server actually
        received from each client, before any aggregation (DESIGN.md §4.9).

        Robust GARs cannot ride the fused dequantize-and-mean of
        :meth:`uplink_mean` (trim/median/Krum/clip don't commute with the
        mean), so the robust wire decodes every worker's payload to a dense
        (n, *leaf) row stack for ``ServerAggregator.combine_stacked``. Key
        discipline is IDENTICAL to the mean path (one split per leaf, same
        per-leaf draw shapes), so the honest rows carry exactly the values
        the fused path would have averaged. The wire cost is unchanged —
        the same payloads cross the same link — and books identically;
        the dense row stack costs the fused path's memory saving.
        ``permk`` is refused upstream (coordinates partition across
        workers; nothing to aggregate robustly). ``uploaded_rows`` scales
        the booking exactly like :meth:`uplink_mean` — rows that never
        arrived ride as zeros for shape stability but do not bill."""
        n = rows_n
        if uploaded_rows is not None and not 0 <= uploaded_rows <= n:
            raise ValueError(
                f"uploaded_rows={uploaded_rows} outside [0, {n}] staged rows"
            )
        up_frac = 1.0 if uploaded_rows is None else uploaded_rows / n

        def book_up(kind: str, bits: float) -> None:
            self.book("up", kind, bits * up_frac)
        leaves, treedef = jax.tree.flatten(diffs)
        keys = jax.random.split(key, len(leaves))
        rows = []
        for lk, leaf in zip(keys, leaves):
            shape = leaf.shape[1:]
            L = int(shape[-1])
            R = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
            kb = max(1, L // 128)
            scale = L / kb
            x = leaf.reshape(n, R, L)
            if self.compression == "qsgd":
                q, norm = _qsgd_quantize_rows(lk, x, int(self.qsgd_s))
                s = int(self.qsgd_s)
                if self.packed_payload and s <= 7 and L % 8 == 0:
                    book_up(
                        "all-gather",
                        (_arr_bits(norm) + _arr_bits(q) / 2) / self.n,
                    )
                    q = _nibble_roundtrip_rows(q)
                else:
                    book_up("all-gather", _arr_bits(q, norm) / self.n)
                dense = q.astype(jnp.float32) * (norm / s)
            else:  # independent Block-RandK masks
                idx = jax.random.randint(lk, (n, R, kb), 0, L, jnp.int32)
                spec = self._row_spec(n)
                vals = self._gather(x, idx, scale, spec)
                book_up("all-gather", _arr_bits(vals, idx) / self.n)
                # each worker's row decodes where it lives
                dense = self._on_devices(
                    jax.vmap(lambda v, i: _scatter_mean_last(
                        v[None], i[None], L, self.backend
                    )),
                    (spec, spec), spec,
                )(vals, idx)
            rows.append(dense.reshape((n,) + tuple(shape)))
        return jax.tree.unflatten(treedef, rows)

    # -- compressed downlink ------------------------------------------------

    def downlink(self, key: jax.Array, delta: PyTree) -> PyTree:
        """Compressed downlink on the aggregated round delta (DESIGN.md
        §4.7). The server broadcasts Q_down(g^{k+1} − g^k) = Q_down(δ_up);
        since δ_up is replicated after aggregation, every device compresses
        with the SHARED round key (one payload, one broadcast) and
        decompress-accumulates — the estimator recursion runs on the
        broadcast sequence, so worker replicas stay bitwise in sync.
        "qsgd": per-row ℓ2-norm s-level quantization, int8 (4-bit nibbles
        with ``packed_payload`` and s ≤ 7). "randk": seeded K-subsample
        (K = L/128 per row), indices regenerate from the key. "none"
        passes the dense delta through and books the dense f32 broadcast
        the ledger used to silently ignore."""
        mode, s = self.downlink_mode, self.downlink_s
        if mode == "none":
            d = sum(int(np.prod(t.shape)) for t in jax.tree.leaves(delta))
            self.book("down", "broadcast", wire.downlink_dense_bits(d))
            return delta
        leaves, treedef = jax.tree.flatten(delta)
        keys = jax.random.split(key, len(leaves))
        outs = []
        for lk, leaf in zip(keys, leaves):
            shape = leaf.shape
            L = int(shape[-1])
            R = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
            x = leaf.reshape(R, L).astype(jnp.float32)
            if mode == "qsgd":
                # the same shared row-quantize formula as the uplink
                q, norm = _qsgd_quantize_rows(lk, x, s)
                if self.packed_payload and s <= 7 and L % 8 == 0:
                    # the broadcast genuinely crosses the 4-bit wire
                    self.book(
                        "down", "broadcast",
                        _arr_bits(norm) + _arr_bits(q) / 2,
                    )
                    q = _nibble_roundtrip_rows(q)
                else:
                    self.book("down", "broadcast", _arr_bits(q, norm))
                y = q.astype(jnp.float32) * (norm / s)
            elif mode == "randk":
                kb = max(1, L // 128)
                idx = jax.random.randint(lk, (R, kb), 0, L, jnp.int32)
                vals = jnp.take_along_axis(x, idx, axis=1) * (L / kb)
                # seeded subsample: values only, indices regenerate
                self.book("down", "broadcast", _arr_bits(vals))
                y = jnp.zeros((R, L), jnp.float32).at[
                    jnp.arange(R)[:, None], idx
                ].add(vals)
            else:
                raise ValueError(f"unknown downlink {mode!r}")
            outs.append(y.reshape(shape).astype(leaf.dtype))
        return jax.tree.unflatten(treedef, outs)


def make_transport(
    mesh,
    topology: Topology,
    waxes: tuple,
    n: int,
    *,
    backend: str = "auto",
    compression: str = "randk",
    qsgd_s: int = 15,
    packed_payload: bool = False,
    staged_payload: bool = True,
    shared_mask: bool = False,
    downlink: str = "none",
    downlink_s: int = 7,
    flat_sync: bool = False,
    sync_layout=None,
    sync_buf_shard=None,
    param_shardings=None,
) -> Transport:
    """Build the per-bundle :class:`Transport` (wire policy + sync-exchange
    layout + a fresh tier ledger). One transport per step bundle: the
    ledger's scopes separate the bundle's jitted entries."""
    return Transport(
        mesh=mesh, topology=topology, waxes=tuple(waxes), n=n,
        backend=backend, compression=compression, qsgd_s=qsgd_s,
        packed_payload=packed_payload, staged_payload=staged_payload,
        shared_mask=shared_mask, downlink_mode=downlink,
        downlink_s=downlink_s, flat_sync=flat_sync, sync_layout=sync_layout,
        sync_buf_shard=sync_buf_shard, param_shardings=param_shardings,
    )
