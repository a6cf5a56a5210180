"""Flat-buffer compression engine (DESIGN.md §4).

The per-leaf path in :mod:`repro.core.compressors` compresses a gradient
pytree leaf by leaf in a Python loop and the server densifies every worker
payload to an ``(n, d)`` tree before averaging — O(n·d) memory and FLOPs for
a round whose whole point is touching only ζ_Q ≪ d coordinates. This module
replaces that with a single packed representation:

* :class:`FlatLayout` — a *static* description of how a pytree maps onto one
  zero-padded ``(rows, B)`` block buffer (B lane-aligned, default 1024): the
  ``nblk`` blocks that hold the tree, rounded up to whole row tiles.
  Computed once per parameter structure, with its rows split into runs:
  where every leaf is whole blocks, each is reshaped straight to its block
  rows; otherwise one flat run writes every leaf into one vector and
  relayouts it into rows. Pack concatenates the runs along the rows,
  unpack slices them.
* :class:`FlatEngine` — the fused compress → uplink → decompress-mean
  pipeline over that buffer. Per-worker payloads are ``(nblk, kb)`` seeded
  RandK values whose indices are *regenerated from the seed* on the server
  (wire format: one uint32 seed + K values, DESIGN.md §4.2); aggregation is a
  scatter-accumulate into a single ``(nblk, B)`` accumulator — the ``(n, d)``
  dense worker trees are never materialized, so the round's cost scales with
  ζ_Q, not n·d.

Backends (DESIGN.md §5): ``pallas`` dispatches to the TPU kernels in
:mod:`repro.kernels` (``randk_seeded`` / ``scatter_accum`` /
``qsgd_block_workers`` / ``qsgd_dequant_mean`` / …); ``ref`` is the
bit-exact pure-jnp oracle from :mod:`repro.kernels.ref` (the two share the
murmur3 counter RNG, so payloads are identical bit for bit);
``pallas_interpret`` runs the kernels in interpret mode for CPU validation.
``auto`` picks ``pallas`` on TPU and ``ref`` elsewhere.

Samplers: seeded RandK (f32 values wire), PermK (correlated partition,
DESIGN.md §4.5), and the packed quantization wire (DESIGN.md §4.6) —
blockwise QSGD (4-bit/int8 levels + per-block norms), blockwise natural
compression, and the bandwidth-optimal RandK∘QSGD composition.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.tracing import stage

PyTree = Any

DEFAULT_BLOCK = 1024  # 8 × 128 VMEM tile width; must be a power of two
#: buffer rows come in whole (32, 128) int8 tiles (8 f32 rows, 16 bf16): a
#: worker stack (n, rows, B) then never splits a tile at a worker boundary,
#: which the TPU compiler otherwise relayouts at great cost (minutes of
#: compile and ~10 GB of host memory at a 0.2B-parameter flat width)
ROW_ALIGN = 32

BACKENDS = ("auto", "pallas", "pallas_interpret", "ref")


def resolve_backend(backend: str = "auto") -> str:
    """'auto' → 'pallas' on TPU, bit-exact 'ref' (pure jnp) elsewhere."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}, expected one of {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    return backend


# ---------------------------------------------------------------------------
# Static layout: pytree ↔ (nblk, B) padded block buffer
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """Where one leaf lives inside the flat buffer (static metadata)."""

    offset: int
    size: int
    shape: tuple
    dtype: Any


@dataclasses.dataclass(frozen=True)
class Run:
    """Rows ``[row, row + nrows)`` of the buffer, written in one piece.

    An ``aligned`` run is one leaf whose offset and size are whole blocks:
    it is already ``(size // B, B)`` block rows. A flat run's leaves are
    flattened, written after one another and cut into rows, zero padded
    past the last leaf.
    """

    row: int
    nrows: int
    slots: tuple    # indices into FlatLayout.slots
    aligned: bool


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Precomputed static layout of a pytree over a padded block buffer.

    Leaves are concatenated in ``jax.tree.flatten`` order at offsets
    ``slots[i].offset``; every entry past ``d`` is a structural zero
    (DESIGN.md §4.1). ``nblk`` blocks hold the tree and are what the wire
    carries; the buffer has ``rows`` ≥ nblk of them, and the rows past nblk
    are zeros that compress to zeros and book no wire bits. Pack and unpack
    move each of the ``runs`` (:class:`Run`, see ``_runs``) as a whole.
    Hashable/static: safe to close over in jitted functions.
    """

    treedef: Any
    slots: tuple
    runs: tuple     # Run, in row order from row 0 (see _runs)
    d: int          # true dimension Σ leaf sizes
    block: int      # B, lane-aligned power of two
    nblk: int       # number of blocks = ceil(d / B)
    rows: int       # buffer rows: nblk rounded up to ROW_ALIGN
    dtype: Any      # buffer compute dtype (leaves are cast in/out)

    @property
    def padded(self) -> int:
        """Coordinates the wire accounts for: nblk whole blocks."""
        return self.nblk * self.block

    @property
    def row_share(self) -> float:
        """Share of the ``d`` coordinates that aligned runs move as block
        rows, with no flattening or relayout: 1 or 0 (``_runs``)."""
        moved = sum(self.slots[r.slots[0]].size for r in self.runs if r.aligned)
        return moved / self.d if self.d else 0.0


def _runs(slots: list, block: int, rows: int) -> tuple:
    """Split the rows into :class:`Run` s: one a leaf where every leaf is
    whole blocks, else one flat run of every leaf and all ``rows``. The TPU
    compiler writes a flat run's relayout to a buffer of its own and then
    copies it into the rows, which costs more than aligned leaves beside it
    save (timed on the chip up to a quarter of the coordinates aligned,
    PERF.md §6)."""
    if all(s.size % block == 0 for s in slots):
        return tuple(Run(s.offset // block, s.size // block, (i,), True)
                     for i, s in enumerate(slots))
    return (Run(0, rows, tuple(range(len(slots))), False),)


def make_layout(
    tree: PyTree, block: int = DEFAULT_BLOCK, dtype=jnp.float32
) -> FlatLayout:
    """Build the static layout for ``tree`` (shapes/dtypes only are read)."""
    assert block > 0 and block & (block - 1) == 0, "block must be a power of two"
    leaves, treedef = jax.tree.flatten(tree)
    slots = []
    off = 0
    for leaf in leaves:
        size = int(np.prod(leaf.shape)) if leaf.shape else 1
        slots.append(LeafSlot(off, size, tuple(leaf.shape), leaf.dtype))
        off += size
    d = off
    nblk = max(1, -(-d // block))
    rows = -(-nblk // ROW_ALIGN) * ROW_ALIGN
    return FlatLayout(
        treedef=treedef, slots=tuple(slots), runs=_runs(slots, block, rows), d=d,
        block=block, nblk=nblk, rows=rows, dtype=dtype,
    )


def _write_leaves(layout: FlatLayout, leaves: list, lead: tuple) -> jax.Array:
    """Leaves with leading axes ``lead`` → ``(*lead, rows, B)``, one piece a
    run, concatenated along the rows with zeros past the last run. An
    aligned leaf is reshaped straight to its block rows. The leaves of a
    flat run are written in place into a zero buffer of the run's rows,
    which is then cut into rows. (A concatenate compiles to the same
    in-place writes, but the compiler drops their scope on the way; and
    ``vmap`` would turn each write into a scatter.)"""
    B, dt = layout.block, layout.dtype
    parts = []
    for run in layout.runs:
        if run.aligned:
            parts.append(leaves[run.slots[0]].reshape(*lead, run.nrows, B)
                         .astype(dt))
            continue
        flat = jnp.zeros((*lead, run.nrows * B), dt)
        for i in run.slots:
            s = layout.slots[i]
            flat = jax.lax.dynamic_update_slice(
                flat, leaves[i].reshape(*lead, s.size).astype(dt),
                (0,) * len(lead) + (s.offset - run.row * B,),
            )
        parts.append(flat.reshape(*lead, run.nrows, B))
    tail = layout.rows - sum(run.nrows for run in layout.runs)
    if tail:
        parts.append(jnp.zeros((*lead, tail, B), dt))
    return jnp.concatenate(parts, axis=-2)


@stage("flat.pack")
def pack(layout: FlatLayout, tree: PyTree) -> jax.Array:
    """Pytree → ``(rows, B)`` padded buffer (zeros past ``d``)."""
    return _write_leaves(layout, layout.treedef.flatten_up_to(tree), ())


@stage("flat.unpack")
def unpack(layout: FlatLayout, buf: jax.Array) -> PyTree:
    """Inverse of :func:`pack`; restores leaf shapes and dtypes. An aligned
    leaf is its rows of ``buf`` reshaped; the leaves of a flat run are cut
    from the flattened rows of that run."""
    outs = [None] * len(layout.slots)
    for run in layout.runs:
        rows = buf[run.row : run.row + run.nrows]
        if run.aligned:
            outs[run.slots[0]] = rows
            continue
        flat, base = rows.reshape(-1), run.row * layout.block
        for i in run.slots:
            s = layout.slots[i]
            outs[i] = flat[s.offset - base : s.offset - base + s.size]
    outs = [o.reshape(s.shape).astype(s.dtype) for o, s in zip(outs, layout.slots)]
    return jax.tree.unflatten(layout.treedef, outs)


@stage("flat.pack")
def pack_stacked(layout: FlatLayout, tree: PyTree) -> jax.Array:
    """Worker-stacked pytree (leading axis n) → ``(n, rows, B)``."""
    leaves = layout.treedef.flatten_up_to(tree)
    return _write_leaves(layout, leaves, leaves[0].shape[:1])


# ---------------------------------------------------------------------------
# Backend-switched block primitives (shared with launch/distributed.py)
# ---------------------------------------------------------------------------


def seeded_offsets(seed: jax.Array, nblk: int, block: int, kb: int) -> jax.Array:
    """(nblk, kb) int32 offsets in [0, block) from the murmur3 counter RNG.

    Bit-identical to what the ``randk_seeded`` kernel samples on-chip for the
    same ``seed`` (the server regenerates indices from the 4-byte seed instead
    of receiving them — DESIGN.md §4.2).
    """
    from repro.kernels import ref

    ctr = (
        jnp.arange(kb, dtype=jnp.uint32)[None, :]
        + (jnp.arange(nblk, dtype=jnp.uint32) * kb)[:, None]
    )
    bits = ref.murmur_bits_ref(seed.astype(jnp.uint32), ctr)
    return (bits & jnp.uint32(block - 1)).astype(jnp.int32)


@stage("flat.compress")
def block_compress(
    x2d: jax.Array, seed: jax.Array, kb: int, scale: float, backend: str = "auto"
):
    """Seeded RandK over a block buffer: (nblk, B) → values/offsets (nblk, kb)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        from repro.kernels import ref

        return ref.randk_seeded_ref(x2d, seed.astype(jnp.uint32), kb, scale)
    from repro.kernels.randk import randk_seeded

    return randk_seeded(
        x2d, seed, kb, scale, interpret=(backend == "pallas_interpret")
    )


@stage("flat.compress")
def block_compress_workers(
    x3d: jax.Array, seeds: jax.Array, kb: int, scale: float, backend: str = "auto"
):
    """Per-worker seeded RandK: (n, nblk, B) + (n,) seeds → (n, nblk, kb) ×2."""
    backend = resolve_backend(backend)
    if backend == "ref":
        from repro.kernels import ref

        return ref.randk_seeded_workers_ref(
            x3d, seeds.astype(jnp.uint32), kb, scale
        )
    from repro.kernels.randk import randk_seeded_workers

    return randk_seeded_workers(
        x3d, seeds, kb, scale, interpret=(backend == "pallas_interpret")
    )


def block_gather(
    x2d: jax.Array, offsets: jax.Array, scale: float, backend: str = "auto"
) -> jax.Array:
    """Gather+scale with host-supplied offsets: (nblk, B), (nblk, kb) → (nblk, kb).
    Its caller names the stage (the mesh transport's ``transport.uplink``)."""
    backend = resolve_backend(backend)
    if backend == "ref":
        from repro.kernels import ref

        return ref.randk_block_compress_ref(x2d, offsets, scale)
    from repro.kernels.randk import randk_gather

    return randk_gather(
        x2d, offsets, scale, interpret=(backend == "pallas_interpret")
    )


def block_scatter_mean(
    values: jax.Array, offsets: jax.Array, block: int, backend: str = "auto",
    into: jax.Array | None = None,
) -> jax.Array:
    """Scatter-accumulate mean over workers: (n, nblk, kb) ×2 → (nblk, block).

    The only dense buffer is the single (nblk, block) accumulator — the n
    worker payloads stay ζ-sized (never densified per worker). With
    ``into`` (nblk, block) the result is ``into + mean``, added in the same
    sweep. Its callers name the stage: ``flat.compress`` in the engine,
    ``transport.uplink`` in the mesh transport.
    """
    backend = resolve_backend(backend)
    if backend == "ref":
        from repro.kernels import ref

        return ref.scatter_accum_ref(values, offsets, block, into)
    from repro.kernels.randk import scatter_accum

    return scatter_accum(
        values, offsets, block, into=into,
        interpret=(backend == "pallas_interpret"),
    )


@stage("flat.compress")
def block_permk_workers(x3d: jax.Array, seed: jax.Array, backend: str = "auto"):
    """PermK uplink: (n, nblk, B) + ONE shared seed → values/offsets
    (n, nblk, B/n). The n workers' offsets partition every block (correlated
    compressor — DESIGN.md §4.5)."""
    backend = resolve_backend(backend)
    n = x3d.shape[0]
    if backend == "ref":
        from repro.kernels import ref

        return ref.permk_seeded_workers_ref(x3d, seed.astype(jnp.uint32), n)
    from repro.kernels.permk import permk_seeded_workers

    return permk_seeded_workers(
        x3d, seed, interpret=(backend == "pallas_interpret")
    )


@stage("flat.compress")
def permk_concat_mean(
    values: jax.Array, seed: jax.Array, block: int, backend: str = "auto"
) -> jax.Array:
    """Scatter-free PermK aggregation: (n, nblk, B/n) payloads → (nblk, B)
    mean via concatenation + inverse-perm gather. Equal to
    :func:`block_scatter_mean` on the same payloads (disjoint supports ⇒ the
    scatter has no collisions), but never builds scatter index machinery —
    this is the server-side shape of the exact d/n-shard exchange."""
    del backend  # pure gather; the jnp form is already the fused shape
    from repro.kernels import ref

    return ref.permk_concat_mean_ref(values, seed, block)


@stage("flat.compress")
def block_qsgd_workers(x3d: jax.Array, seeds: jax.Array, s: int,
                       backend: str = "auto"):
    """Fused blockwise QSGD uplink: (n, nblk, B) + (n,) seeds →
    (levels (n, nblk, B) int8, norms (n, nblk) f32). Per-block ℓ2 norms ride
    the wire; the dither is regenerated from the seed and never transmitted."""
    from repro.kernels import quantize

    return quantize.qsgd_block_workers(
        x3d, seeds, s, backend=resolve_backend(backend)
    )


@stage("flat.compress")
def block_qsgd_dequant_mean(levels: jax.Array, norms: jax.Array, s: int,
                            backend: str = "auto") -> jax.Array:
    """Fused dequantize-and-mean: (n, nblk, B) int8 + (n, nblk) f32 →
    (nblk, B) f32. Aggregation reads the payloads at int8 bandwidth; the only
    dense f32 buffer is the single (nblk, B) accumulator."""
    from repro.kernels import quantize

    return quantize.qsgd_dequant_mean(
        levels, norms, s, backend=resolve_backend(backend)
    )


@stage("flat.compress")
def block_natural_workers(x3d: jax.Array, seeds: jax.Array,
                          backend: str = "auto"):
    """Fused blockwise natural-compression uplink: (n, nblk, B) + (n,) seeds
    → (codes (n, nblk, B) int8, scales (n, nblk) f32)."""
    from repro.kernels import quantize

    return quantize.natural_block_workers(
        x3d, seeds, backend=resolve_backend(backend)
    )


@stage("flat.compress")
def block_natural_dequant_mean(codes: jax.Array, scales: jax.Array,
                               backend: str = "auto") -> jax.Array:
    """Fused decode-and-mean of natural payloads → (nblk, B) f32."""
    from repro.kernels import quantize

    return quantize.natural_dequant_mean(
        codes, scales, backend=resolve_backend(backend)
    )


@stage("flat.compress")
def nibble_roundtrip(levels: jax.Array, block: int,
                     backend: str = "auto") -> jax.Array:
    """Push int8 levels through the genuine 4-bit wire: pack two-per-byte
    into uint32 lane words, then unpack (sign-extended). The identity on
    levels in [-8, 7] — running it in the pipeline keeps the simulation
    honest about what the wire can represent. levels: (n, nblk, B)."""
    from repro.kernels import quantize

    backend = resolve_backend(backend)
    assert levels.shape[-1] == block, (
        f"levels last dim {levels.shape[-1]} != wire block width {block}"
    )
    words = quantize.nibble_pack(levels, backend=backend)
    return quantize.nibble_unpack(words, block, backend=backend)


def key_to_seed(key: jax.Array) -> jax.Array:
    """PRNG key → uint32 seed for the counter-based kernel RNG."""
    return jax.random.bits(key, dtype=jnp.uint32)


def seeded_payload_bits(nblk: int, kb: int) -> float:
    """Wire bits of one seeded-RandK payload (delegates to
    :mod:`repro.core.wire`, the single source of truth — DESIGN.md §4.6)."""
    from . import wire

    return wire.seeded_randk_bits(nblk, kb)


# ---------------------------------------------------------------------------
# The fused engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FlatEngine:
    """Fused compressed-round pipeline over a packed flat buffer.

    One engine instance is built per parameter structure (the layout is
    static) and handed to the MARINA-family optimizers; their compressed
    branch then runs

        pack (n workers) → seeded RandK (kb coords / B-block / worker)
        → scatter-accumulate mean → unpack

    with every stage dispatched through the kernel backend switch. Worker w's
    seed is derived from the round key exactly like the per-leaf tree path
    derives its worker keys (``jax.random.split``), and its counter stream
    restarts at 0 — masks are independent across workers (the 1/n variance
    averaging of Thm 2.1) and, on block-aligned single-leaf layouts, the flat
    path reproduces the tree path's randomness bit for bit (the trajectory
    equivalence test in tests/test_flat.py).

    ω/ζ_Q bookkeeping (DESIGN.md §4.3): sampling is with replacement, so
    E[Q(x)] = x with E‖Q(x)−x‖² = (B/kb)(1−1/B)‖x‖² ≤ ω‖x‖², ω = B/kb.

    ``sampler="permk"`` switches the uplink to the *correlated* PermK sampler
    (DESIGN.md §4.5): one shared seed per round, each worker's payload a
    disjoint (nblk·B)/n slice of the permuted buffer (wire: 32 + 32·(nblk·B)/n
    bits per worker), aggregation collision-free. ``kb`` is ignored there —
    the chunk width is forced to B/n by the partition.

    The *packed quantization wire* (DESIGN.md §4.6) adds three samplers whose
    on-wire representation is bit-packed rather than f32:

    * ``"qsgd"`` — blockwise s-level ℓ2 QSGD: per-block f32 norm + one level
      per coordinate (signed nibble for s ≤ 7 — the pipeline genuinely packs
      through uint32 lane words — int8 for s ≤ 127). Aggregation is the fused
      dequantize-and-mean kernel: int8 input bandwidth, one f32 accumulator.
    * ``"natural"`` — blockwise power-of-two stochastic rounding (ω = 1/8):
      per-block f32 scale + int8 exponent-delta codes.
    * ``"randk_qsgd"`` — the bandwidth-optimal composition: seeded RandK
      keeps kb coords per block, QSGD quantizes ONLY those K values (per-block
      norms of the sampled vector). Wire: seed + nblk norms + K packed levels;
      aggregation dequantizes the K-sized payload and scatter-accumulates.
    """

    layout: FlatLayout
    kb: int = 8
    backend: str = "auto"
    sampler: str = "randk"  # "randk" | "permk" | "qsgd" | "natural" | "randk_qsgd"
    s: int = 7              # quantization levels for the qsgd-family samplers
    #: optional NamedSharding pinned onto the derived per-worker seeds. On a
    #: GSPMD mesh the partitioner may otherwise re-partition the
    #: split→bits threefry chain of :meth:`worker_seeds` and produce
    #: DIFFERENT seed values than the same key yields on one device
    #: (observed on the CPU SPMD partitioner; an optimization barrier does
    #: not prevent it), silently breaking core↔mesh trajectory equality.
    #: Single-device engines leave it None — a no-op.
    seed_constraint: Any = None

    SAMPLERS = ("randk", "permk", "qsgd", "natural", "randk_qsgd")

    def __post_init__(self):
        assert self.sampler in self.SAMPLERS, self.sampler
        if self.sampler in ("qsgd", "randk_qsgd"):
            from . import wire

            assert 1 <= self.s <= wire.INT8_MAX_S, (
                f"s={self.s} does not fit the int8 wire"
            )

    @stage("flat.compress")
    def worker_seeds(self, key: jax.Array, n: int) -> jax.Array:
        """(n,) uint32 seeds, mirroring the tree path's per-worker key split."""
        seeds = jax.vmap(key_to_seed)(jax.random.split(key, n))
        if self.seed_constraint is not None:
            seeds = jax.lax.with_sharding_constraint(seeds, self.seed_constraint)
        return seeds

    @stage("flat.compress")
    def _shared_seed(self, key: jax.Array) -> jax.Array:
        """ONE uint32 seed for the correlated (PermK) sampler, with the same
        partitioner pin as :meth:`worker_seeds`."""
        seed = key_to_seed(key)
        if self.seed_constraint is not None:
            seed = jax.lax.with_sharding_constraint(seed, self.seed_constraint)
        return seed

    @property
    def scale(self) -> float:
        return self.layout.block / self.kb

    @property
    def omega(self) -> float:
        """Def-1.1 ω of one worker's sampler (PermK's is collection-level —
        ask the compressor). Composition: 1+ω multiplies over independent
        stages, the QSGD stage acting on the kb-dim sampled block vector."""
        B = self.layout.block
        if self.sampler == "randk":
            return B / self.kb
        if self.sampler == "qsgd":
            return min(B / self.s**2, float(np.sqrt(B)) / self.s)
        if self.sampler == "natural":
            return 1.0 / 8.0
        if self.sampler == "randk_qsgd":
            w_q = min(self.kb / self.s**2, float(np.sqrt(self.kb)) / self.s)
            return (1.0 + B / self.kb) * (1.0 + w_q) - 1.0
        raise AssertionError("PermK ω is n−1; ask the compressor")

    def payload_bits(self, n: "int | None" = None) -> float:
        """Wire bits per worker per compressed round, from the shared wire
        accounting (repro.core.wire — DESIGN.md §4.6). A permk engine
        REQUIRES the worker count — its chunk width is the partition share
        B/n, and a defaulted n would silently book the full dense buffer as
        one worker's compressed payload, corrupting the loss-vs-bits ledger."""
        from . import wire

        lay = self.layout
        if self.sampler == "permk":
            assert n is not None, "permk payload_bits needs the worker count"
            assert lay.block % n == 0, "n must divide the block width"
            return wire.permk_bits(lay.padded, n)
        if self.sampler == "qsgd":
            return wire.block_qsgd_bits(lay.nblk, lay.block, self.s)
        if self.sampler == "natural":
            return wire.block_natural_bits(lay.nblk, lay.block)
        if self.sampler == "randk_qsgd":
            return wire.randk_qsgd_bits(lay.nblk, self.kb, self.s)
        return wire.seeded_randk_bits(lay.nblk, self.kb)

    # -- stages -------------------------------------------------------------
    def compress_stacked(self, seeds: jax.Array, bufs: jax.Array):
        """(n, nblk, B) + (n,) seeds → per-worker payloads (values, offsets).

        Workers are folded into the kernel grid (one pallas_call over n·nblk
        blocks) rather than vmapped; per-worker seeds live in SMEM.
        """
        return block_compress_workers(
            bufs, seeds, self.kb, self.scale, self.backend
        )

    def decompress_mean(self, vals: jax.Array, offs: jax.Array) -> jax.Array:
        """(n, nblk, kb) payloads → (nblk, B) dense mean over workers."""
        return block_scatter_mean(vals, offs, self.layout.block, self.backend)

    # -- per-worker dense decode (robust GARs — DESIGN.md §4.9) -------------
    @stage("flat.compress")
    def worker_dense(self, key: jax.Array, bufs: jax.Array, n: int) -> jax.Array:
        """Decode each worker's payload densely: (n, nblk, B) diffs →
        (n, nblk, B) f32 rows Q_i(Δ_i). The robust aggregation rules need the
        individual worker values — a scatter-*mean* is exactly what they must
        not compute. Same seeds/payloads as :meth:`aggregate` (the server
        combination is the only thing that changes). PermK refuses: its
        workers partition the coordinates (exactly one worker per coordinate
        — there is no per-coordinate sample to trim or median)."""
        from repro.kernels import ref as kref
        from . import wire

        if self.sampler == "permk":
            raise ValueError(
                "PermK partitions coordinates across workers; robust "
                "aggregation is undefined on its payloads (DESIGN.md §4.9)"
            )
        if self.sampler == "qsgd":
            seeds = self.worker_seeds(key, n)
            levels, norms = block_qsgd_workers(bufs, seeds, self.s, self.backend)
            if self.s <= wire.NIBBLE_MAX_S:
                levels = nibble_roundtrip(levels, self.layout.block, self.backend)
            return levels.astype(jnp.float32) * (norms / self.s)[..., None]
        if self.sampler == "natural":
            seeds = self.worker_seeds(key, n)
            codes, scales = block_natural_workers(bufs, seeds, self.backend)
            return jax.vmap(kref.natural_decode_ref)(codes, scales)
        if self.sampler == "randk_qsgd":
            seeds = self.worker_seeds(key, n)
            vals, offs = self.compress_stacked(seeds, bufs)
            levels, norms = kref.qsgd_sampled_quantize_ref(vals, seeds, self.s)
            vals = kref.randk_qsgd_dequant_ref(levels, norms, self.s)
        else:  # randk
            vals, offs = self.compress_stacked(self.worker_seeds(key, n), bufs)
        # per-worker scatter (n = 1 per row: the scatter-mean divides by 1)
        return jax.vmap(
            lambda v, o: block_scatter_mean(
                v[None], o[None], self.layout.block, self.backend
            )
        )(vals, offs)

    # -- the hot path -------------------------------------------------------
    def fused_delta(
        self, key: jax.Array, diffs: PyTree, n: int, aggregator=None
    ) -> PyTree:
        """Compressed-round aggregate: worker-stacked diff tree → mean Q tree.

        Equivalent to decompressing every worker payload and averaging, but
        the per-worker dense (d,) trees are never built. The PermK sampler
        shares ONE seed across workers (the correlation IS the algorithm) and
        aggregates scatter-free: the disjoint chunks concatenate through the
        inverse permutation. A robust ``aggregator`` (DESIGN.md §4.9) swaps
        the mean for its GAR over the per-worker decoded rows.
        """
        bufs = pack_stacked(self.layout, diffs)
        return unpack(self.layout, self.aggregate(key, bufs, n, aggregator))

    @stage("flat.compress")
    def aggregate(
        self, key: jax.Array, bufs: jax.Array, n: int, aggregator=None
    ) -> jax.Array:
        """Server-side aggregate over packed diffs: (n, nblk, B) → the dense
        (nblk, B) round delta (the buffer-level body of :meth:`fused_delta`,
        exposed so the downlink can re-compress the aggregate before it ever
        leaves flat form — DESIGN.md §4.7). With a robust ``aggregator``
        (a :class:`repro.core.aggregators.ServerAggregator` whose rule is not
        the mean) the combination runs the GAR over :meth:`worker_dense`."""
        if aggregator is not None and aggregator.robust:
            return aggregator.combine_rows(self.worker_dense(key, bufs, n))
        if self.sampler == "permk":
            seed = self._shared_seed(key)  # shared: all workers, same perm
            vals, _ = block_permk_workers(bufs, seed, self.backend)
            dense = permk_concat_mean(
                vals, seed, self.layout.block, self.backend
            )
        elif self.sampler == "qsgd":
            from . import wire

            seeds = self.worker_seeds(key, n)
            levels, norms = block_qsgd_workers(bufs, seeds, self.s, self.backend)
            if self.s <= wire.NIBBLE_MAX_S:
                # the levels genuinely cross the wire as packed nibbles
                levels = nibble_roundtrip(levels, self.layout.block, self.backend)
            dense = block_qsgd_dequant_mean(levels, norms, self.s, self.backend)
        elif self.sampler == "natural":
            seeds = self.worker_seeds(key, n)
            codes, scales = block_natural_workers(bufs, seeds, self.backend)
            dense = block_natural_dequant_mean(codes, scales, self.backend)
        elif self.sampler == "randk_qsgd":
            from repro.kernels import ref
            from . import wire

            # the gather/scatter stay on the backend-switched fused kernels;
            # only the K-sized quantize/dequant runs in plain jnp (ζ ≪ d —
            # bandwidth irrelevant, and bit-exact on every backend).
            seeds = self.worker_seeds(key, n)
            vals, offs = self.compress_stacked(seeds, bufs)
            levels, norms = ref.qsgd_sampled_quantize_ref(vals, seeds, self.s)
            # the K-sized levels are wire-accounted at 4/8 bits (wire.py) but
            # skip the in-pipeline pack/unpack: nibble_pack∘nibble_unpack is
            # a proven bit-exact identity on |level| ≤ s ≤ 7 (tests), and on
            # CPU the roundtrip defeats XLA's gather/scatter fusion for no
            # semantic difference. The dense qsgd sampler above DOES cross
            # the packed representation (its payload is where packing pays).
            vals = ref.randk_qsgd_dequant_ref(levels, norms, self.s)
            dense = self.decompress_mean(vals, offs)
        else:
            vals, offs = self.compress_stacked(self.worker_seeds(key, n), bufs)
            dense = self.decompress_mean(vals, offs)
        return dense

    # -- the fused server epilogue (DESIGN.md §4.7) -------------------------
    @stage("flat.compress")
    def fused_round(
        self,
        key: jax.Array,
        diff_bufs: jax.Array,
        n: int,
        g2d: jax.Array,
        x2d: jax.Array,
        gamma: float,
        down: "FlatEngine | None" = None,
        down_key: "jax.Array | None" = None,
        aggregator=None,
    ):
        """Finish a compressed round in ONE (nblk, B)-tile sweep: sample the
        uplink payloads from the packed diffs, then run the fused epilogue
        (kernels/epilogue.py) — dequant/scatter-mean → ``g += δ`` →
        ``x −= γ·g`` — directly on the wire representation. Returns
        ``(g_new (nblk, B) f32, x_new (nblk, B) layout-dtype)``.

        With ``down`` set (a second engine sharing this layout), the round is
        bidirectional: the uplink aggregates to the dense δ_up, the server
        broadcasts ``Q_down(δ_up)`` (= Q_down(g^{k+1} − g^k) — the estimator
        recursion runs on the broadcast sequence), and the epilogue consumes
        the single downlink payload (n = 1): the worker-side
        decompress-accumulate."""
        from repro.kernels import epilogue as epi
        from repro.kernels import ref as kref

        if down is not None:
            delta = self.aggregate(key, diff_bufs, n, aggregator)
            assert down.layout.block == self.layout.block and (
                down.layout.nblk == self.layout.nblk
            ), "downlink engine must share the uplink layout"
            assert down.sampler != "permk", (
                "PermK is a partition across n receivers; a broadcast "
                "downlink has one payload — use randk/qsgd/natural"
            )
            # the downlink's single server payload is past the GAR already
            return down.fused_round(down_key, delta[None], 1, g2d, x2d, gamma)

        backend = self.backend
        if aggregator is not None and aggregator.robust:
            rows = self.worker_dense(key, diff_bufs, n)
            if aggregator.coordinatewise:
                lo, hi = aggregator.trim_bounds(n)
                return epi.trimmed_delta_epilogue(
                    rows, g2d, x2d, gamma, lo, hi, backend=backend
                )
            delta = aggregator.combine_rows(rows)
            return epi.delta_epilogue(delta, g2d, x2d, gamma, backend=backend)
        if self.sampler == "permk":
            seed = self._shared_seed(key)
            vals, _ = block_permk_workers(diff_bufs, seed, backend)
            delta = permk_concat_mean(vals, seed, self.layout.block, backend)
            return epi.delta_epilogue(delta, g2d, x2d, gamma, backend=backend)
        if self.sampler == "qsgd":
            from . import wire

            seeds = self.worker_seeds(key, n)
            levels, norms = block_qsgd_workers(
                diff_bufs, seeds, self.s, backend
            )
            if self.s <= wire.NIBBLE_MAX_S:
                levels = nibble_roundtrip(levels, self.layout.block, backend)
            return epi.qsgd_epilogue(
                levels, norms, g2d, x2d, gamma, self.s, backend=backend
            )
        if self.sampler == "natural":
            seeds = self.worker_seeds(key, n)
            codes, scales = block_natural_workers(diff_bufs, seeds, backend)
            return epi.natural_epilogue(
                codes, scales, g2d, x2d, gamma, backend=backend
            )
        if self.sampler == "randk_qsgd":
            seeds = self.worker_seeds(key, n)
            vals, offs = self.compress_stacked(seeds, diff_bufs)
            levels, norms = kref.qsgd_sampled_quantize_ref(vals, seeds, self.s)
            vals = kref.randk_qsgd_dequant_ref(levels, norms, self.s)
            return epi.scatter_epilogue(
                vals, offs, g2d, x2d, gamma, backend=backend
            )
        vals, offs = self.compress_stacked(self.worker_seeds(key, n), diff_bufs)
        return epi.scatter_epilogue(vals, offs, g2d, x2d, gamma, backend=backend)

    def fused_sync(self, grad_bufs: jax.Array, x2d: jax.Array, gamma: float,
                   aggregator=None):
        """Sync-round epilogue: worker-mean over the ONE packed gradient
        buffer (the fused psum replacing the per-leaf tree exchange) fused
        with the iterate update. Returns (g_new, x_new) like fused_round.
        A robust ``aggregator`` replaces the mean with its GAR: the
        coordinate-wise rules run the trimmed sync kernel; Krum/norm-clip
        reduce the rows first and reuse the dense-δ epilogue (g = GAR)."""
        from repro.kernels import epilogue as epi

        if aggregator is not None and aggregator.robust:
            n = grad_bufs.shape[0]
            if aggregator.coordinatewise:
                lo, hi = aggregator.trim_bounds(n)
                return epi.trimmed_sync_epilogue(
                    grad_bufs, x2d, gamma, lo, hi, backend=self.backend
                )
            g_agg = aggregator.combine_rows(grad_bufs)
            return epi.delta_epilogue(
                g_agg, jnp.zeros_like(g_agg), x2d, gamma, backend=self.backend
            )
        return epi.mean_epilogue(grad_bufs, x2d, gamma, backend=self.backend)

    # -- test/validation helpers -------------------------------------------
    def roundtrip_worker(self, key: jax.Array, tree: PyTree) -> PyTree:
        """Single-worker Q(x) through the full fused pipeline (for tests)."""
        stacked = jax.tree.map(lambda x: x[None], tree)
        return self.fused_delta(key, stacked, 1)


def make_engine(
    params: PyTree,
    kb: int = 8,
    block: int = DEFAULT_BLOCK,
    backend: str = "auto",
    dtype=jnp.float32,
    sampler: str = "randk",
    s: int = 7,
) -> FlatEngine:
    """Engine for a parameter tree: layout once, fused pipeline forever."""
    return FlatEngine(
        layout=make_layout(params, block=block, dtype=dtype), kb=kb,
        backend=backend, sampler=sampler, s=s,
    )


def make_downlink(
    engine: FlatEngine,
    sampler: str = "qsgd",
    kb: "int | None" = None,
    s: "int | None" = None,
) -> FlatEngine:
    """Downlink engine sharing ``engine``'s layout/backend: the server-side
    compressor of Q_down(g^{k+1} − g^k) (DESIGN.md §4.7). PermK is rejected
    at use time (a broadcast has one payload, not an n-partition)."""
    return dataclasses.replace(
        engine, sampler=sampler,
        kb=engine.kb if kb is None else kb,
        s=engine.s if s is None else s,
    )
