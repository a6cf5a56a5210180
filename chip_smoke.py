"""Smoke run of MARINA training on TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: the sharded round only

One chip: every main-path Pallas kernel is checked against its jnp oracle on
one call at the flat width of qwen1.5-0.5b, then the Trainer takes compressed
MARINA rounds (``method="marina"``, ``carry_grads=True``) on qwen1.5-0.5b at
full width, first on the 4-bit block-QSGD wire and then on the seeded RandK
wire. Four chips: the sharded grad-carry round of ``build_train_steps`` on a
(4, 1) mesh at full width, and the same round at reduced depth against the
core ``Marina(carry=True)`` trajectory on the same seeds.

Each phase prints its lines first; the last line is one JSON object,
``{"ok": true, "device": {...}}``. Any failed check, or a JAX that finds no
TPU, exits nonzero without that line. Everything runs in this one process;
weights and data come from fixed seeds.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SEED = 0
ARCH = "qwen1.5-0.5b"
# the flat engine's block width and RandK coordinates per block
B, KB = 1024, 8
GAMMA = 0.05
STEPS = 6
N_WORKERS = 2
BATCH = 1
# depth of the one-chip training phases, cut from 24 only because n = 2 does
# not fit: a round holds the state (x, g, two carried gradients), two fresh
# gradients, their packed differences and the non-finite guard's copy of the
# new state. Compiled for a v5e, n = 2 and batch 1 at seq 256 need 19.45 GiB
# of HBM at 24 layers (refused) and 13.5 GiB (block RandK; 13.0 GiB
# QSGD) at 8, leaving room for the trainer's initial parameters
LAYERS_1CHIP = 8
MESH_STEPS = 3
MESH_REF_LAYERS = 2
#: largest relative gap allowed between the mesh round and the core one
MESH_TOL = 1e-3

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


class SmokeFailure(Exception):
    """A check of this smoke run failed."""


def say(*parts):
    print(*parts, flush=True)


def require(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def tpu_devices(count: int):
    devs = jax.devices()
    plat = devs[0].platform
    require(plat == "tpu", f"JAX finds no TPU (platform {plat!r})")
    require(len(devs) >= count, f"{count} chips needed, JAX sees {len(devs)}")
    return devs


def device_line(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def release_programs() -> None:
    """Drop dead arrays and every compiled program: a loaded TPU program
    keeps its temp memory reserved, which the next phase needs."""
    gc.collect()
    jax.clear_caches()


def peak_bytes(dev) -> int:
    """Peak HBM in use since the process started (-1 where not reported)."""
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


# ---------------------------------------------------------------------------
# phase 1: each main-path kernel against its oracle, one real-width call
# ---------------------------------------------------------------------------


def flat_rows(cfg) -> int:
    """Rows of the flat engine's buffer for ``cfg`` (shapes only)."""
    from repro.core.flat import make_layout
    from repro.models import init_params

    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    return make_layout(shapes, block=B).rows


def kernel_cases(n: int, nblk: int, backend: str = "pallas"):
    """(name, make inputs from a key, kernel fn, oracle fn, exact) — exact
    outputs move or hash values and must agree bit for bit; the others sum
    floats in a kernel-chosen order and must agree to the 1-ulp standard of
    DESIGN.md §4.4 (rtol 1e-5, atol 1e-6). ``None`` marks integer codes
    plus float per-block scales; ``"qsgd"`` is judged by
    :func:`qsgd_disagreement` / :func:`natural_disagreement`."""
    from repro.kernels import epilogue as epi
    from repro.kernels import quantize as qz
    from repro.kernels import ref
    from repro.kernels.permk import permk_seeded_workers
    from repro.kernels.randk import randk_seeded_workers, scatter_accum

    P, R = dict(backend=backend), dict(backend="ref")
    interp = backend == "pallas_interpret"
    lo, hi = n // 4, n - n // 4

    def stack(k):
        return jax.random.normal(k, (n, nblk, B), jnp.float32)

    def seeds(k):
        return jax.random.bits(jax.random.fold_in(k, 1), (n,), jnp.uint32)

    def bufs(k):
        return [jax.random.normal(jax.random.fold_in(k, 2 + i), (nblk, B))
                for i in range(2)]

    def levels(k):
        return ref.qsgd_block_workers_ref(stack(k), seeds(k), 7)

    def natural(k):
        return ref.natural_block_workers_ref(stack(k), seeds(k))

    def payload(k):
        return ref.randk_seeded_workers_ref(stack(k), seeds(k), KB, B / KB)

    up = [
        ("randk_seeded_workers", lambda k: (stack(k), seeds(k)),
         lambda x, s: randk_seeded_workers(x, s, KB, B / KB, interpret=interp),
         lambda x, s: ref.randk_seeded_workers_ref(x, s, KB, B / KB), True),
        ("permk_seeded_workers", lambda k: (stack(k), seeds(k)[0]),
         lambda x, s: permk_seeded_workers(x, s, interpret=interp),
         lambda x, s: ref.permk_seeded_workers_ref(x, s, n), True),
        ("qsgd_block_workers", lambda k: (stack(k), seeds(k)),
         lambda x, s: qz.qsgd_block_workers(x, s, 7, **P),
         lambda x, s: qz.qsgd_block_workers(x, s, 7, **R), "qsgd"),
        ("natural_block_workers", lambda k: (stack(k), seeds(k)),
         lambda x, s: qz.natural_block_workers(x, s, **P),
         lambda x, s: qz.natural_block_workers(x, s, **R), "natural"),
        ("nibble_pack", lambda k: (levels(k)[0],),
         lambda q: (qz.nibble_pack(q, **P),),
         lambda q: (qz.nibble_pack(q, **R),), True),
        ("nibble_unpack", lambda k: (ref.nibble_pack_ref(levels(k)[0]),),
         lambda w: (qz.nibble_unpack(w, B, **P),),
         lambda w: (qz.nibble_unpack(w, B, **R),), True),
    ]
    server = [
        ("scatter_accum", payload,
         lambda v, o: (scatter_accum(v, o, B, interpret=interp),),
         lambda v, o: (ref.scatter_accum_ref(v, o, B),), False),
        ("qsgd_dequant_mean", levels,
         lambda q, c: (qz.qsgd_dequant_mean(q, c, 7, **P),),
         lambda q, c: (qz.qsgd_dequant_mean(q, c, 7, **R),), False),
        ("natural_dequant_mean", natural,
         lambda q, c: (qz.natural_dequant_mean(q, c, **P),),
         lambda q, c: (qz.natural_dequant_mean(q, c, **R),), False),
        ("delta_epilogue", lambda k: (bufs(k)[0] * 0.1, *bufs(k)),
         lambda d, g, x: epi.delta_epilogue(d, g, x, GAMMA, **P),
         lambda d, g, x: epi.delta_epilogue(d, g, x, GAMMA, **R), False),
        ("mean_epilogue", lambda k: (stack(k), bufs(k)[1]),
         lambda s, x: epi.mean_epilogue(s, x, GAMMA, **P),
         lambda s, x: epi.mean_epilogue(s, x, GAMMA, **R), False),
        ("scatter_epilogue", lambda k: (*payload(k), *bufs(k)),
         lambda v, o, g, x: epi.scatter_epilogue(v, o, g, x, GAMMA, **P),
         lambda v, o, g, x: epi.scatter_epilogue(v, o, g, x, GAMMA, **R),
         False),
        ("qsgd_epilogue", lambda k: (*levels(k), *bufs(k)),
         lambda q, c, g, x: epi.qsgd_epilogue(q, c, g, x, GAMMA, 7, **P),
         lambda q, c, g, x: epi.qsgd_epilogue(q, c, g, x, GAMMA, 7, **R),
         False),
        ("natural_epilogue", lambda k: (*natural(k), *bufs(k)),
         lambda q, c, g, x: epi.natural_epilogue(q, c, g, x, GAMMA, **P),
         lambda q, c, g, x: epi.natural_epilogue(q, c, g, x, GAMMA, **R),
         False),
        ("trimmed_delta_epilogue", lambda k: (stack(k), *bufs(k)),
         lambda s, g, x: epi.trimmed_delta_epilogue(s, g, x, GAMMA, lo, hi, **P),
         lambda s, g, x: epi.trimmed_delta_epilogue(s, g, x, GAMMA, lo, hi, **R),
         False),
        ("trimmed_sync_epilogue", lambda k: (stack(k), bufs(k)[1]),
         lambda s, x: epi.trimmed_sync_epilogue(s, x, GAMMA, lo, hi, **P),
         lambda s, x: epi.trimmed_sync_epilogue(s, x, GAMMA, lo, hi, **R),
         False),
    ]
    return up + server


def disagreement(got, want, exact):
    """(elements outside the tolerance, max |got − want|) of one output."""
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    diff = jnp.abs(g - w)
    if exact:
        bad = got != want
    else:
        bad = diff > 1e-6 + 1e-5 * jnp.abs(w)
    return jnp.sum(bad), jnp.max(diff)


def qsgd_disagreement(x3d, seeds, got, want, s: int = 7):
    """Block-QSGD levels against the oracle's. The kernel sums each block's
    norm in its own order, and a one-ulp norm moves a pre-floor value
    s·|x|/‖x_b‖ + u that sits on an integer across the floor. So a level may
    differ by one where the oracle's value lies within 1e-5 of an integer
    (recomputed here from the oracle's norms and the documented dither
    stream); every other level must agree exactly, the norms to 1 ulp.
    Returns [levels, norms, level flips at such ties]."""
    from repro.kernels import ref

    (lk, nk), (lr, nr) = got, want
    rows, B = x3d.shape[1:]
    ctr = (jnp.arange(B, dtype=jnp.uint32)[None, :]
           + (jnp.arange(rows, dtype=jnp.uint32) * B)[:, None])
    u = jax.vmap(
        lambda sd: ref.uniform_from_bits_ref(ref.murmur_bits_ref(sd, ctr))
    )(seeds)
    v = s * jnp.abs(x3d) / jnp.where(nr > 0, nr, 1.0)[..., None] + u
    step = lk.astype(jnp.int32) - lr.astype(jnp.int32)
    tie = (jnp.abs(step) == 1) & (jnp.abs(v - jnp.round(v)) <= 1e-5)
    bad = (step != 0) & ~tie
    return [
        (jnp.sum(bad), jnp.max(jnp.abs(step)).astype(jnp.float32)),
        disagreement(nk, nr, False),
        (jnp.sum(tie), jnp.float32(0)),
    ]


def natural_disagreement(x3d, seeds, got, want):
    """Natural-compression codes against the oracle's. Codes come from
    ⌊log2 |x|⌋ and from the block reference ⌊log2 max|x|⌋ + 1; log2 is a
    different approximation in the kernel and in XLA, so an argument within
    1e-6 (relative) of a power of two may land on either side. A code may
    differ where its |x| sits at such a tie, and a whole block's codes and
    scale where its max does; everything else must agree exactly. Returns
    [codes, scales, codes differing at such ties]."""
    del seeds
    (ck, sk), (cr, sr) = got, want
    ax = jnp.abs(x3d.astype(jnp.float32))

    def at_tie(a):
        m, _ = jnp.frexp(a)            # mantissa in [0.5, 1)
        return (a > 0) & ((m - 0.5 <= 0.5e-6) | (1.0 - m <= 1e-6))

    block_tie = at_tie(jnp.max(ax, axis=-1))              # (n, rows)
    tie = at_tie(ax) | block_tie[..., None]
    diff = ck != cr
    return [
        (jnp.sum(diff & ~tie), jnp.max(jnp.abs(
            ck.astype(jnp.float32) - cr.astype(jnp.float32)))),
        (jnp.sum((sk != sr) & ~block_tie), jnp.max(jnp.abs(sk - sr))),
        (jnp.sum(diff & tie), jnp.float32(0)),
    ]


def kernel_phase(n: int, nblk: int, backend: str = "pallas") -> list:
    """Runs every case; returns the names of the kernels that disagree."""
    failed = []
    for name, make, kernel, oracle, exact in kernel_cases(n, nblk, backend):
        def check(key):
            args = make(key)
            got, want = kernel(*args), oracle(*args)
            if exact == "qsgd":
                return qsgd_disagreement(*args, got, want)
            if exact == "natural":
                return natural_disagreement(*args, got, want)
            # integer codes must be exact; per-block f32 scales are
            # computed in kernel order (1-ulp standard)
            flags = [exact if exact is not None else i == 0
                     for i in range(len(got))]
            return [disagreement(a, b, e) for a, b, e in zip(got, want, flags)]

        t0 = time.perf_counter()
        stats = jax.jit(check)(jax.random.PRNGKey(SEED))
        stats = [(int(c), float(m)) for c, m in stats]
        dt = time.perf_counter() - t0
        if exact in ("qsgd", "natural"):  # the last entry counts tie flips
            say(f"kernel {name}: {stats[-1][0]} codes differ at rounding "
                f"ties (see {exact}_disagreement)")
            stats = stats[:-1]
        ok = all(c == 0 for c, _ in stats)
        say(f"kernel {name}: {'ok' if ok else 'MISMATCH'} "
            f"(n={n}, nblk={nblk}, B={B}; outside tolerance "
            f"{[c for c, _ in stats]}, max|Δ| {[m for _, m in stats]}; "
            f"compile+run {dt:.2f}s)")
        if not ok:
            failed.append(name)
    return failed


# ---------------------------------------------------------------------------
# phase 2: the Trainer at full width, one compressed wire per run
# ---------------------------------------------------------------------------


def model_config(layers: int):
    from repro.configs import get_arch
    from repro.models.config import dense_stack

    cfg = get_arch(ARCH).model
    if layers != cfg.num_layers:
        cfg = dataclasses.replace(cfg, segments=dense_stack(layers))
    return cfg


def train_phase(name: str, compressor: str, comp_kwargs: dict, cfg, *,
                n: int, batch: int, steps: int, backend: str = "auto") -> dict:
    """Trainer.run for ``steps`` MARINA carry rounds, then a timed steady
    window of the same compiled chunk. Returns what the phase measured."""
    from repro.models import init_params, param_count
    from repro.train import TrainConfig, Trainer

    params = init_params(jax.random.PRNGKey(SEED), cfg)
    tc = TrainConfig(
        method="marina", compressor=compressor, comp_kwargs=comp_kwargs,
        carry_grads=True, n_workers=n, batch_per_worker=batch,
        gamma=GAMMA, steps=steps, log_every=steps, seed=SEED,
        flat_backend=backend,
    )
    tr = Trainer(cfg, tc, params)
    d = param_count(params)

    # the round program as the trainer dispatches it: lowered from shapes,
    # compiled ahead of the run (the run then loads it from the cache)
    b0 = jax.eval_shape(lambda: tr._batches(0, batch))
    st = jax.eval_shape(tr.method.init, params, b0)
    zero = jax.ShapeDtypeStruct((), jnp.float32)
    steps_arr = jax.ShapeDtypeStruct((steps,), jnp.int32)
    t0 = time.perf_counter()
    lowered = tr._jitted_chunk.lower((st, zero, zero, zero, zero), steps_arr)
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    custom = "tpu_custom_call" in lowered.as_text()
    mem = compiled.memory_analysis()
    del compiled, lowered

    t0 = time.perf_counter()
    state, hist = tr.run()
    jax.block_until_ready(state)
    run_s = time.perf_counter() - t0

    # steady window: one more chunk of the same length — the program the
    # run compiled — timed after block_until_ready
    zeros = [jnp.zeros((), jnp.float32) for _ in range(4)]
    window = jnp.arange(steps, 2 * steps, dtype=jnp.int32)
    t0 = time.perf_counter()
    carry, _ = tr._jitted_chunk((state, *zeros), window)
    jax.block_until_ready(carry)
    step_s = (time.perf_counter() - t0) / steps
    del carry, state

    bits = float(hist.bits_cum[-1])
    per_round = float(tr.engine.payload_bits(n))
    dense = 32.0 * d
    return dict(
        name=name, d=d, n=n, batch=batch, steps=steps, losses=hist.loss,
        bits=bits, per_round=per_round,
        # every round books 32d (sync) or the compressed payload
        compressed=(steps * dense - bits) / (dense - per_round),
        skipped=float(hist.skipped_cum[-1]), compile_s=compile_s,
        run_s=run_s, step_s=step_s, custom=custom, mem=mem,
    )


def report_train(r: dict, dev) -> None:
    GB = 1e9
    m = r["mem"]
    say(f"train {r['name']}: device {dev.device_kind}, params {r['d']:,}, "
        f"n={r['n']} workers x batch {r['batch']}, steps {r['steps']}")
    say(f"train {r['name']}: loss first {r['losses'][0]:.6f} last "
        f"{r['losses'][-1]:.6f} (all {[round(l, 6) for l in r['losses']]})")
    say(f"train {r['name']}: bits booked {r['bits']:.0f} per worker "
        f"({r['compressed']:.2f} compressed rounds at {r['per_round']:.0f} "
        f"bits), skipped rounds {r['skipped']:.0f}")
    say(f"train {r['name']}: compile {r['compile_s']:.2f}s, run "
        f"{r['run_s']:.2f}s, steady step {r['step_s']:.4f}s "
        f"(block_until_ready), tpu_custom_call in step: {r['custom']}")
    say(f"train {r['name']}: step program args {m.argument_size_in_bytes / GB:.3f} GB, "
        f"temp {m.temp_size_in_bytes / GB:.3f} GB; peak_bytes_in_use so far "
        f"{peak_bytes(dev) / GB:.3f} GB")


def check_train(r: dict) -> None:
    losses = np.asarray(r["losses"], np.float64)
    require(bool(np.all(np.isfinite(losses))), f"{r['name']}: non-finite loss")
    require(r["custom"], f"{r['name']}: no tpu_custom_call in the step")
    require(r["skipped"] == 0, f"{r['name']}: the non-finite guard skipped rounds")
    k = r["compressed"]
    require(abs(k - round(k)) < 1e-3 and 1 <= round(k) <= r["steps"],
            f"{r['name']}: bits booked match no count of compressed rounds")


# ---------------------------------------------------------------------------
# four chips: the sharded carry round, and the core trajectory it must equal
# ---------------------------------------------------------------------------


def mesh_state(bundle, cfg, params, g, h, tokens):
    """Place (params, g, h, batch) where the round assembly expects them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import sharding as shd

    tr = bundle.transport
    mesh = bundle.mesh
    wlead = tr.waxes if len(tr.waxes) > 1 else tr.waxes[0]
    h_shard = jax.tree.map(
        lambda ns: NamedSharding(mesh, P(wlead, *ns.spec)), tr.param_shardings
    )
    b_shard = NamedSharding(mesh, shd.batch_spec(tr.waxes, None, 3))
    # copies: the round donates its state, and a placed shard may alias
    # the caller's single-device array
    put = lambda t, sh: jax.device_put(t, sh, may_alias=False)
    return (
        put(params, tr.param_shardings), put(g, tr.param_shardings),
        put(h, h_shard), {"tokens": put(tokens, b_shard)},
    )


def worker_rows_on_distinct_devices(h, n: int) -> bool:
    """Every leaf of the worker-stacked carry has its n rows on n devices."""
    for leaf in jax.tree.leaves(h):
        rows = {}
        for sh in leaf.addressable_shards:
            rows.setdefault(sh.device, set()).add(sh.index[0].start or 0)
        if len(rows) != n or sorted(min(r) for r in rows.values()) != list(range(n)):
            return False
    return True


def mesh_full_width(mesh, n: int, steps: int, cfg, backend: str = "auto") -> dict:
    """The grad-carry round of build_train_steps on the (n, 1) mesh: one
    sync round, then ``steps`` compressed rounds."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_arch
    from repro.launch.distributed import build_train_steps
    from repro.models import init_params

    arch = dataclasses.replace(get_arch(ARCH), model=cfg)
    bundle = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=n * BATCH, seq_len=256,
        gamma=GAMMA, dtype=jnp.float32, grad_carry=True,
        compression_backend=backend,
    )
    tr = bundle.transport
    wlead = tr.waxes if len(tr.waxes) > 1 else tr.waxes[0]
    h_shard = jax.tree.map(
        lambda ns: NamedSharding(mesh, P(wlead, *ns.spec)), tr.param_shardings
    )
    # materialize the state in place, never whole on one chip
    params = jax.jit(lambda: init_params(jax.random.PRNGKey(SEED), cfg),
                     out_shardings=tr.param_shardings)()
    g = jax.jit(lambda: jax.tree.map(jnp.zeros_like, params),
                out_shardings=tr.param_shardings)()
    h = jax.jit(lambda: jax.tree.map(lambda p: jnp.zeros((n, *p.shape), p.dtype),
                                     params), out_shardings=h_shard)()
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (n, BATCH, 256), 0, cfg.vocab_size)
    params, g, h, batch = mesh_state(bundle, cfg, params, g, h, tokens)
    distinct = worker_rows_on_distinct_devices(h, n)

    fs, _ = bundle.fns["sync_step"]
    fc, _ = bundle.fns["compressed_step"]
    with mesh:
        t0 = time.perf_counter()
        x, g, h = fs(params, g, h, batch)
        jax.block_until_ready(g)
        sync_s = time.perf_counter() - t0
        times = []
        for i in range(steps):
            t0 = time.perf_counter()
            x, g, h = fc(x, g, h, batch, jax.random.PRNGKey(SEED + 10 + i))
            jax.block_until_ready(g)
            times.append(time.perf_counter() - t0)
    finite = all(bool(jnp.all(jnp.isfinite(t)))
                 for t in jax.tree.leaves((x, g, h)))
    distinct = distinct and worker_rows_on_distinct_devices(h, n)
    return dict(d=sum(p.size for p in jax.tree.leaves(x)), sync_s=sync_s,
                times=times, finite=finite, distinct=distinct,
                up_bits=float(tr.ledger.total_bits(direction="up")))


def mesh_wire_reference(key, diffs, n: int):
    """The mesh's RandK uplink (``Transport.uplink_mean``, randk family) on
    one device from the jnp oracles: per leaf, rows (n, R, L) of the worker
    differences; kb = L/128 offsets per row from one randint per leaf key;
    values scaled L/kb, scatter-averaged over the n workers."""
    from repro.kernels import ref

    leaves, treedef = jax.tree.flatten(diffs)
    out = []
    for lk, leaf in zip(jax.random.split(key, len(leaves)), leaves):
        shape = leaf.shape[1:]
        L = int(shape[-1])
        R = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
        kb = max(1, L // 128)
        x = leaf.reshape(n, R, L)
        idx = jax.random.randint(lk, (n, R, kb), 0, L, jnp.int32)
        vals = jax.vmap(
            lambda xr, ir: ref.randk_block_compress_ref(xr, ir, L / kb)
        )(x, idx)
        out.append(ref.scatter_accum_ref(vals, idx, L).reshape(shape))
    return jax.tree.unflatten(treedef, out)


def rel_gap(a, b) -> float:
    """max |a − b| over max |b|, across the leaves of two trees."""
    a = [np.asarray(t, np.float64) for t in jax.tree.leaves(a)]
    b = [np.asarray(t, np.float64) for t in jax.tree.leaves(b)]
    num = max(float(np.max(np.abs(u - v))) for u, v in zip(a, b))
    return num / max(max(float(np.max(np.abs(v))) for v in b), 1e-30)


def mesh_vs_core(mesh, n: int, cfg, rounds: int, backend: str = "auto") -> list:
    """The mesh carry round against single-device references on the same
    seeds, at ``cfg``'s depth. Sync rounds (p = 1) run against core
    ``Marina(carry=True)``: its state after k rounds is the lookahead
    (x^{k+1}, g^k, h^k) where the mesh holds (x^k, g^k, h^k). Compressed
    rounds run against :func:`mesh_wire_reference` — core's flat engine
    samples a different RandK wire (blockwise over the packed buffer), so
    the mesh's own wire, computed on one device, is what they must equal.
    Returns the relative gaps of g, h and x per round."""
    from repro.configs import get_arch
    from repro.core import BlockRandK, Marina, make_engine
    from repro.core.flat import unpack
    from repro.launch.distributed import BLOCK, KB as MESH_KB, build_train_steps
    from repro.models import init_params, lm_loss

    arch = dataclasses.replace(get_arch(ARCH), model=cfg)
    bundle = build_train_steps(
        arch, mesh, multi_pod=False, global_batch=n * BATCH, seq_len=256,
        gamma=GAMMA, p=1.0, dtype=jnp.float32, grad_carry=True,
        compression_backend=backend,
    )
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1),
                                (n, BATCH, 256), 0, cfg.vocab_size)
    grad_fn = jax.grad(lambda p_, t: lm_loss(p_, cfg, t))
    grads_fn = jax.jit(jax.vmap(grad_fn, in_axes=(None, 0)))
    eng = make_engine(params, kb=MESH_KB, block=BLOCK, backend=backend)
    core = Marina(grad_fn, BlockRandK(kb=MESH_KB, block=BLOCK), GAMMA, 1.0,
                  engine=eng, carry=True)
    st = jax.jit(core.init)(params, tokens)
    x, g, h, batch = mesh_state(
        bundle, cfg, params, unpack(eng.layout, st.g), st.h, tokens
    )
    del params
    gaps = []
    train, _ = bundle.fns["train_step"]
    comp, _ = bundle.fns["compressed_step"]
    # chip 0 also holds its mesh shard: the references update their state
    # in place (donated), or state + outputs + temps overflow its HBM
    core_step = jax.jit(core.step, donate_argnums=(0,))
    with mesh:
        for k in range(rounds):
            key = jax.random.fold_in(jax.random.PRNGKey(SEED + 42), k)
            x, g, h = train(x, g, h, batch, key)
            st, met = core_step(st, key, tokens)
            require(bool(met.sync_round), "p = 1 round was not a sync round")
            lookahead = jax.tree.map(lambda w, gg: w - GAMMA * gg, x, g)
            gaps.append(dict(kind="sync vs core Marina(carry=True)",
                             g=rel_gap(g, unpack(eng.layout, st.g)),
                             h=rel_gap(h, st.h), x=rel_gap(lookahead, st.params)))
        # the core state and programs on chip 0 make room for the reference
        del st, met, lookahead, core_step
        release_programs()
        dev0 = jax.devices()[0]
        # copies: the mesh round donates (x, g, h), which may alias a shard
        rx, rg, rh = (jax.tree.map(jnp.copy, jax.device_put(t, dev0))
                      for t in (x, g, h))

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
        def ref_round(x, g, h, key):
            x_new = jax.tree.map(lambda w, gg: w - GAMMA * gg, x, g)
            g_plus = grads_fn(x_new, tokens)
            delta = mesh_wire_reference(
                key, jax.tree.map(jnp.subtract, g_plus, h), n
            )
            return x_new, jax.tree.map(jnp.add, g, delta), g_plus

        for k in range(rounds):
            key = jax.random.PRNGKey(SEED + 100 + k)
            x, g, h = comp(x, g, h, batch, key)
            rx, rg, rh = ref_round(rx, rg, rh, key)
            gaps.append(dict(kind="compressed vs its wire on one device",
                             g=rel_gap(g, rg), h=rel_gap(h, rh),
                             x=rel_gap(x, rx)))
    return gaps


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def one_chip(dev) -> list:
    """Kernel checks, then both training phases; returns failed checks."""
    failed = []
    full = model_config(24)
    rows = flat_rows(full)
    failed += kernel_phase(N_WORKERS, rows)

    cfg = model_config(LAYERS_1CHIP)
    if LAYERS_1CHIP != full.num_layers:
        say(f"depth cut: {ARCH} trains {LAYERS_1CHIP} of {full.num_layers} "
            f"layers at full width (d_model {cfg.d_model}, {cfg.num_heads} "
            f"heads, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}); n = "
            f"{N_WORKERS} does not fit one chip at full depth")
    for name, comp, kw in (("block_qsgd", "block_qsgd", {"s": 7}),
                           ("block_randk", "block_randk", {"kb": KB})):
        r = train_phase(name, comp, kw, cfg, n=N_WORKERS, batch=BATCH,
                        steps=STEPS)
        report_train(r, dev)
        try:
            check_train(r)
        except SmokeFailure as e:
            say(f"train {name}: FAILED {e}")
            failed.append(f"train {name}")
    return failed


def four_chips(devs) -> list:
    from repro.launch.topology import make_test_mesh

    n = len(devs)
    mesh = make_test_mesh(n, 1)
    failed = []
    r = mesh_full_width(mesh, n, MESH_STEPS, model_config(24))
    say(f"mesh full width: {ARCH} params {r['d']:,}, ({n}, 1) mesh, "
        f"1 sync + {MESH_STEPS} compressed carry rounds, finite {r['finite']}, "
        f"carry rows on {n} distinct devices {r['distinct']}")
    say(f"mesh full width: sync round {r['sync_s']:.3f}s (incl. compile), "
        f"compressed rounds {[round(t, 4) for t in r['times']]}s, uplink bits "
        f"booked {r['up_bits']:.0f}; peak_bytes_in_use per device "
        f"{[round(peak_bytes(d) / 1e9, 3) for d in devs]} GB")
    if not (r["finite"] and r["distinct"]):
        failed.append("mesh full width")
    release_programs()
    gaps = mesh_vs_core(mesh, n, model_config(MESH_REF_LAYERS), MESH_STEPS)
    for gp in gaps:
        say(f"mesh round ({MESH_REF_LAYERS} layers, full width), {gp['kind']}: "
            f"relative gap g {gp['g']:.3e} h {gp['h']:.3e} x {gp['x']:.3e}")
    if max(max(gp["g"], gp["h"], gp["x"]) for gp in gaps) > MESH_TOL:
        failed.append("mesh vs core")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded round on a (4, 1) mesh")
    args = ap.parse_args(argv)
    devs = tpu_devices(args.chips)

    from repro.compile_cache import enable_compile_cache
    from repro.core.flat import resolve_backend

    cache = enable_compile_cache()
    say(f"device: {devs[0].device_kind} x{len(devs)} ({devs[0].platform}); "
        f"compile cache {cache}")
    require(resolve_backend("auto") == "pallas",
            f"backend 'auto' resolves to {resolve_backend('auto')!r}")
    failed = four_chips(devs[:4]) if args.chips == 4 else one_chip(devs[0])
    require(not failed, f"failed: {failed}")
    print(json.dumps({"ok": True, "device": device_line(devs)}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        sys.exit(1)
