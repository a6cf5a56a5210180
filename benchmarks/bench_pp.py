"""Federated PP-MARINA reproduction harness (writes BENCH_pp.json).

Three measurements, rendered into EXPERIMENTS.md (§Federated partial
participation + §Byzantine robustness) by scripts/update_perf.py:

* **Loss-vs-bits curves** — the paper's Figs. 1–2 comparison shape on the
  Dirichlet(α) non-IID binclass problem (core/problems.py): PP-MARINA at
  r ∈ {8, 4} vs full-participation MARINA vs DIANA vs compressed GD (DCGD),
  all on the same RandK wire, each method's x-axis the FLEET-total uplink
  bits its ledger booked (wire.py truth). The table reports ‖∇f‖² reached at
  matched bit budgets across α ∈ {0.1, 1, ∞} heterogeneity.
* **Round-time rows** — the r/n compute+wire saving on a real mesh: an
  8-fake-device subprocess times the full-participation compressed round vs
  the cohort-mapped PP round (only r of n shards backprop, r payload rows on
  the wire) on the reduced-qwen LM step, and books the per-round wire bits
  from repro.core.wire.
* **Straggler wall-clock curves** (`--only async`) — the deadline-cohort
  harness of DESIGN.md §4.10: synchronous MARINA (every round waits for the
  slowest client) vs DeadlineMarina at honest-quantile deadlines (missed
  clients ride the carry table as PP non-participants), with and without
  stale-difference acceptance, under lognormal / exponential / fixed-slow
  compute-time models (core/roundtime.py). Reports simulated wall clock to
  MATCHED loss — the `async` section of BENCH_pp.json.
* **Adversarial grid** (`--only robust`) — the Byzantine stress test of
  DESIGN.md §4.9: attack (sign_flip / omniscient mean_shift / label_flip /
  drop) × GAR (mean / trimmed_mean / coordinate_median / krum / norm_clip)
  × faulty fraction ∈ {0, 1/8, 1/4} on PP-MARINA over the dense 4-bit QSGD
  wire, final honest-objective loss at MATCHED bit budgets (every payload
  cell books identical wire bits; only `drop` books fewer — the carry
  substitution's exact uploaded-row accounting). Plus the robust round-time
  rows: the fused robust epilogues vs the fused mean on the reduced-qwen
  flat layout — the `scripts/check_robust.py` CI gate metric.

Run: PYTHONPATH=src python -m benchmarks.bench_pp [--quick]
     [--only pp|robust|async|all]
(or  PYTHONPATH=src python -m benchmarks.run --only pp|robust|async [--quick])
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    DCGD,
    DeadlineMarina,
    Diana,
    FaultSpec,
    Marina,
    PPMarina,
    RandK,
    RoundTimeModel,
    ServerAggregator,
    async_marina_gamma,
    diana_alpha,
    diana_gamma,
    flip_binclass_labels,
    make_compressor,
    marina_gamma,
    pp_marina_gamma,
)
from repro.core import wire
from repro.core.problems import (
    BinClassData,
    binclass_full_grad,
    binclass_smoothness,
    make_dirichlet_binclass,
    make_synthetic_binclass,
    nonconvex_binclass_loss,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")

N_CLIENTS, M_LOCAL, DIM = 20, 64, 50
BUDGETS_MBITS = (1.0, 4.0, 16.0)   # matched fleet-uplink budgets


def _gradsq(x, data):
    flat = BinClassData(a=data.a.reshape(-1, DIM), y=data.y.reshape(-1))
    return float(jnp.sum(binclass_full_grad(x, flat) ** 2))


def _loss(x, data):
    flat = BinClassData(a=data.a.reshape(-1, DIM), y=data.y.reshape(-1))
    return float(nonconvex_binclass_loss(x, flat))


def _methods(data, L, quick):
    """(name, method, r) — every entry rides the same Rand3 wire."""
    comp = RandK(k=3)
    omega = comp.omega(DIM)
    grad = jax.grad(nonconvex_binclass_loss)
    p_full = comp.default_p(DIM)
    out = [
        ("marina", Marina(grad, comp, marina_gamma(L, omega, p_full, N_CLIENTS),
                          p_full), None),
    ]
    for r in ((4,) if quick else (8, 4)):
        p = p_full * r / N_CLIENTS
        out.append((
            f"pp_marina_r{r}",
            PPMarina(grad, comp, pp_marina_gamma(L, omega, p, r), p, r=r,
                     replace=False),
            r,
        ))
    out.append(("diana", Diana(grad, comp, diana_gamma(L, omega, N_CLIENTS),
                               diana_alpha(omega), N_CLIENTS), None))
    out.append(("dcgd", DCGD(grad, comp,
                             0.3 / (L * (1 + omega / N_CLIENTS)), N_CLIENTS),
                None))
    return out


def _run_curve(method, name, data, steps, every):
    if name in ("diana", "dcgd"):
        state = method.init(jnp.zeros((DIM,)))
    else:
        state = method.init(jnp.zeros((DIM,)), data)
    step = jax.jit(method.step)
    bits = down = 0.0
    pts = [{"round": 0, "mbits_up": 0.0, "mbits_down": 0.0,
            "gradsq": _gradsq(state.params, data),
            "loss": _loss(state.params, data)}]
    t0 = time.time()
    for k in range(steps):
        state, met = step(state, jax.random.PRNGKey(k), data)
        bits += float(met.bits_per_worker) * N_CLIENTS   # fleet uplink
        down += float(met.down_bits) * N_CLIENTS
        if (k + 1) % every == 0:
            pts.append({
                "round": k + 1,
                "mbits_up": bits / 1e6,
                "mbits_down": down / 1e6,
                "gradsq": _gradsq(state.params, data),
                "loss": _loss(state.params, data),
            })
    us = (time.time() - t0) / steps * 1e6
    return pts, us


def bench_pp_curves(quick=False, emit=print):
    steps = 600 if quick else 4000
    every = 50 if quick else 100
    alphas = (0.1, float("inf")) if quick else (0.1, 1.0, float("inf"))
    curves = []
    for alpha in alphas:
        data = make_dirichlet_binclass(
            jax.random.PRNGKey(7), N_CLIENTS, M_LOCAL, DIM, alpha=alpha
        )
        L = binclass_smoothness(data)
        for name, method, r in _methods(data, L, quick):
            pts, us = _run_curve(method, name, data, steps, every)
            curves.append({
                "alpha": "inf" if np.isinf(alpha) else alpha,
                "method": name, "r": r, "steps": steps, "points": pts,
            })
            emit(f"pp_curve/alpha{curves[-1]['alpha']}/{name}", us,
                 f"final_gradsq={pts[-1]['gradsq']:.2e};"
                 f"Mbits={pts[-1]['mbits_up']:.2f}")
    return curves


def budget_table(curves):
    """‖∇f‖² reached within each matched fleet-uplink budget (best point at
    or under the budget — methods that never log under it get null)."""
    rows = []
    for alpha in sorted({c["alpha"] for c in curves}, key=str):
        row = {"alpha": alpha, "budgets": {}}
        for budget in BUDGETS_MBITS:
            cell = {}
            for c in (c for c in curves if c["alpha"] == alpha):
                under = [p["gradsq"] for p in c["points"]
                         if p["mbits_up"] <= budget]
                cell[c["method"]] = min(under) if under else None
            row["budgets"][str(budget)] = cell
        rows.append(row)
    return rows


_ROUNDTIME_PROG = textwrap.dedent(
    """
    import os, json, time
    import dataclasses
    import jax, jax.numpy as jnp
    from repro.configs import get_arch
    from repro.launch.distributed import build_train_steps, BLOCK, KB
    from repro.launch.topology import make_federated_mesh
    from repro.models import reduced, init_params
    from repro.core import wire

    REPS = %(reps)d
    mesh = make_federated_mesh(4, model=2)
    arch = get_arch("qwen1.5-0.5b")
    # large enough that the two vmapped backprops dominate the round — the
    # regime the r/n cohort-compute saving targets (a tiny model measures
    # gather overhead instead of compute)
    arch = dataclasses.replace(arch, model=reduced(arch.model, layers=2, d_model=256))
    cfg = arch.model
    n, r, b = 4, 2, 4

    def build(part):
        return build_train_steps(
            arch, mesh, multi_pod=False, global_batch=n*b, seq_len=64,
            gamma=0.1, dtype=jnp.float32, replicate_params=True,
            participation=part, p=0.1,
        )

    params = init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (n, b, 64), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    sel = jnp.array([1, 2], jnp.int32)

    def timeit(bundle, args):
        fn, _ = bundle.fns["compressed_step"]
        with bundle.mesh:
            p_, g_ = fn(*args)                      # compile + warm
            best = float("inf")
            for _ in range(REPS):
                p_ = jax.tree.map(jnp.array, params)
                g_ = jax.tree.map(jnp.zeros_like, params)
                t0 = time.perf_counter()
                p_, g_ = fn(p_, g_, *args[2:])
                jax.block_until_ready(jax.tree.leaves(g_)[0])
                best = min(best, (time.perf_counter() - t0) * 1e6)
        return best

    full = build(None)
    key = jax.random.PRNGKey(3)
    full_us = timeit(full, (jax.tree.map(jnp.array, params),
                            jax.tree.map(jnp.zeros_like, params), batch, key))
    pp = build((r, "without"))
    pp_us = timeit(pp, (jax.tree.map(jnp.array, params),
                        jax.tree.map(jnp.zeros_like, params), batch, key, sel))

    d = sum(int(jnp.size(t)) for t in jax.tree.leaves(params))
    nblk = -(-d // BLOCK)
    zeta = wire.seeded_randk_bits(nblk, KB)
    print("ROUNDTIME_JSON " + json.dumps({
        "n": n, "r": r, "d": d,
        "full_us": full_us, "pp_us": pp_us,
        "speedup": full_us / pp_us,
        "wire_bits_full": wire.pp_uplink_total_bits(n, zeta),
        "wire_bits_pp": wire.pp_uplink_total_bits(r, zeta),
        "cohort_compute": bool(pp.meta["cohort_compute"]),
    }))
    """
)


def bench_pp_roundtime(quick=False, emit=print):
    prog = _ROUNDTIME_PROG % {"reps": 3 if quick else 10}
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    # a CPU simulation on 8 fake devices by design: the child never
    # competes with this process for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    out = subprocess.run(
        [sys.executable, "-c", prog], capture_output=True, text=True,
        env=env, timeout=900,
    )
    if out.returncode != 0:
        emit("pp_roundtime/FAILED", 0.0, out.stderr.strip()[-200:])
        return None
    line = [l for l in out.stdout.splitlines()
            if l.startswith("ROUNDTIME_JSON ")][0]
    row = json.loads(line[len("ROUNDTIME_JSON "):])
    emit("pp_roundtime/mesh4x2", row["pp_us"],
         f"full_us={row['full_us']:.0f};speedup={row['speedup']:.2f}x;"
         f"wire={row['wire_bits_full']/row['wire_bits_pp']:.1f}x")
    return row


# --- Byzantine-robust adversarial grid (DESIGN.md §4.9) --------------------
#
# Calibrated so the acceptance claim is measurable on CPU in minutes: n = 20
# clients (f = 5 at the ¼ fraction), r = 16 cohorts, dense 4-bit QSGD wire
# (coordinate-wise GARs need comparable per-coordinate payloads — see the
# aggregators.py wire-compatibility note), moderate heterogeneity (the trim
# bias of asymmetric contamination under symmetric trimming scales with the
# honest spread — at heterogeneity ≫ 0.5 even a perfect GAR drifts >10% off
# the attack-free loss, which is the ROBUSTNESS-UTILITY tradeoff, not a bug).
ROB_N, ROB_R, ROB_M, ROB_D = 20, 16, 32, 20
ROB_F = 5                       # assumed Byzantine bound (= ⌊n/4⌋)
ROB_GAMMA, ROB_P = 0.1, 0.3
ROB_SCALE = 10.0                # attack amplitude
ROB_HET = 0.3

ROB_GARS = (
    ("mean", ServerAggregator("mean")),
    ("trimmed_mean", ServerAggregator("trimmed_mean", f=ROB_F)),
    ("coordinate_median", ServerAggregator("coordinate_median")),
    ("krum", ServerAggregator("krum", f=ROB_F)),
    ("norm_clip", ServerAggregator("norm_clip")),
)


def _rob_eval(x, data):
    flat = BinClassData(a=data.a.reshape(-1, ROB_D), y=data.y.reshape(-1))
    return (float(nonconvex_binclass_loss(x, flat)),
            float(jnp.sum(binclass_full_grad(x, flat) ** 2)))


def _rob_method(gar, faults):
    agg = None if gar.rule == "mean" else gar
    return PPMarina(
        jax.grad(nonconvex_binclass_loss),
        make_compressor("qsgd", s=7),
        ROB_GAMMA, ROB_P, r=ROB_R, replace=False, carry=True,
        aggregator=agg, faults=faults,
    )


def _rob_run(method, data, eval_data, steps):
    state = method.init(jnp.zeros((ROB_D,)), data)
    step = jax.jit(method.step)
    bits = 0.0
    t0 = time.time()
    for k in range(steps):
        state, met = step(state, jax.random.PRNGKey(k), data)
        bits += float(met.bits_per_worker) * ROB_N
    us = (time.time() - t0) / steps * 1e6
    loss, gradsq = _rob_eval(state.params, eval_data)
    return loss, gradsq, bits / 1e6, us


def bench_robust_grid(quick=False, emit=print):
    """attack × GAR × faulty-fraction grid → final honest-objective loss.

    Every cell runs the same optimizer/wire/step count, so the fleet bit
    budgets match by construction (the `mbits_up` column proves it — only
    `drop` books fewer bits, exactly r − #dropped uploads per round).
    `label_flip` poisons the DATA (the faulty clients follow the protocol
    honestly on flipped labels); all cells are evaluated on the clean data."""
    steps = 150 if quick else 300
    fracs = (0.125, 0.25) if not quick else (0.25,)
    attacks = (("sign_flip", "payload"), ("mean_shift", "payload"),
               ("label_flip", "data"))
    if quick:
        attacks = attacks[:2]
        gars = ROB_GARS[:3]
    else:
        gars = ROB_GARS
    data = make_synthetic_binclass(
        jax.random.PRNGKey(11), ROB_N, ROB_M, ROB_D, heterogeneity=ROB_HET
    )
    cells = []

    def run_cell(attack, frac, gar_name, gar, run_data, faults):
        loss, gradsq, mbits, us = _rob_run(
            _rob_method(gar, faults), run_data, data, steps
        )
        cells.append({
            "attack": attack, "frac": frac, "gar": gar_name,
            "f_assumed": gar.f if gar.rule in ("trimmed_mean", "krum") else None,
            "final_loss": loss, "final_gradsq": gradsq, "mbits_up": mbits,
        })
        emit(f"robust/{attack}_f{frac}/{gar_name}", us,
             f"loss={loss:.4f};gradsq={gradsq:.2e};Mbits={mbits:.2f}")

    # fault-free baselines: one per GAR (the robustness *cost* at f = 0)
    for gar_name, gar in gars:
        run_cell("none", 0.0, gar_name, gar, data, None)
    free = next(c for c in cells if c["gar"] == "mean")["final_loss"]

    for attack, kind in attacks:
        for frac in fracs:
            poisoned = (flip_binclass_labels(data, int(frac * ROB_N))
                        if kind == "data" else data)
            faults = (FaultSpec(attack, frac=frac, scale=ROB_SCALE)
                      if kind == "payload" else None)
            for gar_name, gar in gars:
                run_cell(attack, frac, gar_name, gar, poisoned, faults)

    # dropped clients: a transport fault, not an adversary — the server
    # substitutes the carry row (Δ̂_i = 0) and books only actual uploads
    run_cell("drop", 0.25, "mean", ServerAggregator("mean"), data,
             FaultSpec("drop", frac=0.25))

    for c in cells:
        c["loss_vs_free"] = c["final_loss"] / free
    return {"n": ROB_N, "r": ROB_R, "m_local": ROB_M, "d": ROB_D,
            "compressor": "qsgd_s7", "gamma": ROB_GAMMA, "p": ROB_P,
            "heterogeneity": ROB_HET, "scale": ROB_SCALE, "steps": steps,
            "free_loss": free, "cells": cells}


def bench_robust_roundtime(quick=False, emit=print):
    """Fused robust rounds vs the fused mean on the reduced-qwen flat layout
    (nblk ≈ 1699 f32 blocks, n = 8 worker rows, dense QSGD uplink).

    `round_*` times the full `FlatEngine.fused_round` (quantize → decode →
    GAR → g/x epilogue) — the unit a compressed round actually pays, and the
    CI gate metric (scripts/check_robust.py: robust/mean ≤ 1.25). The
    isolated sync-epilogue ratio is recorded too but NOT gated on CPU: the
    mean epilogue is one memory-bound pass while the trimmed ref is a
    compute-bound O(n²/2) compare-exchange network — on TPU the Pallas
    kernel's extra compares ride in-register on the same HBM traffic as the
    mean, which is where the ~1.2× epilogue claim lives."""
    from repro.core import flat
    from repro.kernels import epilogue as epi

    n = 8
    nblk = 425 if quick else 1699   # quick: ~0.44M params, full: reduced qwen
    bufs = jax.random.normal(jax.random.PRNGKey(0), (n, nblk, 1024))
    x2d = jax.random.normal(jax.random.PRNGKey(1), (nblk, 1024))
    g2d = jnp.zeros((nblk, 1024))
    gamma = 0.1
    trim = ServerAggregator("trimmed_mean", f=2)
    med = ServerAggregator("coordinate_median")
    lo_t, hi_t = trim.trim_bounds(n)
    lo_m, hi_m = med.trim_bounds(n)

    params = {"w": jnp.zeros((nblk * 1024,), jnp.float32)}
    eng = flat.FlatEngine(layout=flat.make_layout(params), sampler="qsgd", s=7)
    kr = jax.random.PRNGKey(2)

    # arrays cross as jit ARGUMENTS (closed-over arrays are compile-time
    # constants XLA is free to fold — a nullary jit would time nothing)
    fns = {
        "round_mean": jax.jit(
            lambda k, b, g, x: eng.fused_round(k, b, n, g, x, gamma)),
        "round_trimmed": jax.jit(
            lambda k, b, g, x: eng.fused_round(k, b, n, g, x, gamma,
                                               aggregator=trim)),
        "round_median": jax.jit(
            lambda k, b, g, x: eng.fused_round(k, b, n, g, x, gamma,
                                               aggregator=med)),
        "sync_mean": jax.jit(
            lambda k, b, g, x: epi.mean_epilogue(b, x, gamma)),
        "sync_trimmed": jax.jit(
            lambda k, b, g, x: epi.trimmed_sync_epilogue(
                b, x, gamma, lo_t, hi_t)),
        "sync_median": jax.jit(
            lambda k, b, g, x: epi.trimmed_sync_epilogue(
                b, x, gamma, lo_m, hi_m)),
    }
    args_ = (kr, bufs, g2d, x2d)
    # interleaved min-of-trials (the bench_compression discipline): every
    # candidate measured in each trial window so load noise hits all alike
    for fn in fns.values():
        jax.block_until_ready(fn(*args_))
    rounds = 5 if quick else 12
    best = {name: float("inf") for name in fns}
    for _ in range(rounds):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args_))
            best[name] = min(best[name], (time.perf_counter() - t0) * 1e6)

    row = {
        "n": n, "d": nblk * 1024,
        "backend": "ref(cpu)" if jax.default_backend() != "tpu" else "pallas",
        **{k: v for k, v in best.items()},
        "round_trimmed_over_mean": best["round_trimmed"] / best["round_mean"],
        "round_median_over_mean": best["round_median"] / best["round_mean"],
        "sync_trimmed_over_mean": best["sync_trimmed"] / best["sync_mean"],
        "sync_median_over_mean": best["sync_median"] / best["sync_mean"],
    }
    emit("robust/roundtime", best["round_trimmed"],
         f"mean_us={best['round_mean']:.0f};"
         f"trimmed={row['round_trimmed_over_mean']:.2f}x;"
         f"median={row['round_median_over_mean']:.2f}x")
    return row


# --- Straggler / deadline wall-clock harness (DESIGN.md §4.10) -------------
#
# The paper's curves are loss-vs-bits; a federated fleet also pays WALL
# CLOCK, and a synchronous round costs the slowest client. The harness runs
# DeadlineMarina on the same Dirichlet non-IID problem and Rand3 wire as the
# pp curves, under three straggler distributions, and reports simulated
# wall clock to a MATCHED loss: synchronous full participation (a deadline
# no draw reaches — bit-identical trajectory to Marina carry, wall = max
# client time per round) vs deadline cohorts at honest-quantile deadlines,
# with and without stale-difference acceptance.

#: deadline no compute-time draw ever reaches: every client makes every
#: round, so the trajectory IS synchronous MARINA and the wall clock pays
#: max_i T_i — the baseline the deadline variants race.
NEVER_MISS_S = 1e9

ASYNC_TIMES = {
    # multiplicative heterogeneity with a heavy right tail (σ = 1: the p99
    # honest client is ~6× the median)
    "lognormal": RoundTimeModel(dist="lognormal", mean_s=1.0, sigma=1.0),
    # memoryless service times
    "exponential": RoundTimeModel(dist="exponential", mean_s=1.0),
    # two persistently slow clients at 8×: the static-drop regime — a
    # deadline permanently excludes the same cohort every round
    "fixed_slow": RoundTimeModel(
        dist="fixed", mean_s=1.0, slow_ids=(3, 11), slow_factor=8.0
    ),
}


def _expected_arrive_frac(tm: RoundTimeModel, deadline: float) -> float:
    """Expected per-round arrival fraction under a deadline: honest clients
    beat it w.p. 1 − miss_prob; the persistently slow set (slow_factor ≥
    deadline/mean for every model here) is counted fully missing."""
    slow = len(tm.slow_ids) / N_CLIENTS
    return (1.0 - tm.miss_prob(deadline)) * (1.0 - slow)


def _run_async_curve(method, data, steps, every):
    state = method.init(jnp.zeros((DIM,)), data)
    step = jax.jit(method.step)
    bits = wall = up = 0.0
    pts = [{"round": 0, "wall_s": 0.0, "mbits_up": 0.0,
            "loss": _loss(state.params, data),
            "gradsq": _gradsq(state.params, data)}]
    t0 = time.time()
    for k in range(steps):
        state, met = step(state, jax.random.PRNGKey(k), data)
        bits += float(met.bits_per_worker) * N_CLIENTS   # fleet uplink
        wall += float(met.wall_clock_s)
        up += float(met.uploaded)
        if (k + 1) % every == 0:
            pts.append({
                "round": k + 1,
                "wall_s": wall,
                "mbits_up": bits / 1e6,
                "loss": _loss(state.params, data),
                "gradsq": _gradsq(state.params, data),
            })
    us = (time.time() - t0) / steps * 1e6
    return pts, up / (steps * N_CLIENTS), us


def bench_async_curves(quick=False, emit=print):
    """Loss-vs-wall-clock curves per straggler distribution: synchronous
    MARINA vs deadline cohorts (tau_max = 0) vs deadline + stale acceptance
    (tau_max = 2), every variant at its heuristic stepsize
    (:func:`async_marina_gamma` on the expected arrival fraction)."""
    steps = 400 if quick else 2000
    every = 25 if quick else 50
    data = make_dirichlet_binclass(
        jax.random.PRNGKey(7), N_CLIENTS, M_LOCAL, DIM, alpha=0.1
    )
    L = binclass_smoothness(data)
    comp = RandK(k=3)
    omega = comp.omega(DIM)
    p = comp.default_p(DIM)
    grad = jax.grad(nonconvex_binclass_loss)
    names = ("lognormal", "fixed_slow") if quick else tuple(ASYNC_TIMES)
    quants = (0.8,) if quick else (0.6, 0.8)
    curves = []
    for dist_name in names:
        tm = ASYNC_TIMES[dist_name]
        variants = [(
            "sync", None, 0,
            DeadlineMarina(
                grad, comp, marina_gamma(L, omega, p, N_CLIENTS), p,
                deadline=NEVER_MISS_S, times=tm,
            ),
        )]
        for q in quants:
            dl = tm.deadline_for_quantile(q)
            arrive = _expected_arrive_frac(tm, dl)
            variants.append((
                f"deadline_q{q:g}", q, 0,
                DeadlineMarina(
                    grad, comp,
                    async_marina_gamma(
                        L, omega, p, N_CLIENTS, arrive_frac=arrive
                    ),
                    p, deadline=dl, times=tm,
                ),
            ))
        # stale acceptance at the tightest deadline: late uploads land
        # within 2 rounds instead of vanishing; γ additionally degrades
        # with the anchor-age heuristic
        q = quants[0]
        dl = tm.deadline_for_quantile(q)
        arrive = _expected_arrive_frac(tm, dl)
        variants.append((
            f"deadline_q{q:g}_tau2", q, 2,
            DeadlineMarina(
                grad, comp,
                async_marina_gamma(
                    L, omega, p, N_CLIENTS, arrive_frac=arrive, staleness=1.0
                ),
                p, deadline=dl, times=tm, tau_max=2,
            ),
        ))
        for vname, q, tau, method in variants:
            pts, arrived, us = _run_async_curve(method, data, steps, every)
            curves.append({
                "dist": dist_name, "variant": vname, "quantile": q,
                "tau_max": tau, "deadline_s": float(method.deadline),
                "gamma": float(method.gamma), "steps": steps,
                "arrived_frac": arrived, "points": pts,
            })
            emit(f"async/{dist_name}/{vname}", us,
                 f"final_loss={pts[-1]['loss']:.4f};"
                 f"wall_s={pts[-1]['wall_s']:.1f};arrived={arrived:.2f}")
    return curves


def async_wall_table(curves):
    """Simulated wall clock to a MATCHED loss, per distribution: the target
    is the worst final loss among that distribution's variants (so every
    variant reaches it), wall_s the first logged point at/below it, and
    speedup_vs_sync the headline — how much sooner the deadline round
    delivers the same loss than waiting for the slowest client."""
    rows = []
    for dist in sorted({c["dist"] for c in curves}):
        group = [c for c in curves if c["dist"] == dist]
        target = max(c["points"][-1]["loss"] for c in group)
        row = {"dist": dist, "target_loss": target,
               "wall_s": {}, "rounds": {}}
        for c in group:
            hit = next(
                (pt for pt in c["points"] if pt["loss"] <= target), None
            )
            row["wall_s"][c["variant"]] = hit["wall_s"] if hit else None
            row["rounds"][c["variant"]] = hit["round"] if hit else None
        sync_wall = row["wall_s"].get("sync")
        row["speedup_vs_sync"] = {
            v: (sync_wall / w if sync_wall and w else None)
            for v, w in row["wall_s"].items()
        }
        rows.append(row)
    return rows


def _write_merged(update):
    """Read-merge-update BENCH_pp.json so `--only robust` doesn't clobber
    the pp curves (and vice versa). The write is ATOMIC: the merged JSON is
    serialized to a temp file in the same directory and os.replace'd over
    the target, so a run killed mid-write (a CI timeout on `--quick`) can
    only ever leave a stray temp file — never a truncated/corrupt
    BENCH_pp.json that would take the other sections' results with it."""
    path = os.path.join(ROOT, "BENCH_pp.json")
    out = {}
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)
    out.update(update)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=2)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    print(f"# wrote {os.path.normpath(path)}", file=sys.stderr)
    return out


def bench_pp(quick=False, emit=None):
    """Entry point shared with benchmarks.run (--only pp)."""
    if emit is None:
        def emit(name, us, derived):
            print(f"{name},{us:.2f},{derived}", flush=True)
    curves = bench_pp_curves(quick=quick, emit=emit)
    roundtime = bench_pp_roundtime(quick=quick, emit=emit)
    return _write_merged({
        "quick": bool(quick),
        "problem": {"n_clients": N_CLIENTS, "m_local": M_LOCAL, "d": DIM,
                    "compressor": "rand3", "scheme": "without"},
        "budgets_mbits": list(BUDGETS_MBITS),
        "curves": curves,
        "budget_table": budget_table(curves),
        "roundtime": roundtime,
    })


def bench_robust(quick=False, emit=None):
    """Entry point shared with benchmarks.run (--only robust)."""
    if emit is None:
        def emit(name, us, derived):
            print(f"{name},{us:.2f},{derived}", flush=True)
    grid = bench_robust_grid(quick=quick, emit=emit)
    roundtime = bench_robust_roundtime(quick=quick, emit=emit)
    return _write_merged({
        "robust": {"quick": bool(quick), **grid, "roundtime": roundtime},
    })


def bench_async(quick=False, emit=None):
    """Entry point shared with benchmarks.run (--only async)."""
    if emit is None:
        def emit(name, us, derived):
            print(f"{name},{us:.2f},{derived}", flush=True)
    curves = bench_async_curves(quick=quick, emit=emit)
    return _write_merged({
        "async": {
            "quick": bool(quick),
            "problem": {"n_clients": N_CLIENTS, "m_local": M_LOCAL,
                        "d": DIM, "compressor": "rand3", "alpha": 0.1},
            "curves": curves,
            "wall_table": async_wall_table(curves),
        },
    })


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only", default="all", choices=("pp", "robust", "async", "all")
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.only in ("pp", "all"):
        bench_pp(quick=args.quick)
    if args.only in ("robust", "all"):
        bench_robust(quick=args.quick)
    if args.only in ("async", "all"):
        bench_async(quick=args.quick)


if __name__ == "__main__":
    main()
