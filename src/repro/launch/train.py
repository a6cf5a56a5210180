"""Production training driver.

Selects an assigned architecture (``--arch``) and runs either:

* ``--backend sim`` (the default) — the simulation backend: every worker on
  one device, a MARINA-family ``--method`` with any ``--compressor``
  (``repro.train.Trainer``), or
* ``--backend mesh`` — MARINA's sharded round, one worker per device on a
  ``(devices, 1)`` mesh: gradient carry, the per-leaf RandK wire (L/128
  coordinates a row of each leaf) and p = 1/128 unless ``--p`` is given
  (``repro.train.mesh.MeshTrainer``). ``--method``, ``--compressor``,
  ``--k``, ``--workers`` and ``--mb`` are the simulation's; the mesh's
  workers are the devices.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --steps 20 \
      --method vr_marina --compressor randk --k 0.02 --reduced
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --steps 20 \
      --backend mesh --batch 1 --gamma 0.05
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import PUBLIC_TO_MODULE, get_arch
from repro.models import init_params, param_count, reduced as reduce_cfg
from repro.train import TrainConfig, Trainer

#: tokens a row of the mesh round
MESH_SEQ_LEN = 256


def run_mesh(arch, cfg, args) -> None:
    """``args.steps`` rounds of the sharded MARINA round, with the loss and
    the uplink ledger at every log point."""
    from repro.train.mesh import MeshTrainer

    mt = MeshTrainer(dataclasses.replace(arch, model=cfg),
                     batch_per_worker=args.batch, seq_len=MESH_SEQ_LEN,
                     gamma=args.gamma, p=args.p)
    print(f"mesh: {mt.n} workers, one a device; p={mt.p}")
    state = mt.init(lambda: init_params(jax.random.PRNGKey(0), cfg))
    sync = mt.sync_rounds(range(args.steps))
    bits = mt.uplink_bits()
    log_every = max(1, args.steps // 10)
    booked = 0.0
    print(f"\n{'step':>6} {'loss':>9} {'Mbits/worker':>13} {'round':>10}")
    for k in range(args.steps):
        state = mt.step(state, k)
        kind = "sync" if sync[k] else "compressed"
        booked += bits[kind]
        if (k + 1) % log_every == 0 or k == args.steps - 1:
            print(f"{k:>6} {mt.loss(state, k):>9.4f} {booked / 1e6:>13.2f} {kind:>10}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(PUBLIC_TO_MODULE))
    ap.add_argument("--backend", choices=("sim", "mesh"), default="sim",
                    help="sim: every worker on one device; mesh: one worker "
                         "a device, the sharded round")
    ap.add_argument("--method", default="vr_marina")
    ap.add_argument("--compressor", default="randk")
    ap.add_argument("--k", type=float, default=0.02)
    ap.add_argument("--gamma", type=float, default=0.2)
    ap.add_argument("--p", type=float, default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--mb", type=int, default=2)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced variant (CPU-feasible)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch)
    cfg = (
        reduce_cfg(arch.model, layers=args.layers, d_model=args.d_model)
        if args.reduced
        else arch.model
    )
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    print(f"arch={args.arch} ({'reduced' if args.reduced else 'FULL'}) "
          f"params={param_count(shapes):,} backend={args.backend}")
    if args.backend == "mesh":
        run_mesh(arch, cfg, args)
        return
    print(f"method={args.method}")
    params = init_params(jax.random.PRNGKey(0), cfg)

    comp_kwargs = {"k": args.k} if args.compressor in ("randk", "shared_randk", "topk") else {}
    tcfg = TrainConfig(
        method=args.method,
        compressor=args.compressor,
        comp_kwargs=comp_kwargs,
        gamma=args.gamma,
        p=args.p,
        n_workers=args.workers,
        batch_per_worker=args.batch,
        mb_per_worker=args.mb,
        steps=args.steps,
        log_every=max(1, args.steps // 10),
        ckpt_dir=args.ckpt_dir,
        ckpt_every=max(1, args.steps // 3) if args.ckpt_dir else 0,
    )
    trainer = Trainer(cfg, tcfg, params, prefix_len=8 if arch.prefix_len else 0)
    _, hist = trainer.run()
    print(f"\n{'step':>6} {'loss':>9} {'Mbits/worker':>13} {'oracle':>9}")
    for s, l, b, o in zip(hist.step, hist.loss, hist.bits_cum, hist.oracle_cum):
        print(f"{s:>6} {l:>9.4f} {b/1e6:>13.2f} {o:>9.0f}")


if __name__ == "__main__":
    main()
