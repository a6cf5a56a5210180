"""Production training driver.

Selects an assigned architecture (``--arch``), a MARINA-family method and a
compressor, and runs either:

* ``--backend sim``  — the CPU simulation backend (reduced model; the default
  here since this container has one device), or
* ``--backend mesh`` — the sharded GSPMD step on the production mesh
  (requires real devices, or --dry-compile to stop after compilation).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --steps 20 \
      --method vr_marina --compressor randk --k 0.02 --reduced
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import PUBLIC_TO_MODULE, get_arch
from repro.models import init_params, param_count, reduced as reduce_cfg
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(PUBLIC_TO_MODULE))
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
    params = init_params(jax.random.PRNGKey(0), cfg)
    print(f"arch={args.arch} ({'reduced' if args.reduced else 'FULL'}) "
          f"params={param_count(params):,} method={args.method}")

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
