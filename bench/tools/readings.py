"""The readings that the limits of ``bench/limits/<workload>.json`` are set
from, on the chip at the cell's own size:

    python3 bench/tools/readings.py --workload <name> --seeds 1,2,3

For each seed, against the reference: the program's first rounds (the lower
reading); the control, the reference computed in bfloat16 in the program's
place; and the reference with half of the batch left out (upper readings).
One JSON line per seed and kind.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import importlib

    import jax
    import jax.numpy as jnp

    from bench import compare
    from bench.run import load_spec, set_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("readings: JAX finds no TPU", file=sys.stderr)
        return 3
    set_compile_cache(ROOT)
    spec = load_spec(ROOT, args.workload)
    entry = importlib.import_module(f"bench.entries.{spec['traffic']['entry']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        s = entry.Session(spec["config"], spec["traffic"], seed)
        s.build()
        prog = s.first_rounds()
        s.release()
        want = s.reference()
        kinds = {"program": prog, "control": s.reference(dtype=jnp.bfloat16),
                 "half_batch": s.reference(fault="half_batch")}
        for kind, got in kinds.items():
            print(json.dumps({"workload": args.workload, "seed": seed, "kind": kind,
                              **compare.numbers(got, want)}), flush=True)
        del s
    return 0


if __name__ == "__main__":
    sys.exit(main())
