"""The readings that a mesh cell's limits (``bench/limits/<workload>.json``)
are set from, on the chip at the cell's own size:

    python3 bench/tools/mesh_readings.py --workload <name> --seeds 1,2,3

The lines are those of ``bench/tools/readings.py``: for each seed, against
the reference, the program's first rounds (the lower reading), the control
(the reference in bfloat16 in the program's place) and the reference with
half of the batch left out (upper readings). The order differs, so that a
four-chip state is built once: the program reads every seed on one compiled
round, then its programs are freed and the references follow, each of their
programs compiled once for all seeds. While the program reads, host threads
compile the references' programs (float32 and the bfloat16 control), which
their first calls then load from the persistent cache.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(spec: dict, seeds: list, out=sys.stdout) -> None:
    """Print one JSON line per seed and kind for the cell ``spec``."""
    import importlib

    import jax.numpy as jnp

    from bench import compare

    entry = importlib.import_module(f"bench.entries.{spec['traffic']['entry']}")
    s = entry.Session(spec["config"], spec["traffic"], seeds[0])
    ahead = [threading.Thread(target=s.marina(dtype).compile)
             for dtype in (jnp.float32, jnp.bfloat16)]
    for t in ahead:
        t.start()
    s.build()
    prog = {}
    for seed in seeds:
        if seed != seeds[0]:
            s.restart(seed)
        prog[seed] = s.first_rounds()
    s.release()
    for t in ahead:
        t.join()
    workload = spec["cell"]["name"]
    for seed in seeds:
        s.seed = seed % 2**32
        want = s.reference()
        kinds = {"program": prog[seed], "control": s.reference(dtype=jnp.bfloat16),
                 "half_batch": s.reference(fault="half_batch")}
        for kind, got in kinds.items():
            print(json.dumps({"workload": workload, "seed": seed, "kind": kind,
                              **compare.numbers(got, want)}), file=out, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench.run import load_spec, set_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("mesh_readings: JAX finds no TPU", file=sys.stderr)
        return 3
    set_compile_cache(ROOT)
    readings(load_spec(ROOT, args.workload), [int(x) for x in args.seeds.split(",")])
    return 0


if __name__ == "__main__":
    sys.exit(main())
