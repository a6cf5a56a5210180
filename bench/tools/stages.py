"""Print every stage's device time a round for one cell, from a short traced
window on the chip:

    python3 bench/tools/stages.py --workload <name> --calls 8 [--out <dir>]

Stages are the program's own (``repro.tracing.STAGES``); each line splits a
stage into its Pallas kernels and the rest, and the operations in no stage
come last, with the heaviest of them. With ``--out`` it also writes the
window's trace ``<workload>.named.xplane.pb``, the chunk's stage map
``<workload>.named.stages.json``, its compiled text
``<workload>.named.hlo.txt.gz`` (where an operation left in no stage comes
from) and this summary as JSON; the stage readers' test fixture is
recorded this way.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summary(reduced, stages: dict, rounds: int, top: int = 8) -> dict:
    """ms a round by stage (kernels, rest), busy ms a round, and the
    heaviest operations left in no stage."""
    from bench.stages import UNSCOPED, by_stage

    per = max(1, reduced.devices) * rounds * 1e6
    split = by_stage(reduced, stages)
    rows = {}
    for (st, kernel), ns in split.items():
        row = rows.setdefault(st, {"kernels_ms": 0.0, "rest_ms": 0.0})
        row["kernels_ms" if kernel else "rest_ms"] += ns / per
    for row in rows.values():
        row["ms"] = row["kernels_ms"] + row["rest_ms"]
    loose = sorted(((n, ns / per) for n, ns in reduced.op_ns.items()
                    if n not in stages and n not in reduced.kernel_ns),
                   key=lambda kv: -kv[1])[:top]
    return {"rounds": rounds, "busy_ms": reduced.busy_ns / rounds / 1e6,
            "stages": dict(sorted(rows.items(), key=lambda kv: -kv[1]["ms"])),
            "unscoped_top": loose, "unscoped_key": UNSCOPED}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, default=8)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import importlib

    import jax
    import numpy as np

    from bench import trace
    from bench.run import load_spec, set_compile_cache
    from bench.stages import stage_map

    set_compile_cache(ROOT)
    spec = load_spec(ROOT, args.workload)
    entry = importlib.import_module(f"bench.entries.{spec['traffic']['entry']}")
    s = entry.Session(spec["config"], spec["traffic"], args.seed)
    s.build()
    s.first_rounds()
    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    with jax.profiler.trace(tdir):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(args.calls):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    met = s.dispatch()
            with jax.profiler.TraceAnnotation("bench.wait"):
                jax.block_until_ready(met)
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
    reduced = trace.load(path)
    rounds = args.calls * s.rounds_per_call
    ctx = types.SimpleNamespace(session=s, reduced=reduced, rounds=rounds)
    stages = stage_map(ctx) or {}
    out = {"workload": args.workload, "device": jax.devices()[0].device_kind,
           **summary(reduced, stages, rounds)}
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        base = os.path.join(args.out, f"{args.workload}.named")
        shutil.copy(path, base + ".xplane.pb")
        with open(base + ".stages.json", "w") as f:
            json.dump(dict(sorted(stages.items())), f, indent=0)
        with open(base + ".summary.json", "w") as f:
            json.dump(out, f, indent=1)
        steps = np.arange(s.rounds_per_call, dtype=np.int32)
        with gzip.open(base + ".hlo.txt.gz", "wt") as f:
            f.write(s.tr._jitted_chunk.lower(s.carry, steps).compile().as_text())
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"{args.workload}: {rounds} rounds, busy {out['busy_ms']:.3f} ms a round")
    for st, row in out["stages"].items():
        print(f"  {st:16s} {row['ms']:10.3f} ms  (kernels {row['kernels_ms']:.3f}, "
              f"rest {row['rest_ms']:.3f})")
    for name, ms in out["unscoped_top"]:
        print(f"  unscoped {name}: {ms:.3f} ms")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
