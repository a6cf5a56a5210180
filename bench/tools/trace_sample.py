"""Record a short traced window of a cell and print what the trace holds:

    python3 bench/tools/trace_sample.py --workload <name> --calls 4 --out <dir>

Writes ``<dir>/<workload>.xplane.pb`` and a summary of its planes, lines and
heaviest events; the reduction's test fixture is recorded this way.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def summary(pd) -> list:
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r} events={len(evs)}")
            tot, cnt = collections.Counter(), collections.Counter()
            for e in evs:
                tot[e.name] += e.duration_ns
                cnt[e.name] += 1
            for name, ns in tot.most_common(30):
                out.append(f"    {ns / 1e6:12.3f} ms x{cnt[name]:5d} {name[:160]}")
            for e in evs[:3]:
                stats = {k: str(v)[:200] for k, v in dict(e.stats).items()}
                out.append(f"    e.g. {e.name[:100]!r} {stats}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import importlib

    import jax
    from jax.profiler import ProfileData

    from bench import trace
    from bench.run import load_spec, set_compile_cache

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
    os.makedirs(args.out, exist_ok=True)
    dest = os.path.join(args.out, f"{args.workload}.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(tdir, ignore_errors=True)
    lines = summary(ProfileData.from_file(dest))
    with open(os.path.join(args.out, f"{args.workload}.summary.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[:300]))
    r = trace.load(dest)
    print("reduced:", {k: v for k, v in vars(r).items() if k not in ("op_ns", "op_count")})
    return 0


if __name__ == "__main__":
    sys.exit(main())
