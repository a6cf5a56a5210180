"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file, the traffic file ``bench/traffic/<traffic>.json`` (whose
``entry`` names the module under ``bench/entries/`` that drives the program),
the limits ``bench/limits/<workload>.json`` and, in a traced run, one reader
``bench/metrics/<metric>.py`` per per-layer metric. Adding a cell, a mix or a
metric adds files and entries; no file here changes.

Set-up (counted in ``setup_s``, from process start to the first timed
dispatch) builds the program and drives its first calls, which compile the
window's program. The window then dispatches calls back to back for
``--seconds`` and ends at ``block_until_ready`` of the last one. After it the
peak device memory is read, the program's state is freed, and the plain
reference follows the first calls; the comparison decides ``correct``.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import collections  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]


#: calls dispatched ahead of the device in the window
IN_FLIGHT = 2


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def load_spec(root: Path, workload: str) -> dict:
    """The cell named ``workload`` with its configuration, traffic and limits."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    limits = json.loads(
        (root / "bench" / "limits" / f"{workload}.json").read_text())["limits"]
    return {"bench": bench, "cell": cell, "config": config,
            "traffic": traffic, "limits": limits}


def cell_metrics(bench: dict, workload: str, trace: bool) -> list:
    """The cell's end-to-end metrics, or in a traced run its per-layer ones."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload] if m["moves"] in moved else [])]


def peaks_for(root: Path, kind: str) -> dict:
    """The device's peaks; a device not in the table is an error."""
    table = json.loads((root / "bench" / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


class CompileCounter:
    """Counts compilations (and persistent-cache loads) while it is on."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_hits")

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, _secs, **_kw):
        if self.on and event in self.EVENTS:
            self.count += 1

    def _ev(self, event, **_kw):
        if self.on and event in self.EVENTS:
            self.count += 1


def set_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else
    ``<checkout>/.jax_cache``; every program is kept, however quick."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def window(session, seconds: float, annotate: bool) -> tuple[int, float, float]:
    """Dispatch calls back to back for ``seconds``, at most ``IN_FLIGHT``
    ahead of the device; returns (calls, seconds until the last is ready,
    the longest the host spent between two waits). A host stretch longer
    than ``IN_FLIGHT`` calls of the device leaves the device idle."""
    import jax

    if annotate:
        span = jax.profiler.TraceAnnotation
    else:
        import contextlib

        def span(_name):
            return contextlib.nullcontext()

    pending = collections.deque()
    calls, host_max = 0, 0.0
    t0 = waited = time.perf_counter()
    while True:
        with span("bench.dispatch"):
            pending.append(session.dispatch())
        calls += 1
        if len(pending) > IN_FLIGHT:
            host_max = max(host_max, time.perf_counter() - waited)
            with span("bench.wait"):
                jax.block_until_ready(pending.popleft())
            waited = time.perf_counter()
        if time.perf_counter() - t0 >= seconds:
            break
    host_max = max(host_max, time.perf_counter() - waited)
    with span("bench.wait"):
        jax.block_until_ready(list(pending))
    return calls, time.perf_counter() - t0, host_max


def peak_bytes(stats: dict) -> int:
    """A chip's peak: its buffers' peak plus the region the TPU runtime
    reserves for programs' temporaries, which ``peak_bytes_in_use`` leaves
    out (the region grows to the largest temporary a program has needed)."""
    return int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0))


def device_line(devs, chips: int) -> dict:
    used = devs[:chips]
    peak = max(peak_bytes(d.memory_stats() or {}) for d in used)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        root: Path = ROOT, require_tpu: bool = True, session_cls=None,
        spec: dict | None = None, log=sys.stderr) -> dict:
    """One run of one cell; returns the result line as a dict. Tests pass
    ``spec`` (a cell of their own) and ``session_cls`` (a broken path)."""
    from bench import compare, trace as trace_mod

    spec = spec or load_spec(root, workload)
    cell, traffic = spec["cell"], spec["traffic"]
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < cell["chips"]:
        raise NoChip(f"the cell asks for {cell['chips']} chips, JAX sees {len(devs)}")
    peaks = peaks_for(root, devs[0].device_kind) if require_tpu else None
    set_compile_cache(root)
    counter = CompileCounter()

    entry = importlib.import_module(f"bench.entries.{traffic['entry']}")
    session = (session_cls or entry.Session)(spec["config"], traffic, seed)
    session.build()
    readings = session.first_rounds()
    setup_s = time.time() - T_START

    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    counter.on = True
    if trace:
        with jax.profiler.trace(tdir):
            with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
                calls, window_s, host_max = window(session, seconds, True)
    else:
        calls, window_s, host_max = window(session, seconds, False)
    counter.on = False
    device = device_line(devs, cell["chips"])
    attempted = calls * session.rounds_per_call
    failed = int(session.skipped())
    print(f"window: {calls} calls, {attempted} rounds in {window_s:.6f} s; "
          f"compilations inside the window: {counter.count}; longest host "
          f"stretch between waits: {host_max:.6f} s", file=log)

    metrics, breakdown = {}, None
    wanted = cell_metrics(spec["bench"], workload, trace)
    if trace:
        path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)[0]
        reduced = trace_mod.load(path)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = reduced.busy_ns / 1e9
        device["window_s"] = reduced.window_ns / 1e9
        breakdown = trace_mod.breakdown(reduced)
        # what a per-layer reader may read: the reduced trace, the window's
        # counts, the chip's peaks, and the session for the program's counters
        ctx = types.SimpleNamespace(
            session=session, reduced=reduced, calls=calls, rounds=attempted,
            window_s=window_s, chips=cell["chips"], peaks=peaks)
        for m in wanted:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {
            "tokens_per_s": calls * session.tokens_per_call / window_s,
            "peak_hbm_gib": device["memory_peak_bytes"] / 2**30,
            "setup_s": setup_s,
        }
        for m in wanted:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    session.release()
    t_ref = time.perf_counter()
    want = session.reference()
    print(f"reference: {time.perf_counter() - t_ref:.3f} s", file=log)
    values = compare.numbers(readings, want)
    correct, checks = compare.judge(values, spec["limits"])
    for name, (v, lim) in checks.items():
        print(f"check {name}: {v!r} (limit {lim!r})", file=log)
    line = {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
