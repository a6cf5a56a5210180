"""Production serving driver: continuous batching over the paged KV cache
(DESIGN.md §8), with the legacy static-batch path kept for A/B comparison.

Continuous mode threads one donated page-pool cache through a single jitted
decode step per iteration, joining prefill chunks into the running batch as
slots and pages free up. Static mode is the old serve loop: pad every
request to the longest prompt, prefill once, decode until the longest
generation finishes. BENCH_serve (benchmarks/bench_serve.py) runs both over
the same mixed-length workload and reports the tokens/s ratio.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b \
      --requests 32:24,32:4,8:4,8:4 --slots 4 --mode continuous
  PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma-2b \
      --mode static --batch 4 --prompt 32 --gen 16
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import PUBLIC_TO_MODULE, get_arch
from repro.core.paging import PagedLayout
from repro.launch.scheduler import ContinuousEngine, ContinuousScheduler, Request
from repro.models import (
    decode_step,
    init_paged_cache,
    init_params,
    paged_copy_pages,
    paged_decode_step,
    paged_gather_pages,
    paged_prefill_chunk,
    paged_scatter_pages,
    prefill,
    reduced as reduce_cfg,
)


def parse_requests(spec: str) -> list[tuple[int, int]]:
    """``"32:24,8:4"`` → [(prompt_len, gen_len), ...]."""
    out = []
    for part in spec.split(","):
        p, g = part.split(":")
        out.append((int(p), int(g)))
    return out


def make_workload(cfg, pairs, seed: int = 1) -> list[Request]:
    key = jax.random.PRNGKey(seed)
    reqs = []
    for rid, (p, g) in enumerate(pairs):
        key, sub = jax.random.split(key)
        prompt = np.asarray(
            jax.random.randint(sub, (p,), 0, cfg.vocab_size), np.int32
        )
        reqs.append(Request(rid=rid, prompt=prompt, max_new=g))
    return reqs


def build_paged_steps(
    params, cfg, *, temperature: float = 0.0, seed: int = 0,
) -> dict:
    """Jitted paged step + COW/swap page-op closures, reusable across
    engines. Build these ONCE and pass them to every :func:`build_engine` /
    :func:`run_continuous` call that shares the params — a fresh closure
    per run re-pays ~0.7 s of XLA compilation, which poisons benchmark
    ratios. One set serves both f32 and quantized caches (jit re-traces
    per cache pytree structure).

    Sampling is fused into the jitted step; the PRNG key is threaded (and
    split) only when ``temperature > 0`` — greedy decoding never touches
    the key. The page ops run over fixed-width null-padded id vectors, so
    each compiles exactly once per cache structure.
    """
    state = {"key": jax.random.PRNGKey(seed)}

    def sample(logits, key):
        if temperature > 0:
            return jax.random.categorical(key, logits / temperature, axis=-1)
        return jnp.argmax(logits, axis=-1)

    @jax.jit
    def _prefill(cache, toks, start, row, nv, key=None):
        logits, cache = paged_prefill_chunk(params, cfg, cache, toks, start, row, nv)
        return sample(logits, key).astype(jnp.int32), cache

    @jax.jit
    def _decode(cache, toks, lengths, tables, key=None):
        logits, cache = paged_decode_step(params, cfg, cache, toks, lengths, tables)
        return sample(logits, key).astype(jnp.int32), cache

    def next_key():
        state["key"], sub = jax.random.split(state["key"])
        return sub

    def prefill_fn(cache, toks, start, row, nv):
        if temperature > 0:
            return _prefill(cache, toks, start, row, nv, next_key())
        return _prefill(cache, toks, start, row, nv)

    def decode_fn(cache, toks, lengths, tables):
        if temperature > 0:
            return _decode(cache, toks, lengths, tables, next_key())
        return _decode(cache, toks, lengths, tables)

    _copy = jax.jit(paged_copy_pages, donate_argnums=(0,))
    _gather = jax.jit(paged_gather_pages)
    _scatter = jax.jit(paged_scatter_pages, donate_argnums=(0,))

    return {
        "prefill": prefill_fn,
        "decode": decode_fn,
        "copy": lambda c, s, d: _copy(c, jnp.asarray(s), jnp.asarray(d)),
        # snapshots live host-side while the request is swapped out
        "gather": lambda c, i: jax.tree.map(
            np.asarray, _gather(c, jnp.asarray(i))
        ),
        "scatter": lambda c, i, sn: _scatter(c, jnp.asarray(i), sn),
    }


def build_engine(
    params, cfg, layout: PagedLayout, *, chunk: int,
    temperature: float = 0.0, quantized: bool = False, seed: int = 0,
    share_prefix: bool = False, admission: str = "expected",
    steps: dict | None = None,
) -> ContinuousEngine:
    """Single-process engine over jitted paged steps and a donated cache.

    ``share_prefix`` maps cached prompt pages via the prefix index (COW on
    first write); ``admission`` picks the scheduler policy ("expected" =
    lazy pages + preemption, "reserve" = PR-9 full reservation). Pass a
    :func:`build_paged_steps` dict via ``steps`` to share compiled code
    across engines.
    """
    if steps is None:
        steps = build_paged_steps(
            params, cfg, temperature=temperature, seed=seed
        )
    cache = init_paged_cache(
        cfg, layout.npage, layout.page_size, quantized=quantized
    )
    sched = ContinuousScheduler(
        layout, admission=admission, share_prefix=share_prefix
    )
    return ContinuousEngine(
        sched, cache, steps["prefill"], steps["decode"], chunk=chunk,
        copy_fn=steps["copy"], gather_fn=steps["gather"],
        scatter_fn=steps["scatter"],
    )


def run_continuous(
    params, cfg, reqs: list[Request], *, slots: int, page_size: int,
    npage: int | None = None, chunk: int = 16, temperature: float = 0.0,
    quantized: bool = False, share_prefix: bool = False,
    admission: str = "expected", steps: dict | None = None,
):
    """Serve ``reqs`` with continuous batching; returns the ServeReport."""
    need = max(r.prompt_len + r.max_new for r in reqs)
    max_pages = -(-need // page_size)
    if npage is None:
        # enough for every slot to hold a worst-case request, plus the null page
        npage = 1 + slots * max_pages
    layout = PagedLayout(
        npage=npage, page_size=page_size, max_pages=max_pages, n_slots=slots
    )
    engine = build_engine(
        params, cfg, layout, chunk=chunk, temperature=temperature,
        quantized=quantized, share_prefix=share_prefix, admission=admission,
        steps=steps,
    )
    report = engine.run(reqs)
    engine.sched.pool.check_conservation(engine.sched.tables)
    return report


def run_static(
    params, cfg, reqs: list[Request], *, batch: int, temperature: float = 0.0,
    seed: int = 0, jit_cache: dict | None = None,
):
    """Legacy static batching: pad each batch of ``batch`` requests to the
    longest prompt, prefill, decode until the longest generation finishes.
    tokens/s counts USEFUL tokens only (what each request asked for), so
    padding and overrun show up as lost throughput. Pass (and reuse) a
    ``jit_cache`` dict to keep compiled steps across calls — benchmarks
    must not re-pay compilation inside the measured run."""
    t0 = time.perf_counter()
    key = jax.random.PRNGKey(seed)
    total_new = 0
    firsts, comps = [], []

    jc = jit_cache if jit_cache is not None else {}
    if "dec" not in jc:
        jc["dec"] = jax.jit(lambda c, t, pos: decode_step(params, cfg, c, t, pos))
    dec = jc["dec"]
    for i in range(0, len(reqs), batch):
        group = reqs[i:i + batch]
        pmax = max(r.prompt_len for r in group)
        gmax = max(r.max_new for r in group)
        toks = np.zeros((len(group), pmax), np.int32)
        for j, r in enumerate(group):
            toks[j, pmax - r.prompt_len:] = r.prompt  # left-pad
        if ("prefill", pmax + gmax) not in jc:
            jc[("prefill", pmax + gmax)] = jax.jit(
                lambda t, ml=pmax + gmax: prefill(params, cfg, t, max_len=ml)
            )
        logits, cache = jc[("prefill", pmax + gmax)](jnp.asarray(toks))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / temperature, axis=-1)
        else:
            tok = jnp.argmax(logits, -1)
        t_first = time.perf_counter()
        for j, r in enumerate(group):
            firsts.append((t_first - t0) * 1e3)
        done_at = [None] * len(group)
        for step in range(1, gmax):
            lg, cache = dec(cache, tok, pmax + step - 1)
            if temperature > 0:
                key, sub = jax.random.split(key)
                tok = jax.random.categorical(sub, lg / temperature, axis=-1)
            else:
                tok = jnp.argmax(lg, -1)
            jax.block_until_ready(tok)
            now = time.perf_counter()
            for j, r in enumerate(group):
                if done_at[j] is None and step + 1 >= r.max_new:
                    done_at[j] = now
        now = time.perf_counter()
        for j, r in enumerate(group):
            total_new += r.max_new
            comps.append(((done_at[j] or now) - t0) * 1e3)
    wall = time.perf_counter() - t0
    return {
        "n_requests": len(reqs),
        "total_new_tokens": total_new,
        "wall_s": wall,
        "tokens_per_s": total_new / wall if wall > 0 else 0.0,
        "first_token_p50_ms": float(np.percentile(firsts, 50)),
        "first_token_p99_ms": float(np.percentile(firsts, 99)),
        "completion_p50_ms": float(np.percentile(comps, 50)),
        "completion_p99_ms": float(np.percentile(comps, 99)),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(PUBLIC_TO_MODULE))
    ap.add_argument("--mode", choices=["continuous", "static"], default="continuous")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument(
        "--requests", default=None,
        help="mixed workload 'prompt:gen,prompt:gen,...' (overrides --batch/--prompt/--gen)",
    )
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument(
        "--reduced", action=argparse.BooleanOptionalAction, default=True,
        help="CPU-sized config (--no-reduced lowers the full arch)",
    )
    ap.add_argument("--quantized", action="store_true", help="int8 KV pages")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument(
        "--share-prefix", action="store_true",
        help="map cached prompt pages via the prefix index (COW on write)",
    )
    ap.add_argument(
        "--admission", choices=["expected", "reserve"], default="expected",
        help="'expected' admits on fresh prompt pages and preempts under "
             "pressure; 'reserve' requires the full worst-case reservation",
    )
    ap.add_argument(
        "--npage", type=int, default=None,
        help="pool size override (default: worst-case fit for --slots)",
    )
    args = ap.parse_args()
    enable_compile_cache()

    arch = get_arch(args.arch)
    cfg = reduce_cfg(arch.model, layers=2, d_model=128) if args.reduced else arch.model
    params = init_params(jax.random.PRNGKey(0), cfg)

    pairs = (
        parse_requests(args.requests)
        if args.requests
        else [(args.prompt, args.gen)] * args.batch
    )
    reqs = make_workload(cfg, pairs)

    if args.mode == "continuous":
        rep = run_continuous(
            params, cfg, reqs, slots=args.slots, page_size=args.page_size,
            npage=args.npage, chunk=args.chunk, temperature=args.temperature,
            quantized=args.quantized, share_prefix=args.share_prefix,
            admission=args.admission,
        ).to_dict()
    else:
        rep = run_static(
            params, cfg, reqs, batch=args.batch, temperature=args.temperature
        )
    print(json.dumps(rep, indent=1))


if __name__ == "__main__":
    main()
