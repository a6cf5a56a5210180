"""A cell small enough for a CPU test: the qwen1.5-0.5b block at width 64,
two layers, two workers, each cell's wire at a 128-wide block."""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"


def program_config(arch, m):
    """Stands in for the entry's config lookup: the registry holds only
    published sizes, so the tiny width is set here."""
    from repro.configs import get_arch
    from repro.models.config import dense_stack

    return dataclasses.replace(
        get_arch(arch).model, d_model=m.hidden_size,
        num_heads=m.num_attention_heads, num_kv_heads=m.num_key_value_heads,
        d_ff=m.intermediate_size, vocab_size=m.vocab_size,
        segments=dense_stack(m.num_hidden_layers),
    )


def spec(workload: str) -> dict:
    """The cell ``workload`` of BENCHMARK.json with its limits and wire, cut
    to the tiny model."""
    from bench.run import load_spec

    full = load_spec(ROOT, workload)
    traffic = dict(full["traffic"])
    traffic.update(
        wire={**traffic["wire"], "block": 128}, batch_per_worker=2,
        seq_len=128,
    )
    arch = full["config"]["program_arch"]
    config = json.loads((DATA / f"tiny-{arch}.json").read_text())
    return {**full, "config": config, "traffic": traffic}


def workloads() -> list:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [w["name"] for w in bench["workloads"]]


def patch(monkeypatch):
    """Tiny program config, and no persistent cache (CPU entries are of no
    use to the chip, and the cache is the checkout's)."""
    from bench import run
    from bench.entries import trainer_chunk

    monkeypatch.setattr(trainer_chunk, "_program_config", program_config)
    monkeypatch.setattr(run, "set_compile_cache", lambda root: None)
