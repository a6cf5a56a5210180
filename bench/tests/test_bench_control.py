"""The control: the reference computed in bfloat16, put in the program's
place, fails the cell's limits, where the program passes them. At a tiny
size on the CPU; the readings at the cells' own sizes are in PERF.md."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import compare
from bench.entries import trainer_chunk
from bench.tests import tiny


@pytest.mark.parametrize("workload", tiny.workloads())
def test_control_fails_where_the_program_passes(workload, monkeypatch):
    tiny.patch(monkeypatch)
    spec = tiny.spec(workload)
    s = trainer_chunk.Session(spec["config"], spec["traffic"], 7)
    s.build()
    prog = s.first_rounds()
    s.release()
    want = s.reference()
    ok, checks = compare.judge(compare.numbers(prog, want), spec["limits"])
    assert ok, checks
    control = s.reference(dtype=jnp.bfloat16)
    ok, checks = compare.judge(compare.numbers(control, want), spec["limits"])
    assert not ok, checks
