"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a run of
each cell, cut to a tiny model on the CPU: a sound run is correct; a round
that returns its state unchanged, and a round that leaves out half of the
batch and takes the mean over the rest, are not.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from bench import run
from bench.entries import trainer_chunk
from bench.tests import tiny


class StateUnchanged(trainer_chunk.Session):
    def build(self):
        super().build()
        self.tr._jitted_chunk = jax.jit(lambda carry, steps: (carry, jnp.zeros(())))


class HalfBatch(trainer_chunk.Session):
    def build(self):
        super().build()
        whole = self.tr._batches

        def half(step, per_worker):
            rows = max(1, per_worker // 2)
            return jax.tree.map(lambda t: t[:, :rows], whole(step, per_worker))

        self.tr._batches = half


def _run(workload, monkeypatch, session_cls=None):
    tiny.patch(monkeypatch)
    return run.run(workload, 2**31 + 101, 0.5, False, require_tpu=False,
                   spec=tiny.spec(workload), session_cls=session_cls)


@pytest.mark.parametrize("workload", tiny.workloads())
def test_sound_run_is_correct(workload, monkeypatch):
    line = _run(workload, monkeypatch)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", [StateUnchanged, HalfBatch])
@pytest.mark.parametrize("workload", tiny.workloads())
def test_broken_round_is_not_correct(workload, fault, monkeypatch):
    line = _run(workload, monkeypatch, fault)
    assert not line["correct"], line["checks"]
