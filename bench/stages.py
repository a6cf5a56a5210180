"""Device time of a traced window by the program's own stage of the round.

The program names the stages of its round (``repro.tracing.STAGES``) as
scopes that every compiled instruction keeps in its metadata, and its
trainer returns the ``{instruction: stage}`` map of the compiled chunk
(``Trainer.chunk_stages``). The reduced trace holds the self time of every
operation by instruction name, and of the Pallas kernels by kernel name
(``bench.trace.kernel_label``); a kernel counts under the stage of the
instruction that ran it, which the program names after the kernel.

A program that names no stages (no ``chunk_stages``, or an empty map) gives
no map, and the readers then report nothing.
"""

from __future__ import annotations

import collections
import re

import numpy as np

#: the key of operations in no stage, or missing from the map
UNSCOPED = "unscoped"

#: the flat engine's stages: the round's data movement around its kernels
FLAT_GLUE = ("marina.diff", "flat.pack", "flat.compress", "flat.epilogue", "flat.unpack")

_SUFFIX = re.compile(r"\.\d+$")


def stage_map(ctx) -> dict | None:
    """The window chunk's ``{instruction: stage}`` map, asked of the program
    once a run and kept on ``ctx``; None where the program names no stages."""
    if not hasattr(ctx, "stage_map"):
        tr = getattr(ctx.session, "tr", None)
        chunk_stages = getattr(tr, "chunk_stages", None)
        found = None
        if chunk_stages is not None:
            steps = np.arange(ctx.session.rounds_per_call, dtype=np.int32)
            found = chunk_stages(ctx.session.carry, steps) or None
        ctx.stage_map = found
    return ctx.stage_map


def by_stage(reduced, stages: dict) -> dict:
    """``{(stage, is_kernel): ns}`` of self time over all devices. A kernel
    key (a kernel's name) takes the stage of the instruction named after it;
    any other key is an instruction name."""
    by_kernel = {}
    for instr, st in stages.items():
        by_kernel.setdefault(_SUFFIX.sub("", instr), st)
    out = collections.Counter()
    for name, ns in reduced.op_ns.items():
        kernel = name in reduced.kernel_ns
        st = stages.get(name) or (by_kernel.get(name) if kernel else None)
        out[(st or UNSCOPED, kernel)] += ns
    return dict(out)


def ms_per_round(ctx, stages, kernels: bool = True) -> float | None:
    """Self time a round, per device, of the operations in ``stages``
    (kernels included unless ``kernels`` is False), in ms; None without a
    stage map or rounds."""
    m = stage_map(ctx)
    if not m or not ctx.rounds:
        return None
    ns = sum(v for (st, k), v in by_stage(ctx.reduced, m).items()
             if st in stages and (kernels or not k))
    return ns / max(1, ctx.reduced.devices) / ctx.rounds / 1e6
