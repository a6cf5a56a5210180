"""Named stages of the MARINA round, host spans, and the map from a compiled
program's instructions to its stages.

Stages are ``jax.named_scope``s: metadata in the compiled program that costs
nothing at run time. Every HLO instruction keeps the scope path of the JAX
operation it came from in its ``op_name`` (a fusion keeps its root's), and a
profiler trace names each device operation by its instruction, so
:func:`op_stages` on the compiled module's text gives every operation of a
traced round its stage.

Host spans write into the profiler's own trace, on the device trace's clock.
Both refuse names outside their tables, so the names a reader looks for
cannot drift from the names the program writes.
"""

from __future__ import annotations

import collections
import re

import jax

#: stage -> what it covers
STAGES = {
    "trainer.data": "the step's key, token stream and prefix, made in the scan",
    "marina.coin": "the round's key split and its Bernoulli coin c_k",
    "marina.backprop": "every worker's forward and backward, loss included",
    "marina.carry": "the h_i update",
    "marina.diff": "the uplink difference grads - h, with its faults",
    "flat.pack": "tree -> (n, rows, B) buffers, and x -> (rows, B)",
    "flat.compress": "uplink sampling and the wire round trip",
    "flat.epilogue": "dequant/scatter-mean, g += delta, x -= gamma * g",
    "flat.unpack": "buffer -> tree",
    "marina.metrics": "the estimator's norm and the round's bit ledger",
    "trainer.guard": "the non-finite guard and its revert",
    "transport.uplink": "the mesh's compressed uplink: per-leaf gather, worker all-gather, scatter-mean",
    "transport.sync": "the mesh's dense sync exchange: the worker psum of the gradients",
    "marina.update": "the mesh's x -= gamma * g and g += delta (no fused epilogue there)",
}

#: host span -> what it covers
SPANS = {
    "trainer.chunk": "one chunk's dispatch and its ledger read-back",
    "trainer.eval": "eval_loss at a log point",
    "trainer.checkpoint": "save_checkpoint",
    "trainer.restore": "resuming from the latest checkpoint",
}


def stage(name: str):
    """``jax.named_scope(name)`` for a name in :data:`STAGES`; a context
    manager, or a decorator that runs the whole function in the stage."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages are {sorted(STAGES)}")
    return jax.named_scope(name)


def span(name: str, **kw):
    """A host span in the profiler's trace; with ``step_num`` a step span.
    A no-op unless a profiler trace is being recorded."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}; spans are {sorted(SPANS)}")
    if "step_num" in kw:
        return jax.profiler.StepTraceAnnotation(name, **kw)
    return jax.profiler.TraceAnnotation(name, **kw)


# a path component may be wrapped by transformations: vmap(jvp(flat.pack))
_WRAPPED = re.compile(r"^(?:[\w\-]+\()*([^()]*)\)*$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")


def stage_of(op_name: str) -> str | None:
    """The innermost stage on an ``op_name`` path, or None."""
    found = None
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in STAGES:
            found = m.group(1)
    return found


def _most_common(stages) -> str | None:
    """The stage named most often (the first named among equals), or None."""
    counts = collections.Counter(s for s in stages if s)
    return counts.most_common(1)[0][0] if counts else None


def op_stages(hlo_text: str) -> dict:
    """``{instruction name: stage}`` for the instructions of a compiled
    module's text that belong to a stage.

    An instruction belongs to the innermost stage on its ``op_name`` path.
    A fusion whose root has no stage belongs to the stage most of its fused
    instructions carry. An instruction the compiler made with no metadata
    at all (a layout copy, an asynchronous copy or slice) belongs to the
    stage of the instructions that use it, or else of those it reads."""
    comps, body, op_name, refs, calls = set(), {}, {}, {}, {}
    cur = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            comps.add(cur)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        body.setdefault(cur, []).append(name)
        on, called = _OP_NAME.search(rest), _CALLS.search(rest)
        op_name[name] = on.group(1) if on else None
        calls[name] = called.group(1) if called else None
        refs[name] = _REF.findall(rest.split(", metadata=")[0])
    reads = {n: [r for r in rs if r in op_name and r not in comps]
             for n, rs in refs.items()}
    users = {}
    for name, rs in reads.items():
        for r in rs:
            users.setdefault(r, []).append(name)

    own = {}
    for name, on in op_name.items():
        st = stage_of(on) if on else None
        if st is None and on is None and calls[name] in body:
            st = _most_common(stage_of(op_name[i])
                              for i in body[calls[name]] if op_name[i])
        own[name] = st

    def along(name, step, seen):
        """The stage of ``name``'s neighbours in ``step``, looking through
        neighbours that have no metadata either."""
        found = []
        for nb in step.get(name, []):
            if nb not in seen:
                seen.add(nb)
                meta = own[nb] or op_name[nb] is not None
                found.append(own[nb] if meta else along(nb, step, seen))
        return _most_common(found)

    out = {}
    for name, st in own.items():
        if st is None and op_name[name] is None:
            st = along(name, users, {name}) or along(name, reads, {name})
        if st is not None:
            out[name] = st
    return out
