"""Bytes the mesh uplink's Pallas kernels move, from the compiled round's
own text: every call of ``randk_gather`` (one worker's rows of a leaf, its
offsets, the kept values) and of ``scatter_accum`` (every worker's values and
offsets, the leaf's dense mean) reads each operand once and writes each
result once, at the shapes the compiled call has on its chip."""

from __future__ import annotations

import re

from bench.trace import _types

#: the mesh uplink's kernels, by the name the program gives their calls
KERNELS = ("randk_gather", "scatter_accum")

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
            "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8}

_CALL = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s+custom-call\((.*)$", re.M)
_OPERANDS = re.compile(r"operand_layout_constraints=\{(.*?)\}(?:, [a-z_]+=|$)")


def array_bytes(types) -> int:
    total = 0
    for dt, dims in types:
        size = ITEMSIZE[dt]
        for x in dims:
            size *= x
        total += size
    return total


def call_bytes(hlo_text: str) -> dict:
    """``{instruction: bytes}`` of every call of the mesh uplink's kernels
    in a compiled module's text."""
    out = {}
    for name, result, rest in _CALL.findall(hlo_text):
        if not name.startswith(KERNELS) or "tpu_custom_call" not in rest:
            continue
        # a compiled module names its operands by instruction and gives their
        # shapes as the call's operand layout constraints
        operands = _OPERANDS.search(rest)
        if operands is None:
            continue
        out[name] = array_bytes(_types(result)) + array_bytes(_types(operands.group(1)))
    return out
