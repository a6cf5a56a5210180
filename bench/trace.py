"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:TPU:<i>``; their ``XLA Ops`` line holds one event
per operation that ran on the chip. The harness's own host spans
(``bench.window``, ``bench.dispatch``, ``bench.wait``) sit on the host plane,
on the same clock. From these:

* busy time: the union of operation intervals inside the window, per device;
* time per operation name, and of the Pallas kernels (``tpu_custom_call``);
* collective time, and the part of it during which no other operation runs
  on that device (exposed);
* idle gaps between operations, each named by the host span that covers most
  of it (what the host was doing while the chip waited).
"""

from __future__ import annotations

import collections
import dataclasses
import re

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.dispatch", "bench.wait")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
)


@dataclasses.dataclass
class Reduced:
    window_ns: float
    devices: int
    busy_ns: float                    # mean over devices
    op_ns: dict                       # name -> self time ns (all devices)
    op_count: dict                    # name -> events (all devices)
    kernel_ns: dict                   # Pallas kernel name -> total ns
    kernel_count: dict
    collective_ns: float              # mean over devices
    collective_exposed_ns: float      # mean over devices
    gaps: list                        # (host label, ns), longest first


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _minus(intervals, cover):
    """Length of ``intervals`` (merged) not covered by ``cover`` (merged)."""
    total, j = 0.0, 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while cur < e and k < len(cover) and cover[k][0] < e:
            if cover[k][0] > cur:
                total += cover[k][0] - cur
            cur = max(cur, cover[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


_ARRAY = re.compile(r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)\[([\d,]*)\]")


def _types(text: str) -> list:
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _ARRAY.findall(text)]


def short_name(name: str) -> str:
    """``%pad.36 = f32[...] pad(...)`` -> ``pad.36``."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%")


def kernel_label(name: str) -> str | None:
    """The flat engine's kernel that a ``tpu_custom_call`` event ran, told
    apart by its operand and result types (the program gives its kernels no
    names of their own yet); None for an event that is not a Pallas kernel.
    An unknown kernel keeps its instruction name."""
    if "tpu_custom_call" not in name or " custom-call(" not in name:
        return None
    left, right = name.split(" custom-call(", 1)
    res = [dt for dt, _ in _types(left.split(" = ", 1)[-1])]
    ops = _types(right.split("custom_call_target", 1)[0])
    odt = [dt for dt, _ in ops]
    if res == ["s8", "f32"] and odt[-1:] == ["f32"]:
        return "qsgd_block_workers"
    if res == ["u32"] and odt[:1] == ["s8"]:
        return "nibble_pack"
    if res == ["s8"] and odt[:1] == ["u32"]:
        return "nibble_unpack"
    if res == ["f32", "s32"]:
        return "randk_seeded"
    if res == ["f32", "f32"]:
        if odt[:1] == ["s8"]:
            return "qsgd_epilogue"
        if odt[:2] == ["f32", "s32"]:
            return "scatter_epilogue"
        if len(ops) == 2 and len(ops[0][1]) == 3:
            return "mean_epilogue"
    return short_name(name)


def _self_times(evs):
    """Each event's duration less its nested events' (XLA Ops nests the ops
    of a while or conditional inside it); also whether it is a leaf."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    child = [0.0] * len(evs)
    stack = []
    for i in order:
        s, e = evs[i][0], evs[i][1]
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(ev[1] - ev[0]) - c for ev, c in zip(evs, child)], [c == 0 for c in child]


def reduce_profile(pd) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    host_spans, window = [], None
    dev_events = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if evs:
                dev_events[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in HOST_SPANS:
                        host_spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = window
    op_ns, op_count = collections.Counter(), collections.Counter()
    k_ns, k_count = collections.Counter(), collections.Counter()
    busy, coll, exposed = [], [], []
    gap_ns = collections.Counter()
    host_spans.sort()
    for evs in dev_events.values():
        inside = [(max(s, w0), min(e, w1), n) for s, e, n in evs if e > w0 and s < w1]
        selfs, leaves = _self_times(inside)
        for (s, e, n), own in zip(inside, selfs):
            label = kernel_label(n)
            op_ns[label or short_name(n)] += own
            op_count[label or short_name(n)] += 1
            if label is not None:
                k_ns[label] += e - s
                k_count[label] += 1
        merged = _merge([(s, e) for s, e, _ in inside])
        busy.append(sum(e - s for s, e in merged))
        is_coll = [bool(COLLECTIVE.search(n.split("(", 1)[0])) for _, _, n in inside]
        c = _merge([(s, e) for (s, e, _), k in zip(inside, is_coll) if k])
        other = _merge([(s, e) for (s, e, _), k, leaf in zip(inside, is_coll, leaves)
                        if leaf and not k])
        coll.append(sum(e - s for s, e in c))
        exposed.append(_minus(c, other))
        # idle gaps inside the window, named by the host span that covers
        # most of each (both lists are in time order)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        j = 0
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 <= g0:
                continue
            while j < len(host_spans) and host_spans[j][1] <= g0:
                j += 1
            best, lab = 0.0, "host: other"
            for s, e, n in host_spans[j:]:
                if s >= g1:
                    break
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, lab = ov, n
            gap_ns[lab] += g1 - g0
    ndev = max(1, len(dev_events))
    return Reduced(
        window_ns=w1 - w0, devices=len(dev_events),
        busy_ns=sum(busy) / ndev, op_ns=dict(op_ns), op_count=dict(op_count),
        kernel_ns=dict(k_ns), kernel_count=dict(k_count),
        collective_ns=sum(coll) / ndev, collective_exposed_ns=sum(exposed) / ndev,
        gaps=sorted(gap_ns.items(), key=lambda kv: -kv[1]),
    )


def load(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def breakdown(r: Reduced, top: int = 10) -> dict:
    """The ``breakdown`` of a traced run's line: seconds, all digits."""
    ops = sorted(r.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[n, ns / 1e9] for n, ns in r.gaps[:top]],
    }
