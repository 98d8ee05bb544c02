"""Reduction of a profiler trace to device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace`: per device, its op events and its program (module)
events, and the host spans of this harness. Everything else here works on
that plain form, so a test can build one by hand.

Events are ``(name, start_ns, duration_ns)``. Device planes are the
``/device:TPU:<n>`` planes; their ``XLA Ops`` line holds one event per
executed operation, their ``XLA Modules`` line one per executed program.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]

HOST_SPANS = ("client.submit", "engine.step", "client.observe", "client.wait")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter", re.IGNORECASE)


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Event]]        # device plane -> op events
    modules: Dict[str, List[Event]]    # device plane -> program events
    host: List[Event]                  # harness spans (HOST_SPANS)

    def window(self) -> Tuple[float, float]:
        """[first harness span start, last harness span end] in ns."""
        if not self.host:
            raise ValueError("trace holds no harness span")
        return (min(s for _, s, _ in self.host),
                max(s + d for _, s, d in self.host))


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(found)}")
    return found[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and plane.name[12:].isdigit():
            for line in plane.lines:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events]
                if line.name == "XLA Ops":
                    ops[plane.name] = evs
                elif line.name == "XLA Modules":
                    modules[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, float(e.start_ns), float(e.duration_ns))
                         for e in line.events if e.name in HOST_SPANS]
    return Trace(ops=ops, modules=modules, host=host)


def clip(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def merge(events: Sequence[Event]) -> List[Tuple[float, float]]:
    """Union of event intervals as sorted disjoint (start, end)."""
    spans = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(spans: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in spans)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Parts of the disjoint sorted spans ``a`` not covered by ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def planes(trace: Trace) -> List[str]:
    return sorted(trace.ops, key=lambda p: int(p.rsplit(":", 1)[1]))


def busy_seconds(trace: Trace) -> float:
    """Seconds with an op running, averaged over the device planes."""
    lo, hi = trace.window()
    ps = planes(trace)
    if not ps:
        return 0.0
    return sum(length(merge(clip(trace.ops[p], lo, hi)))
               for p in ps) / len(ps) * 1e-9


def module_seconds(trace: Trace, pattern: str,
                   plane: Optional[str] = None) -> Tuple[int, float]:
    """(executions, device seconds) of programs whose name matches
    ``pattern`` on ``plane`` (default: the first device) in the window.
    Only executions that start inside the window count."""
    if not trace.ops:
        return 0, 0.0
    lo, hi = trace.window()
    plane = plane or planes(trace)[0]
    rx = re.compile(pattern)
    hits = [(n, s, d) for n, s, d in trace.modules.get(plane, [])
            if rx.search(n) and lo <= s < hi]
    return len(hits), sum(d for _, _, d in hits) * 1e-9


def collective_exposed_seconds(trace: Trace,
                               plane: Optional[str] = None) -> float:
    """Seconds in which a collective op runs and no other op does."""
    lo, hi = trace.window()
    plane = plane or planes(trace)[0]
    evs = clip(trace.ops.get(plane, []), lo, hi)
    coll = merge([e for e in evs if COLLECTIVE.search(e[0])])
    other = merge([e for e in evs if not COLLECTIVE.search(e[0])])
    return length(subtract(coll, other)) * 1e-9


CONTROL_OPS = ("while", "conditional", "call")


def op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """[program/op, seconds] of the ops with most device time on the
    first device; control ops (a loop's time is its body's) are left out.
    Each op is named with the program that ran it."""
    if not trace.ops:
        return []
    lo, hi = trace.window()
    plane = planes(trace)[0]
    mods = sorted((s, s + d, n.split("(", 1)[0])
                  for n, s, d in trace.modules.get(plane, []))
    starts = [m[0] for m in mods]
    tot: Dict[str, float] = {}
    for name, s, d in clip(trace.ops.get(plane, []), lo, hi):
        op = op_name(name)
        if op.split(".", 1)[0] in CONTROL_OPS:
            continue
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        key = f"{prog}/{op}"
        tot[key] = tot.get(key, 0.0) + d
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """[host span, seconds] of the longest gaps with no op on the first
    device, each named by the innermost harness span around its middle."""
    if not trace.ops:
        return []
    lo, hi = trace.window()
    busy = merge(clip(trace.ops.get(planes(trace)[0], []), lo, hi))
    gaps = subtract([(lo, hi)], busy)
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (a + b) / 2
        around = [(d, name) for name, s, d in trace.host if s <= mid <= s + d]
        out.append([min(around)[1] if around else "host.other",
                    (b - a) * 1e-9])
    return out
