"""From a profiler trace (``.xplane.pb``) to device intervals and host spans.

Device and host events share one clock in the trace.  On each device plane
(``/device:TPU:<n>``) the ``XLA Ops`` line holds one event per HLO
instruction executed, named by its HLO text (``%flat_vr_lamb.1 = (...)
custom-call(...)``); loop and call instructions (``while``, ``conditional``,
``call``) enclose the instructions they run and are left out, so that no
time counts twice.  The ``XLA Modules`` line holds one event per program
execution.  The benchmark's own host spans (``jax.profiler.TraceAnnotation``:
``window``, ``data_wait``, ``dispatch``, ``sync``) are found by name on the
host plane.

The profiler runs only around the traced window, so every device op in the
trace belongs to the window's steps; they are all counted.  The device
clock sits up to about a millisecond off the host's, so device ops are not
cut at the host span's edges: the window's length is the host span's.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Optional, Tuple

CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")
SPANS = ("window", "data_wait", "dispatch", "sync")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # HLO instruction name, e.g. "flash_attention.45"
    start: int  # ns
    dur: int  # ns
    text: str  # the whole HLO instruction


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]  # device plane name -> its leaf ops, by start
    modules: Dict[str, List[Tuple[str, int, int]]]  # plane -> (name, start, dur)
    spans: List[Tuple[str, int, int]]  # the benchmark's host spans

    @property
    def window(self) -> Tuple[int, int]:
        w = [s for s in self.spans if s[0] == "window"]
        if len(w) != 1:
            raise ValueError(f"the trace holds {len(w)} 'window' spans, not one")
        return w[0][1], w[0][1] + w[0][2]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9


def op_name(text: str) -> str:
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{trace_dir}: {len(paths)} xplane files, want 1")
    return paths[0]


def load(path: str) -> Trace:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs = [Op(op_name(e.name), int(e.start_ns), int(e.duration_ns), e.name)
                           for e in line.events]
                    ops[plane.name] = sorted((o for o in evs if not CONTAINER.match(o.name)),
                                             key=lambda o: o.start)
                elif line.name == "XLA Modules":
                    modules[plane.name] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events if e.name in SPANS)
    return Trace(ops, modules, sorted(spans, key=lambda s: s[1]))


def union(intervals: List[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _intervals(ops: List[Op]) -> List[Tuple[int, int]]:
    return [(o.start, o.start + o.dur) for o in ops]


def _span(trace: Trace, plane: str) -> Tuple[int, int]:
    """The window on the device's clock: the host span, widened to hold
    every device op of the trace."""
    lo, hi = trace.window
    ops = trace.ops[plane]
    if ops:
        lo = min(lo, ops[0].start)
        hi = max(hi, max(o.start + o.dur for o in ops))
    return lo, hi


def busy_ns(trace: Trace, plane: str) -> int:
    return sum(e - s for s, e in union(_intervals(trace.ops[plane]), *_span(trace, plane)))


def mean_busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran on the device, averaged over the
    device planes."""
    planes = list(trace.ops)
    if not planes:
        return 0.0
    return sum(busy_ns(trace, p) for p in planes) / len(planes) * 1e-9


def op_ns(trace: Trace, pick: Callable[[Op], bool]) -> int:
    """Summed device time of the ops ``pick`` selects, over all planes."""
    return sum(o.dur for ops in trace.ops.values() for o in ops if pick(o))


def busy_after(trace: Trace, first: Callable[[Op], bool]) -> int:
    """Device time from the start of the first op that ``first`` selects in
    each program execution to the end of that execution, summed."""
    total = 0
    for plane, mods in trace.modules.items():
        ops = trace.ops.get(plane, [])
        for _, ms, md in mods:
            inside = [o for o in ops if ms <= o.start < ms + md]
            start = next((o.start for o in inside if first(o)), None)
            if start is None:
                continue
            total += sum(e - s for s, e in union(
                _intervals([o for o in inside if o.start >= start]), start, ms + md))
    return total


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` ops with the most summed device time, in seconds."""
    tot: Dict[str, int] = {}
    for ops in trace.ops.values():
        for o in ops:
            tot[o.name] = tot.get(o.name, 0) + o.dur
    return [(k, v * 1e-9) for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle stretches of the first device inside the
    window, each labelled by the host span that covers most of it."""
    if not trace.ops:
        return []
    plane = sorted(trace.ops)[0]
    lo, hi = _span(trace, plane)
    busy = union(_intervals(trace.ops[plane]), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    host = [s for s in trace.spans if s[0] != "window"]
    out = []
    for s, e in gaps:
        best, label = 0, "none"
        for name, hs, hd in host:
            cover = min(e, hs + hd) - max(s, hs)
            if cover > best:
                best, label = cover, name
        out.append((label, (e - s) * 1e-9))
    return sorted(out, key=lambda g: -g[1])[:n]


def overlap_ns(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the intersection of two lists of sorted, disjoint
    intervals (as ``union`` gives them)."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def exposed_ns(trace: Trace, plane: str, pick: Callable[[Op], bool]) -> int:
    """Time on ``plane`` in which an op that ``pick`` selects runs and no
    other op does."""
    span = _span(trace, plane)
    ops = trace.ops[plane]
    mine = union(_intervals([o for o in ops if pick(o)]), *span)
    rest = union(_intervals([o for o in ops if not pick(o)]), *span)
    return sum(e - s for s, e in mine) - overlap_ns(mine, rest)


# the HLO opcode: the first word after the instruction's type that opens
# its operand list
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute")


def opcode(op: Op) -> str:
    """The HLO opcode of the op's instruction, "" where its text has none."""
    parts = op.text.split(" = ", 1)
    m = _OPCODE.search(" " + parts[1]) if len(parts) == 2 else None
    return m.group(1) if m else ""


def is_collective(op: Op) -> bool:
    """A synchronous collective, or the wait (``-done``) of an asynchronous
    one; its ``-start`` only launches it and is not selected."""
    code = opcode(op)
    return code.removesuffix("-done") in COLLECTIVES


def spans_ns(trace: Trace, name: str) -> Optional[int]:
    lo, hi = trace.window
    got = [d for n, s, d in trace.spans if n == name and lo <= s < hi]
    return sum(got) if got else None


def named(*prefixes: str) -> Callable[[Op], bool]:
    """Selects ops whose HLO name, without its ``.<n>`` suffix, is one of
    ``prefixes`` (the program's kernels carry their Pallas call's name)."""
    return lambda op: op.name.split(".")[0] in prefixes


_STAT_OPERAND = re.compile(r"f32\[[0-9,]*,1\]")


def reads_row_stats(op: Op) -> bool:
    """The op takes a float32 (..., 1) operand, as an attention backward
    takes its softmax statistics (lse) and delta."""
    i = op.text.find("custom-call(")
    return i >= 0 and bool(_STAT_OPERAND.search(op.text[i:]))
