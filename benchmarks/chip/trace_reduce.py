"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read.

* Device ops: the events of each TPU plane's ``XLA Ops`` line.  Busy time
  is the union of their intervals inside the window, averaged over the
  chips; the idle share is 1 - busy / window.
* The window runs from the first to the last host annotation the harness
  wrote (``admit``, ``prefill``, ``insert``, ``decode``, ``release``).
* Each stretch in which no op runs on the device is attributed to the
  innermost host annotation open at its midpoint (``host`` where none is).
* Roofline: each op's least time on the chip is the larger of its FLOPs
  over the peak FLOP/s and its bytes over the peak bandwidth.  FLOPs and
  bytes come from the shapes of the op's operands and result, which the
  trace records in the op's HLO text (``ops_and_bytes``).  An op whose text
  the trace lacks counts in ``unshaped`` and in no share.
"""

from __future__ import annotations

import bisect
import functools
import glob
import math
import re
from collections import Counter, defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

ANNOTATIONS = ("admit", "prefill", "insert", "decode", "release", "generate",
               "train_step")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "s4": 0.5,
                "u4": 0.5}
# dtype[dims] and, optionally, its layout {...}; a layout that names a
# memory space S(n) places the array on the chip (VMEM, SMEM), not in HBM
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES)
                    + r")\[([0-9,]*)\](\{[^}]*\})?")


def shapes(text: str, hbm_only: bool = False
           ) -> list[tuple[str, tuple[int, ...]]]:
    """Every ``dtype[d0,d1,...]`` in an HLO instruction's text, in order;
    with ``hbm_only`` only those whose layout names no on-chip memory
    space."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims, layout in _SHAPE.findall(text)
            if not (hbm_only and "S(" in layout)]


def nbytes(dt: str, dims: tuple[int, ...]) -> float:
    return _DTYPE_BYTES[dt] * math.prod(dims)


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")
# ops that only hold other ops (their bodies appear as ops of their own)
CONTAINERS = ("while", "conditional", "call")


@functools.lru_cache(maxsize=None)
def parse(text: str):
    """(opcode, result shapes, operand shapes) of an HLO instruction's text
    ``%name = <result> opcode(<operands>), <attributes>``; None where it is
    not such a text."""
    head, sep, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    if m is None:
        return None
    return (m.group(1), shapes(rest[:max(m.start() - 1, 0)], True),
            shapes(rest[m.end() - 1:], True))


def ops_and_bytes(text: str, trip: int = 0) -> tuple[float, float] | None:
    """(FLOPs, bytes) of one HLO instruction from the shapes in its text.
    Bytes are the result's plus every operand's that live in HBM (an array
    whose layout names an on-chip memory space moves no HBM bytes); inside
    a loop of ``trip``
    iterations, an array whose leading dimension is ``trip`` is a stack of
    per-iteration slices, of which one is read or written.  FLOPs: a
    ``dot`` or ``convolution`` counts 2 * M * N * K (K from the operand and
    result sizes); every other op one per result element, which undercounts
    a fusion that holds a dot.  None for a container op, an asynchronous
    start or done (its time is not on this line), a buffer allocation, an
    in-place update (``dynamic-update-slice``, ``scatter``: its buffer
    operand is aliased, and what it moves cannot be told from shapes), or
    a text with no shapes."""
    p = parse(text)
    if p is None:
        return None
    opcode, res, opnds = p
    name = text.split(" = ")[0]
    if not res and not opnds:
        return 0.0, 0.0
    if (opcode in CONTAINERS or opcode.endswith(("-start", "-done"))
            or (opcode == "custom-call" and not opnds)
            or any(k in name or k == opcode
                   for k in ("dynamic-update-slice", "scatter"))):
        return None

    def size(dt, d):
        b = nbytes(dt, d)
        return b / trip if trip > 1 and len(d) >= 2 and d[0] == trip else b

    out_elems = sum(math.prod(d) for _, d in res)
    opnd_bytes = [size(dt, d) for dt, d in opnds]
    if "kind=kCustom" in text:
        # a gather: it reads at most as many bytes from a table as it
        # writes; one whose result is its first operand updates in place
        every = shapes(text.split(" = ", 1)[1])
        if len(every) > 1 and every[0] == every[1]:
            return None
        cap = sum(nbytes(dt, d) for dt, d in shapes(
            text.split(" = ", 1)[1][:text.split(" = ", 1)[1].find(opcode + "(")]))
        opnd_bytes = [min(b, cap) for b in opnd_bytes]
    byts = sum(size(dt, d) for dt, d in res) + sum(opnd_bytes)
    flops = float(out_elems)
    if opcode in ("dot", "convolution") and len(opnds) >= 2:
        a, b = math.prod(opnds[0][1]), math.prod(opnds[1][1])
        flops = 2.0 * out_elems * math.sqrt(a * b / max(out_elems, 1))
    return flops, byts


@dataclass
class Op:
    name: str
    start: int
    dur: int
    text: str = ""


@dataclass
class Summary:
    busy_s: float
    window_s: float
    ops: dict = field(default_factory=dict)          # name -> [seconds, calls]
    idle: dict = field(default_factory=dict)         # annotation -> seconds
    host_only: dict = field(default_factory=dict)    # annotation -> [seconds each]
    roofline: float | None = None                    # device-time-weighted share
    roofline_s: float = 0.0                          # op time with shapes
    unshaped: dict = field(default_factory=dict)     # op name -> seconds

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:10]
        gaps = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, v[0]] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in gaps]}


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class _Timeline:
    """Busy time before any instant, from disjoint sorted intervals."""

    def __init__(self, iv: list[tuple[int, int]]):
        self.starts = [s for s, _ in iv]
        self.ends = [e for _, e in iv]
        self.before = np.concatenate([[0], np.cumsum([e - s for s, e in iv])])

    def busy_until(self, t: int) -> int:
        i = bisect.bisect_right(self.starts, t) - 1
        if i < 0:
            return 0
        return int(self.before[i]) + min(t, self.ends[i]) - self.starts[i]

    def covered(self, lo: int, hi: int) -> int:
        return self.busy_until(hi) - self.busy_until(lo)


def _innermost(host: list[tuple[str, int, int]]):
    """Segments (start, end, name) of the innermost open annotation."""
    marks = sorted([(s, 1, -e, n) for n, s, e in host]
                   + [(e, 0, 0, n) for n, s, e in host])
    segs, stack, prev = [], [], None
    for t, kind, neg_end, n in marks:
        if stack and prev is not None and t > prev:
            segs.append((prev, t, stack[-1][1]))
        if kind:
            stack.append((-neg_end, n))
        else:
            for i in range(len(stack) - 1, -1, -1):
                if stack[i][1] == n and stack[i][0] == t:
                    del stack[i]
                    break
        prev = t
    return segs


def _stat(ev, *names) -> str:
    for k, v in ev.stats:
        if k in names:
            return str(v)
    return ""


def load(path) -> tuple[list[list[Op]], list[tuple[str, int, int]]]:
    """(device ops per chip, host annotations) from one xplane file.  On a
    TPU each event of the ``XLA Ops`` line is named by its HLO instruction's
    text; an op is named ``<module>/<instruction>`` after the event of the
    ``XLA Modules`` line that holds it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    chips, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                           e.name)
                          for e in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            ops = []
            for ev in lines.get("XLA Ops", []):
                t0 = int(ev.start_ns)
                text = ev.name if " = " in ev.name else _stat(ev, "long_name")
                instr = (text or ev.name).split(" = ")[0].lstrip("%")
                i = bisect.bisect_right(starts, t0) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= t0 else "?"
                ops.append(Op(f"{mod}/{instr}", t0, int(ev.duration_ns), text))
            chips.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ANNOTATIONS:
                        host.append((ev.name, int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)))
    return chips, host


def reduce(chips: list[list[Op]], host: list[tuple[str, int, int]],
           peaks: dict | None = None) -> Summary:
    if not host:
        raise ValueError("the trace holds no harness annotation")
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    window = hi - lo
    busy_each, ops = [], defaultdict(lambda: [0.0, 0])
    busy_iv: list[tuple[int, int]] = []
    for k, chip in enumerate(chips):
        iv = _union(_clip([(o.start, o.start + o.dur) for o in chip], lo, hi))
        busy_each.append(sum(e - s for s, e in iv))
        if k == 0:
            busy_iv = iv
        for o in chip:
            if lo <= o.start < hi and not _is_container(o):
                ops[o.name][0] += o.dur * 1e-9 / len(chips)
                ops[o.name][1] += 1
    s = Summary(busy_s=float(np.mean(busy_each)) * 1e-9 if chips else 0.0,
                window_s=window * 1e-9, ops=dict(ops))
    # idle stretches of the first chip, named by the innermost annotation
    gaps, prev = [], lo
    for b0, b1 in busy_iv:
        if b0 > prev:
            gaps.append((prev, b0))
        prev = max(prev, b1)
    if hi > prev:
        gaps.append((prev, hi))
    segs = _innermost(host)
    seg_starts = [a for a, _, _ in segs]
    idle = defaultdict(float)
    for g0, g1 in gaps:
        mid = (g0 + g1) // 2
        i = bisect.bisect_right(seg_starts, mid) - 1
        name = segs[i][2] if i >= 0 and segs[i][1] > mid else "host"
        idle[name] += (g1 - g0) * 1e-9
    s.idle = dict(idle)
    line = _Timeline(busy_iv)
    host_only = defaultdict(list)
    for n, a0, a1 in host:
        host_only[n].append((a1 - a0 - line.covered(a0, a1)) * 1e-9)
    s.host_only = dict(host_only)
    if peaks is not None:
        _roofline(s, chips, lo, hi, peaks)
    return s


def _is_container(o: Op) -> bool:
    p = parse(o.text) if o.text else None
    return p is not None and p[0] in CONTAINERS


def loop_trips(chip: list[Op]) -> list[int]:
    """For each op, the trip count of the innermost loop op that holds it
    (0 outside any loop): the most times one instruction of that loop's
    body ran inside it."""
    loops = sorted((o.start, o.start + o.dur, i) for i, o in enumerate(chip)
                   if o.text and (p := parse(o.text)) and p[0] == "while")
    starts = [a for a, _, _ in loops]
    owner = [-1] * len(chip)
    for i, o in enumerate(chip):
        j = bisect.bisect_right(starts, o.start) - 1
        while j >= 0:
            a, b, k = loops[j]
            if k != i and a <= o.start and o.start + o.dur <= b:
                owner[i] = j
                break
            j -= 1
            if j >= 0 and loops[j][1] < o.start and loops[j][0] < starts[j]:
                break
    counts: dict[int, Counter] = defaultdict(Counter)
    for i, o in enumerate(chip):
        if owner[i] >= 0:
            counts[owner[i]][o.name] += 1
    trip = {j: max(c.values()) for j, c in counts.items()}
    return [trip.get(owner[i], 0) for i in range(len(chip))]


def _roofline(s: Summary, chips, lo, hi, peaks) -> None:
    bound, timed = 0.0, 0.0
    unshaped = defaultdict(float)
    for chip in chips:
        trips = loop_trips(chip)
        for o, trip in zip(chip, trips):
            if not lo <= o.start < hi or o.dur <= 0 or _is_container(o):
                continue
            fb = ops_and_bytes(o.text, trip) if o.text else None
            if fb is None:
                unshaped[o.name] += o.dur * 1e-9
                continue
            flops, byts = fb
            bound += max(flops / peaks["bf16_flops_per_s"],
                         byts / peaks["hbm_bytes_per_s"])
            timed += o.dur * 1e-9
    s.unshaped = dict(unshaped)
    s.roofline_s = timed
    s.roofline = 100.0 * bound / timed if timed > 0 else None


def reduce_dir(trace_dir, peaks: dict | None = None) -> Summary:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no xplane trace under {trace_dir}")
    chips, host = load(files[-1])
    return reduce(chips, host, peaks)
