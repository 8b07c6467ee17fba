"""Device idle time inside the program's own spans, from a JAX profiler
trace (``*.xplane.pb``).

While ``repro.obs`` tracing is enabled, every program span is also a
``jax.profiler.TraceAnnotation``, so a traced window holds them on the host
plane beside the harness's own annotations (``trace_reduce.ANNOTATIONS``)
and on the device's clock.  Of a served decode step they are
``serve.decode`` > ``serve.decode.prep``, ``exec.decode_step`` >
(``exec.check``, ``exec.walk``), ``serve.decode.sample``,
``serve.decode.readback``, ``serve.decode.commit``; of a prefill
``serve.prefill`` > ``exec.prefill`` > (``exec.check``, ``exec.walk``).

Each stretch of the window in which no op runs on the device is cut at
every span boundary, and each piece goes to the innermost program span
that covers it.  (``trace_reduce`` gives a whole stretch to the annotation
open at its midpoint; one stretch here often runs from a step's read-back
through its commit and the next step's prep and check into its walk.)
Pieces that lie in no ``serve.decode`` or ``serve.prefill`` go to no
metric.  Only spans on the thread that wrote the harness's annotations are
read.
"""

from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field

import loadgen
import trace_reduce

PROGRAM = ("serve.", "exec.")
STEPS = ("serve.decode", "serve.prefill")
# per-step idle, by the innermost program span it lies in
DECODE_PARTS = {
    "decode_walk_ms": ("exec.walk",),
    "decode_prep_ms": ("serve.decode.prep", "exec.check",
                       "serve.decode.sample", "serve.decode.commit"),
    "decode_sync_ms": ("serve.decode.readback",),
}


@dataclass
class Span:
    name: str
    start: int
    end: int
    args: dict = field(default_factory=dict)


@dataclass
class Step:
    """One ``serve.decode`` or ``serve.prefill`` span: its idle seconds by
    innermost program span, and the ``exec.walk`` launches inside it."""
    name: str
    start: int
    end: int
    idle: dict = field(default_factory=lambda: defaultdict(float))
    launches: list = field(default_factory=list)
    modules: int = 0                 # device XLA Modules started inside it


@dataclass
class Spans:
    steps: list = field(default_factory=list)       # [Step]
    idle: dict = field(default_factory=dict)        # "<harness>/<span>" -> s

    def idle_spans(self) -> list:
        """The ten largest idle totals by harness annotation / program
        span (``-`` where no program span covers the piece)."""
        top = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return [[n, v] for n, v in top]

    def _median(self, step: str, values) -> float | None:
        v = [values(s) for s in self.steps if s.name == step]
        v = [x for x in v if x is not None]
        return loadgen.percentile(v, 50) if v else None

    def metrics(self) -> dict:
        """Each per-layer value this reduction gives (None where the trace
        holds no program span to read)."""
        out = {name: self._median(
                   "serve.decode",
                   lambda s, ps=parts: 1e3 * sum(s.idle.get(p, 0.0)
                                                 for p in ps))
               for name, parts in DECODE_PARTS.items()}
        out["decode_launches"] = self._median(
            "serve.decode",
            lambda s: loadgen.percentile(s.launches, 50) if s.launches
            else None)
        out["prefill_walk_ms"] = self._median(
            "serve.prefill", lambda s: 1e3 * s.idle.get("exec.walk", 0.0))
        return out


def load(path) -> tuple[list[Span], list[int]]:
    """(program spans on the harness's thread, start of each device XLA
    module of the first chip) from one xplane file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans, modules = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and not modules:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = sorted(int(e.start_ns) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = list(line.events)
                if not any(e.name in trace_reduce.ANNOTATIONS for e in evs):
                    continue
                spans += [Span(e.name, int(e.start_ns),
                               int(e.start_ns + e.duration_ns),
                               {k: v for k, v in e.stats})
                          for e in evs if e.name.startswith(PROGRAM)]
    return spans, modules


def reduce(chips, host, spans: list[Span], modules=()) -> Spans:
    """``chips`` and ``host`` as ``trace_reduce.load`` gives them."""
    out = Spans()
    if not spans or not host:
        return out
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    busy = trace_reduce._union(trace_reduce._clip(
        [(o.start, o.start + o.dur) for o in chips[0]], lo, hi)) if chips \
        else []
    gaps, prev = [], lo
    for b0, b1 in busy:
        if b0 > prev:
            gaps.append((prev, b0))
        prev = max(prev, b1)
    if hi > prev:
        gaps.append((prev, hi))

    # every interval, harness and program; sweep their boundaries keeping
    # the stack of open ones, so each elementary segment knows its
    # innermost harness annotation, program span and step
    items = [(n, s, e, None) for n, s, e in host if e > s]
    items += [(sp.name, sp.start, sp.end, sp) for sp in spans
              if sp.end > sp.start]
    steps = {i: Step(n, s, e) for i, (n, s, e, sp) in enumerate(items)
             if sp is not None and n in STEPS and lo <= s and e <= hi}
    marks = sorted([(s, 1, -e, i) for i, (_, s, e, _) in enumerate(items)]
                   + [(e, 0, 0, i) for i, (_, s, e, _) in enumerate(items)])
    segs, stack, t_prev = [], [], None
    for t, opening, _, i in marks:
        if stack and t > t_prev:
            segs.append((t_prev, t, _attribution(items, stack, steps)))
        if opening:
            stack.append(i)
        else:
            stack.remove(i)
        t_prev = t

    idle = defaultdict(float)
    starts = [a for a, _, _ in segs]
    for g0, g1 in gaps:
        k = max(bisect.bisect_right(starts, g0) - 1, 0)
        while k < len(segs) and segs[k][0] < g1:
            a, b, (harness, program, step) = segs[k]
            piece = (min(b, g1) - max(a, g0)) * 1e-9
            if piece > 0:
                idle[f"{harness}/{program}"] += piece
                if step is not None:
                    step.idle[program] += piece
            k += 1
    out.idle = dict(idle)
    out.steps = sorted(steps.values(), key=lambda s: s.start)
    step_starts = [st.start for st in out.steps]
    for sp in spans:
        k = bisect.bisect_right(step_starts, sp.start) - 1
        if (sp.name == "exec.walk" and "launches" in sp.args and k >= 0
                and sp.start < out.steps[k].end):
            out.steps[k].launches.append(int(sp.args["launches"]))
    for st in out.steps:
        st.modules = (bisect.bisect_right(modules, st.end)
                      - bisect.bisect_left(modules, st.start))
    return out


def _attribution(items, stack, steps):
    """(harness annotation, program span, step) innermost in ``stack``."""
    harness = program = "-"
    step = None
    for i in reversed(stack):
        name, _, _, sp = items[i]
        if sp is None and harness == "-":
            harness = name
        elif sp is not None and program == "-":
            program = name
        if step is None:
            step = steps.get(i)
    return ("host" if harness == "-" else harness), program, step


def reduce_file(path) -> Spans:
    chips, host = trace_reduce.load(path)
    spans, modules = load(path)
    return reduce(chips, host, spans, modules)
