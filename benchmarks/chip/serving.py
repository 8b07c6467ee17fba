"""A serving cell: set-up, the measured window, and the check.

The system under test is ``repro``'s serving engine, built as
``python -m repro.launch.serve`` builds it (``build_engine``), and driven
through its three-stage API: ``Engine.prefill`` (which reads the first
token back) -> ``Engine.insert`` -> ``Engine.generate_step(1)`` (which reads
every slot's token back) -> ``Engine.release``.  The admission loop is the
benchmark's own: before each decode step it admits every waiting request
into a free slot, and it reads each token back as a streaming server does.

Every host span the loop records is also a ``jax.profiler.TraceAnnotation``
named after the engine call (``admit``, ``prefill``, ``insert``, ``decode``,
``release``), so that a traced run can attribute device idle time to what
the host was doing.
"""

from __future__ import annotations

import gc
import math
import time
from dataclasses import dataclass, field

import numpy as np

import arch
import loadgen

# seconds at the end of the window that a traced run records
TRACE_S = 5.0


@dataclass
class Window:
    """Everything the metric readers see of one run."""
    workload: dict
    cfg: dict
    mix: dict
    seconds: float
    requests: list = field(default_factory=list)      # attempted
    served: list = field(default_factory=list)        # any token read back
    t_open: float = 0.0
    t_close: float = 0.0
    setup_s: float = 0.0
    land_s: float = 0.0
    prefill_calls: list = field(default_factory=list)  # (t0, t1, bucket)
    decode_calls: list = field(default_factory=list)   # (t0, t1, active)
    compiles: int = 0
    checked: dict = field(default_factory=dict)       # what the check read
    trace: object = None             # trace_reduce.Summary in a traced run
    peaks: dict | None = None


def build(workload: dict, cfg: dict, weights):
    """The engine, as ``repro.launch.serve --stitch`` builds it."""
    from repro.launch.serve import build_engine, make_parser
    from repro.models import build_model

    e = workload["engine"]
    mcfg = arch.load(cfg).program(cfg, workload["config"])
    argv = ["--arch", workload["config"], "--slots", str(e["slots"]),
            "--max-len", str(e["max_len"]), "--page-size", str(e["page_size"])]
    if e["stitch"]:
        argv += ["--stitch", "--cache-dir", e["stitch_cache_dir"],
                 "--plan-budget", str(e["plan_budget"])]
    return build_engine(make_parser().parse_args(argv), mcfg,
                        build_model(mcfg), weights)


def _bucket(eng, n: int) -> int:
    from repro.serve.scheduler import ADMISSION_BUCKET
    return min(ADMISSION_BUCKET.bucket_dim(n), eng.cfg.max_len)


def land(eng, reqs: list) -> float:
    """Trace every prefill bucket and insert shape the traffic uses and the
    decode step, land their stitched plans, then run each once more so the
    stitched programs compile.  Returns the seconds it took."""
    from repro.serve.kv import Prefix

    t0 = time.perf_counter()
    page = eng.cfg.page_size
    by_bucket: dict[int, np.ndarray] = {}
    pages: dict[int, set] = {}
    for r in reqs:
        b = _bucket(eng, len(r.prompt))
        by_bucket.setdefault(b, r.prompt)
        pages.setdefault(b, set()).add(max(1, math.ceil(len(r.prompt) / page)))
    for rnd in range(2):         # 0: traces and plans; 1: stitched compiles
        pxs = {b: eng.prefill(p) for b, p in sorted(by_bucket.items())}
        if rnd == 0:
            eng.insert(pxs[min(pxs)], 0)
            eng.generate_step(1)
            eng.release(0)
            lacking = eng.land_plans()
            if lacking:
                raise arch.ProgramFault(f"{lacking} stitched plans never landed")
    for b, ns in sorted(pages.items()):
        kv = pxs[b].kv
        for n in sorted(ns):
            length = min(n * page, b)
            eng.insert(Prefix(lengths=np.array([length], np.int32),
                              first_tokens=np.zeros(1, np.int64), bucket=b,
                              kv=kv), 0)
            eng.release(0)
    for s in range(eng.cfg.batch):
        eng.insert(pxs[min(pxs)], s)
    eng.generate_step(1)
    for s in range(eng.cfg.batch):
        eng.release(s)
    rep = eng.report()
    if eng.stitch_service is not None:
        for which in ("decode", "prefill"):
            r = rep[which]
            bad = {k: v["status"] for k, v in r["plans"].items()
                   if v["status"] != "hit"}
            if bad or r["errors"] or r["service_error"]:
                raise arch.ProgramFault(f"{which}: plans {bad}, errors "
                                   f"{r['errors']}, {r['service_error']}")
    return time.perf_counter() - t0


class _Loop:
    """The admission loop over the engine's three stages."""

    def __init__(self, eng, win: Window, annotate):
        self.eng = eng
        self.win = win
        self.ann = annotate
        self.free = list(range(eng.cfg.batch - 1, -1, -1))
        self.active: dict[int, loadgen.Request] = {}
        self.finished: list[loadgen.Request] = []

    def admit(self, req: loadgen.Request) -> None:
        slot = self.free.pop()
        req.admitted = time.perf_counter()
        with self.ann("prefill"):
            px = self.eng.prefill(req.prompt, rid=req.rid)
        t = time.perf_counter()
        self.win.prefill_calls.append((req.admitted, t, px.bucket))
        req.tokens.append(int(px.first_tokens[0]))
        req.times.append(t)
        with self.ann("insert"):
            self.eng.insert(px, slot)
        self.active[slot] = req
        self._maybe_finish(slot)

    def decode(self) -> None:
        t0 = time.perf_counter()
        with self.ann("decode"):
            toks = self.eng.generate_step(1)
        t = time.perf_counter()
        self.win.decode_calls.append((t0, t, len(self.active)))
        for slot in list(self.active):
            req = self.active[slot]
            req.tokens.append(int(toks[slot, 0]))
            req.times.append(t)
            self._maybe_finish(slot)

    def _maybe_finish(self, slot: int) -> None:
        req = self.active[slot]
        if len(req.tokens) >= req.out_len:
            with self.ann("release"):
                self.eng.release(slot)
            del self.active[slot]
            self.free.append(slot)
            self.finished.append(req)


def run_open(eng, win: Window, reqs: list, annotate, drain_s: float,
             prof) -> list:
    """Requests arrive at ``t_open + arrival``; after the window closes the
    loop admits nothing new and serves what arrived until it finishes (or
    ``drain_s`` passes).  Returns the finished requests."""
    loop = _Loop(eng, win, annotate)
    queue: list[loadgen.Request] = []
    nxt = 0
    t_open = win.t_open
    while True:
        now = time.perf_counter()
        prof.tick(now)
        while nxt < len(reqs) and t_open + reqs[nxt].arrival <= now:
            queue.append(reqs[nxt])
            nxt += 1
        with annotate("admit"):
            while queue and loop.free:
                loop.admit(queue.pop(0))
        if loop.active:
            loop.decode()
        elif nxt < len(reqs):
            time.sleep(max(0.0, t_open + reqs[nxt].arrival - time.perf_counter()))
        elif not queue:
            break
        if now > win.t_close + drain_s:
            break
    for slot in list(loop.active):      # unfinished after the drain: failed
        eng.release(slot)
    return loop.finished


def run_closed(eng, win: Window, reqs: list, annotate, prof) -> list:
    """Every slot holds a request; a finished one is replaced at once by the
    next from ``reqs``.  The window closes after ``seconds``; requests in
    flight then are cut off, released and not counted."""
    loop = _Loop(eng, win, annotate)
    nxt = 0
    while (now := time.perf_counter()) < win.t_close:
        prof.tick(now)
        with annotate("admit"):
            while loop.free:
                if nxt >= len(reqs):
                    raise ValueError("closed loop ran out of requests")
                loop.admit(reqs[nxt])
                nxt += 1
        loop.decode()
    for slot in list(loop.active):      # cut off at the close
        eng.release(slot)
    return [r for r in loop.finished if r.times[-1] <= win.t_close]


def measure(eng, win: Window, reqs: list, check: dict, trace_dir=None,
            t_start: float | None = None) -> list:
    """The measured window: opens it, serves ``reqs`` for ``win.seconds``
    (the last ``TRACE_S`` of them under the profiler when ``trace_dir`` is
    given) and fills ``win``.  Returns the finished requests."""
    gc.collect()
    counter = _CompileCounter()
    annotate = _annotator(trace_dir is not None)
    win.t_open = time.perf_counter()
    if t_start is not None:
        win.setup_s = win.t_open - t_start
    win.t_close = win.t_open + win.seconds
    prof = _Profiler(trace_dir, win)
    if win.mix["loop"] == "open":
        finished = run_open(eng, win, reqs, annotate, check["drain_s"], prof)
        win.requests = reqs
    else:
        finished = run_closed(eng, win, reqs, annotate, prof)
        win.requests = finished
    prof.close()
    win.compiles = counter.stop()
    win.served = [r for r in reqs if r.times]
    return finished


class _Profiler:
    """The JAX profiler from ``TRACE_S`` seconds before the window closes
    (or from its opening, when shorter) until the loop ends: a trace of a
    few seconds holds every op and stays small enough to read back inside
    a run.  It stops after an open loop's drain, so that writing the trace
    stalls no request."""

    def __init__(self, trace_dir, win: Window):
        self.dir = trace_dir
        self.start_at = win.t_close - min(win.seconds, TRACE_S)
        self.on = False
        self.done = trace_dir is None

    def tick(self, now: float) -> None:
        import jax

        if self.done:
            return
        if not self.on and now >= self.start_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # harness annotations suffice
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            self.on = True

    def close(self) -> None:
        import jax

        if self.on:
            jax.profiler.stop_trace()
        self.on, self.done = False, True


def setup(workload: dict, cfg: dict, mix: dict, seed: int, seconds: float):
    """(engine, weights, requests, land seconds) for one run."""
    import jax

    import weights as wmod

    arch.load(cfg).check_traffic(cfg, mix, workload["engine"]["max_len"])
    w = wmod.make(cfg, seed)
    jax.block_until_ready(w)
    eng = build(workload, cfg, w)
    reqs = loadgen.generate(mix, seed, seconds, cfg["vocab_size"])
    return eng, w, reqs, land(eng, reqs)


def run(workload: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        trace_dir, t_start: float, check: dict, *, faults=None):
    """One run of a serving cell.  Returns (window, correct, compared,
    attempted, failed, memory_peak_bytes).  ``compared`` maps each number
    compared to (value, limit).  ``faults`` (tests only) breaks the timed
    path: a callable that receives the engine after set-up."""
    import correctness

    win = Window(workload=workload, cfg=cfg, mix=mix, seconds=seconds)
    eng, w, reqs, win.land_s = setup(workload, cfg, mix, seed, seconds)
    if faults is not None:
        faults(eng)
    finished = measure(eng, win, reqs, check, trace_dir, t_start)
    done = {r.rid for r in finished}
    failed = sum(1 for r in win.requests if r.rid not in done)
    mem = _memory_peak()

    # the check runs once the program's state is gone
    del eng
    gc.collect()
    picked = correctness.sample(finished, seed, check["sample_requests"],
                                check["sample_tokens"])
    gap, npos = correctness.widest_gap(w, cfg, picked)
    compared = {"widest_logit_gap": (gap, check["gap_limit"]),
                "failed_requests": (failed, 0)}
    correct = bool(picked) and gap <= check["gap_limit"] and failed == 0
    win.checked = {"requests": len(picked), "positions": npos}
    return win, correct, compared, len(win.requests), failed, mem


def _memory_peak() -> int | None:
    """Peak bytes in use on the fullest local device, where reported."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class _CompileCounter:
    """Counts lowerings (new jit specializations, cached or not) between
    construction and ``stop``."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        self.on = True

        def listen(event, duration, **kw):
            if self.on and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                self.n += 1

        monitoring.register_event_duration_secs_listener(listen)

    def stop(self) -> int:
        self.on = False
        return self.n


def _annotator(on: bool):
    import contextlib

    import jax

    if on:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()
