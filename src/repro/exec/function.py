"""``stitch()`` — a jit-like transform executing through the fusion pipeline.

This module is the single execution layer the whole repo dispatches
through: before it existed, the trace / compile-or-fallback /
miss-then-upgrade-polling / shard_map-dispatch / shape-drift-fallback logic
lived as three divergent copies inside the stitched train step, the serving
engine, and the packed optimizer.  ``stitch()`` owns all of it:

* **Tracing** is pytree-aware: positional args, kwargs, and arbitrarily
  nested containers flatten at the boundary and unflatten on return, so any
  ``fn(pytree...) -> pytree`` round-trips.  ``static_argnums`` values are
  baked into the trace (jit-like: they must be hashable) and a *changed*
  static value retraces into a new specialization.
* **Compilation** goes through :class:`repro.cache.CompilationService`
  miss-then-upgrade: the first call returns the instantly-available
  XLA-mode fallback artifact while the full stitch pipeline (pattern
  generation, ILP, tuning) runs on a background thread; every later call
  polls the cache and upgrades mid-flight.  A background compile that
  *fails* is surfaced once as a :class:`RuntimeWarning` and in
  :meth:`StitchedFunction.report` — the fallback keeps serving, and the
  doomed compile is not re-kicked.
* **Dispatch** is always one jitted program per specialization: the
  artifact's group walk (``CompiledGraph.__call__``) traced under
  ``jax.jit``, so one stitched call is one launch.  Pallas groups stay the
  plan's Pallas kernels and fused-jnp groups become XLA fusions inside the
  program.  The program is built when the artifact is first served,
  memoized per artifact, and rebuilt once when an upgrade swaps it.  With
  ``mesh=`` the only difference is that the body is wrapped in
  ``shard_map``: the function is traced at *shard-local* shapes
  (collectives inside ``fn`` trace via ``axis_env`` into executable CUSTOM
  fusion partitions) and compiled under a mesh+spec placement cache key.
* **Fallback**: trace failure (of ``fn``, or of the walk under ``jax.jit``)
  or a per-call shape/structure drift serves that call through
  ``jax.jit(fn)`` (sharded when specs resolve), counted in
  :meth:`StitchedFunction.report`.

Modes: ``"stitch"`` executes through the artifact; ``"shadow"`` compiles
and reports but serves jit (the serving engine's ``stitch_execute=False``);
``"offline"`` compiles synchronously at trace time (no background thread);
``"jit"`` disables stitching entirely and is pure (sharded) jit dispatch.
"""

from __future__ import annotations

import hashlib
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.obs import timer as _ktimer

__all__ = ["StitchedFunction", "shard_wrap", "stitch", "tree_avals"]

MODES = ("stitch", "shadow", "offline", "jit")

_UNSEEN = object()   # jit-memo sentinel: signature not yet classified


def tree_avals(tree) -> tuple:
    """(shape, dtype) per leaf — the signature every drift/eligibility
    check in the repo compares; Python scalars get a scalar stand-in."""
    return tuple(
        (getattr(x, "shape", ()), str(getattr(x, "dtype", type(x).__name__)))
        for x in jax.tree_util.tree_leaves(tree))


_avals = tree_avals


def _resolve(spec_or_fn, args):
    """Partition specs may be given as values or as ``callable(*args)`` so
    they can depend on the concrete pytree structure (e.g. a KV cache whose
    slot specs are leaf-name based).  ``None`` means "this signature is not
    shardable — use the plain jit path"."""
    if spec_or_fn is None:
        return None
    if callable(spec_or_fn) and not isinstance(spec_or_fn, P):
        return spec_or_fn(*args)
    return spec_or_fn


def _is_spec_leaf(x) -> bool:
    return x is None or isinstance(x, P)


def _local_avals(args, in_specs, mesh: Mesh):
    """Shard-local ShapeDtypeStruct tree for ``args`` under (possibly
    pytree-prefix) ``in_specs`` — the shapes a ``shard_map`` body sees and
    therefore the shapes the stitch pipeline traces per-shard graphs at."""
    from repro.models.sharding import local_shape

    spec_leaves, spec_def = jax.tree_util.tree_flatten(
        in_specs, is_leaf=_is_spec_leaf)
    subtrees = spec_def.flatten_up_to(args)
    mapped = []
    for spec, sub in zip(spec_leaves, subtrees):
        spec = spec if spec is not None else P()
        mapped.append(jax.tree.map(
            lambda l, _s=spec: jax.ShapeDtypeStruct(
                local_shape(tuple(l.shape), _s, mesh), l.dtype), sub))
    return jax.tree_util.tree_unflatten(spec_def, mapped)


def shard_wrap(fn: Callable, mesh: Mesh, in_specs, out_specs,
               refresh_key: Callable[[], Any] | None = None) -> Callable:
    """Jit-memoized ``shard_map`` dispatch for a shard-local body.

    The wrapper is compiled once and reused; ``refresh_key`` (a zero-arg
    callable) identifies mutable state the body closes over — e.g. a
    compiled artifact that a background upgrade may swap — and a changed
    key rebuilds the wrapper so the new state is baked in.  This is the
    dispatch idiom every mesh-aware caller shares; keeping it here means no
    caller hand-writes shard_map construction."""
    state: dict[str, Any] = {"key": _UNSEEN, "fn": None}

    def dispatch(*args):
        key = refresh_key() if refresh_key is not None else None
        if state["fn"] is None or state["key"] is not key:
            state["fn"] = jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                check_vma=False))
            state["key"] = key
        return state["fn"](*args)

    return dispatch


class _Specialization:
    """One traced-and-compiled (graph, artifact) pair at fixed avals."""

    __slots__ = ("status", "graph", "names", "compiled", "out_tree",
                 "in_sig", "placement", "sig", "lookup_compiler",
                 "executable", "error", "warned", "sharded",
                 "sm_in_specs", "sm_out_specs", "program", "program_for",
                 "program_builds")

    def __init__(self):
        self.status: str | None = None
        self.graph = None
        self.names: list[str] | None = None
        self.compiled = None
        self.out_tree = None
        self.in_sig = None
        self.placement = ""
        self.sig = None
        self.lookup_compiler = None
        self.executable = False
        self.error: str | None = None
        self.warned = False
        self.sharded = False
        self.sm_in_specs = None
        self.sm_out_specs = None
        self.program = None          # the jitted walk of ``program_for``
        self.program_for = None
        self.program_builds = 0

    @property
    def ok(self) -> bool:
        return self.graph is not None and self.executable \
            and self.compiled is not None


class StitchedFunction:
    """The callable :func:`stitch` returns — see the module docstring.

    Observability compatible with the pre-refactor phases: ``status``,
    ``graph``, ``compiled``, ``placement`` expose the active
    specialization; :meth:`report` aggregates call counts, plan stats,
    cache hit rates, and any background-compile failure.
    """

    def __init__(self, fn: Callable, *, mode: str = "stitch", service=None,
                 mesh: Mesh | None = None, in_specs=None, out_specs=None,
                 donate_argnums=(), static_argnums=(),
                 eligibility_argnums=None, respecialize: int = 0,
                 placement: str = "", name: str | None = None):
        if mode not in MODES:
            raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
        self.fn = fn
        self.mode = mode
        self.name = name or getattr(fn, "__name__", "stitched")
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self.static_argnums = tuple(sorted(set(static_argnums)))
        self.donate_argnums = tuple(sorted(set(donate_argnums)))
        # respecialize=N: a new input signature (shape/structure drift)
        # traces a NEW specialization instead of falling back to jit —
        # jit-like shape polymorphism through the fusion pipeline, bounded
        # at N live specializations (LRU eviction).  The serving engine's
        # bucketed prefill dispatch is the canonical user: each pow2 bucket
        # lands its own placement-keyed plan.
        self.respecialize = int(respecialize)
        if self.respecialize and mesh is not None:
            raise ValueError("respecialize is not supported together with "
                             "mesh dispatch")
        # args whose avals the per-call drift check covers (None = all).
        # Callers with an operand that is fixed for the function's lifetime
        # (e.g. the serving engine's params) exclude it so the hot-path
        # check stays O(small); excluded args are still traced normally.
        self.eligibility_argnums = (
            tuple(sorted(set(eligibility_argnums)))
            if eligibility_argnums is not None else None)
        if self.mesh is not None and self.static_argnums:
            raise ValueError("static_argnums is not supported together with "
                             "mesh dispatch")
        if self.mesh is not None and (in_specs is None or out_specs is None):
            raise ValueError("mesh dispatch requires in_specs and out_specs")
        if set(self.static_argnums) & set(self.donate_argnums):
            raise ValueError("an argument cannot be both static and donated")
        if mode != "jit" and service is None:
            from repro.cache import CompilationService
            service = CompilationService()
        self.service = service
        self.in_specs = in_specs
        self.out_specs = out_specs
        self._placement_override = placement
        self._specs: dict[Any, _Specialization] = {}
        self._active: _Specialization | None = None
        self._jit_plain = jax.jit(fn, static_argnums=self.static_argnums,
                                  donate_argnums=self.donate_argnums)
        self._jit_sharded: dict = {}     # (treedef, avals) -> jit(shard_map)
        # with respecialize, jit-served signatures get their OWN jit
        # instance in a same-cap LRU: evicting an entry drops its compiled
        # executable too, so jit/shadow modes are as bounded as stitch mode
        self._jit_lru: OrderedDict = OrderedDict()
        self.stitched_calls = 0          # served through the compiled artifact
        self.fallback_calls = 0          # drift / trace failure -> jit
        self.jit_calls = 0               # by-design jit ("jit"/"shadow" modes)
        self._span_name = f"exec.{self.name}"
        # path -> measured-wall-clock Histogram, populated only while the
        # opt-in kernel timer (repro.obs.timer) is enabled
        self._measured: dict[str, obs.Histogram] = {}

    # -- argument plumbing -----------------------------------------------------
    def _split(self, args):
        statics = tuple(args[i] for i in self.static_argnums if i < len(args))
        dyn = tuple(a for i, a in enumerate(args)
                    if i not in self.static_argnums)
        return statics, dyn

    def _bind(self, statics):
        if not self.static_argnums:
            return self.fn
        at = dict(zip(self.static_argnums, statics))
        n_static = len(statics)

        def bound(*dyn, **kwargs):
            merged, di = [], iter(dyn)
            for i in range(len(dyn) + n_static):
                merged.append(at[i] if i in at else next(di))
            return self.fn(*merged, **kwargs)

        return bound

    def _in_sig(self, dyn, kwargs):
        if self.eligibility_argnums is not None:
            sel, di = [], 0
            for i in range(len(dyn) + len(self.static_argnums)):
                if i in self.static_argnums:
                    continue
                if i in self.eligibility_argnums:
                    sel.append(dyn[di])
                di += 1
            dyn = tuple(sel)
        return (jax.tree_util.tree_structure((dyn, kwargs)),
                _avals((dyn, kwargs)))

    # -- tracing ---------------------------------------------------------------
    def _trace(self, statics, dyn, kwargs) -> _Specialization:
        from repro.cache.signature import compute_signature, placement_key
        from repro.core.trace import trace_to_graph

        sp = _Specialization()
        sp.in_sig = self._in_sig(dyn, kwargs)
        sp.placement = self._placement_override
        if self.respecialize:
            # per-signature placement: each specialization (e.g. each pow2
            # prefill bucket) gets its own cache entry/plan even when the
            # bucket policy would coarsen their shapes together
            digest = hashlib.sha1(repr(sp.in_sig).encode()).hexdigest()[:8]
            base = self._placement_override or self.name
            sp.placement = f"{base}@{digest}"
        bound = self._bind(statics)
        tsp = obs.span("exec.trace", cat="exec", fn=self.name, mode=self.mode)
        tsp.__enter__()
        try:
            axis_env = None
            targs = ((dyn, kwargs),)

            def run_fn(packed):
                return bound(*packed[0], **packed[1])

            if self.mesh is not None:
                in_specs = _resolve(self.in_specs, dyn)
                if in_specs is not None:
                    if kwargs:
                        raise ValueError("kwargs unsupported on the sharded "
                                         "stitched path")
                    sp.sharded = True
                    sp.sm_in_specs = in_specs
                    sp.sm_out_specs = _resolve(self.out_specs, dyn)
                    sp.placement = placement_key(self.mesh, in_specs)
                    axis_env = [(a, self.mesh.shape[a])
                                for a in self.mesh.axis_names]
                    targs = tuple(_local_avals(dyn, in_specs, self.mesh))
                    run_fn = bound
            sp.graph, sp.names = trace_to_graph(
                run_fn, *targs, name=self.name, axis_env=axis_env)
            _, out_shape = jax.make_jaxpr(
                run_fn, axis_env=axis_env, return_shape=True)(*targs)
            sp.out_tree = jax.tree_util.tree_structure(out_shape)
            # duplicated outputs collapse in the graph: not executable, but
            # the plan still powers reporting / cache warmth
            sp.executable = sp.out_tree.num_leaves == len(sp.graph.outputs)
            if self.mode == "offline":
                sp.compiled = self.service.compile(
                    sp.graph, placement=sp.placement)
                sp.status = "compiled"
            else:
                sp.compiled, sp.status = self.service.compile_or_fallback(
                    sp.graph, placement=sp.placement)
            sp.sig = compute_signature(sp.graph)
            sp.lookup_compiler = self.service.compiler("stitch", sp.placement)
        except Exception as e:              # noqa: BLE001 — degrade to jit
            sp.status = "error"
            sp.error = f"{type(e).__name__}: {e}"
            sp.graph = None
            sp.compiled = None
            sp.executable = False
        finally:
            tsp.set(status=sp.status, placement=sp.placement)
            tsp.__exit__(None, None, None)
        return sp

    def _spec_key(self, statics, dyn, kwargs):
        if not self.respecialize:
            return statics
        return (statics, self._in_sig(dyn, kwargs))

    def _get(self, statics, dyn, kwargs) -> _Specialization:
        key = self._spec_key(statics, dyn, kwargs)
        sp = self._specs.get(key)
        if sp is None:
            sp = self._trace(statics, dyn, kwargs)
            self._specs[key] = sp
        elif self.respecialize:
            self._specs[key] = self._specs.pop(key)      # LRU touch
        while self.respecialize and len(self._specs) > self.respecialize:
            evicted = next(iter(self._specs))
            if evicted == key:                           # never evict current
                break
            del self._specs[evicted]
        self._active = sp
        return sp

    # -- miss-then-upgrade polling ---------------------------------------------
    def _poll(self, sp: _Specialization) -> None:
        if sp.status not in ("miss", "pending"):
            return
        svc = self.service
        hit = svc.cache.lookup(sp.graph, sp.lookup_compiler, sig=sp.sig,
                               count=False)
        if hit is not None:
            sp.compiled = hit
            sp.status = "hit"
            # the acceptance-critical marker: this call flips from the XLA
            # fallback to the stitched artifact mid-flight
            obs.event("exec.upgrade", cat="exec", fn=self.name,
                      placement=sp.placement,
                      n_kernels=hit.stats.n_kernels,
                      modeled_time_s=hit.stats.modeled_time)
            return
        err = svc.error_for(sp.sig, sp.placement)
        if err is not None:
            # the background stitch compile died: keep serving the fallback
            # artifact, stop re-kicking the doomed compile, and say so once
            sp.status = "failed"
            sp.error = err
            if not sp.warned:
                sp.warned = True
                warnings.warn(
                    f"background stitch compile for {self.name!r} failed; "
                    f"serving the fallback artifact permanently: {err}",
                    RuntimeWarning, stacklevel=4)
            return
        # re-kick if the background compile was deferred (worker cap): a
        # long-lived function must not serve the fallback forever
        svc.ensure_compiling(sp.graph, sig=sp.sig, placement=sp.placement)

    def poll_upgrade(self) -> None:
        """Poll the active specialization's background upgrade (also done
        automatically on every call)."""
        if self._active is not None and self.mode not in ("jit", "offline"):
            self._poll(self._active)

    # -- execution -------------------------------------------------------------
    def _build(self, sp: _Specialization, dyn, kwargs) -> bool:
        """Make ``sp.program`` the one jitted program of the current
        artifact: its group walk traced under ``jax.jit`` (inside
        ``shard_map`` when sharded).  Memoized per artifact, so an upgrade
        swap rebuilds it once.  A walk that fails to trace marks the
        specialization an error, served by jit from then on; returns False
        then."""
        if sp.program_for is sp.compiled:
            return True
        compiled, graph = sp.compiled, sp.graph
        names, out_tree = sp.names, sp.out_tree

        def body(*args, **kw):
            outs = compiled(dict(zip(
                names, jax.tree_util.tree_leaves((args, kw)))))
            flat = [outs[o] for o in graph.outputs]
            return jax.tree_util.tree_unflatten(out_tree, flat)

        if sp.sharded:
            body = jax.shard_map(body, mesh=self.mesh,
                                 in_specs=sp.sm_in_specs,
                                 out_specs=sp.sm_out_specs, check_vma=False)
        program = jax.jit(body)
        try:
            # traces the walk now (the call reuses this trace), so that a
            # walk jit cannot trace degrades like a failed trace of ``fn``
            program.trace(*dyn, **kwargs)
        except Exception as e:              # noqa: BLE001 — degrade to jit
            sp.status = "error"
            sp.error = f"{type(e).__name__}: {e}"
            sp.executable = False
            return False
        sp.program, sp.program_for = program, compiled
        sp.program_builds += 1
        return True

    def _run(self, sp: _Specialization, dyn, kwargs, wsp=obs.NULL_SPAN):
        """Dispatch the specialization's program: the whole walk is one
        launch, and its constants are part of the program."""
        wsp.set(launches=1, uploads=0)
        return sp.program(*dyn, **kwargs)

    def _jit_call(self, args, dyn, kwargs):
        if self.respecialize and self.mesh is None:
            key = self._in_sig(dyn, kwargs)
            fn = self._jit_lru.get(key)
            if fn is None:
                fn = jax.jit(self.fn, static_argnums=self.static_argnums,
                             donate_argnums=self.donate_argnums)
            else:
                self._jit_lru.pop(key)               # LRU touch
            self._jit_lru[key] = fn
            while len(self._jit_lru) > self.respecialize:
                self._jit_lru.popitem(last=False)
            return fn(*args, **kwargs)
        if self.mesh is not None and not kwargs:
            # signature-keyed memo holds the shardable/unshardable decision
            # too, so the spec callable (a pytree walk) runs once per
            # signature, not once per call.  The key is the eligibility
            # signature: args excluded there are lifetime-fixed by contract
            # (and the inner jit re-specializes on concrete shapes anyway).
            key = self._in_sig(dyn, {})
            fn = self._jit_sharded.get(key, _UNSEEN)
            if fn is _UNSEEN:
                if len(self._jit_sharded) >= 64:   # long-lived servers with
                    self._jit_sharded.clear()      # ever-new extra shapes
                specs = _resolve(self.in_specs, dyn)
                fn = None if specs is None else jax.jit(jax.shard_map(
                    self.fn, mesh=self.mesh, in_specs=specs,
                    out_specs=_resolve(self.out_specs, dyn),
                    check_vma=False))
                self._jit_sharded[key] = fn
            if fn is not None:
                return fn(*dyn)
        return self._jit_plain(*args, **kwargs)

    def _donate(self, args, out) -> None:
        """Stitched analogue of the jit path's ``donate_argnums``: free the
        consumed operands once the artifact has been dispatched.  Leaves
        aliased by the outputs (a passthrough input) are kept — jit's
        donation aliases them safely, so deleting would corrupt the
        result."""
        keep = {id(l) for l in jax.tree_util.tree_leaves(out)
                if isinstance(l, jax.Array)}
        for i in self.donate_argnums:
            if i < len(args):
                for leaf in jax.tree_util.tree_leaves(args[i]):
                    if (isinstance(leaf, jax.Array) and id(leaf) not in keep
                            and not leaf.is_deleted()):
                        leaf.delete()

    def _timed(self, path: str, fn, *call_args):
        """The opt-in measured timer around one served call; timer off (the
        default) is a one-attribute-read passthrough."""
        if not _ktimer.enabled:
            return fn(*call_args)
        t0 = time.perf_counter()
        out = fn(*call_args)
        # bracket device execution, not just async dispatch
        jax.block_until_ready(out)
        self._record_measured(path, time.perf_counter() - t0)
        return out

    def _record_measured(self, path: str, measured_s: float) -> None:
        h = self._measured.get(path)
        if h is None:
            h = self._measured[path] = obs.Histogram()
        h.observe(measured_s)
        sp = self._active
        modeled = None
        if path == "stitched" and sp is not None and sp.compiled is not None:
            modeled = sp.compiled.stats.modeled_time
        _ktimer.record(self.name, path, measured_s, modeled_s=modeled,
                       placement=sp.placement if sp is not None else "")

    def _route(self, args, kwargs):
        """(path, specialization, dynamic args) for one call: the drift
        check and the upgrade poll, counted per path."""
        statics, dyn = self._split(args)
        if self.mode == "jit":
            self.jit_calls += 1
            return "jit", None, dyn
        sp = self._get(statics, dyn, kwargs)
        if not sp.ok or sp.in_sig != self._in_sig(dyn, kwargs):
            self.fallback_calls += 1
            return "fallback", None, dyn
        if self.mode != "offline":
            self._poll(sp)
        if self.mode == "shadow":
            self.jit_calls += 1
            return "jit", None, dyn
        if not self._build(sp, dyn, kwargs):
            self.fallback_calls += 1
            return "fallback", None, dyn
        return "stitched", sp, dyn

    def __call__(self, *args, **kwargs):
        # spans: exec.<name> holds exec.check (routing, and the program's
        # build when the artifact is new) and, on the stitched path,
        # exec.walk (the program's one dispatch)
        with obs.span(self._span_name, cat="exec") as osp:
            with obs.span("exec.check", cat="exec"):
                path, sp, dyn = self._route(args, kwargs)
            osp.set(path=path)
            if path != "stitched":
                return self._timed(path, self._jit_call, args, dyn, kwargs)
            with obs.span("exec.walk", cat="exec") as wsp:
                out = self._timed(path, self._run, sp, dyn, kwargs, wsp)
            self.stitched_calls += 1
            if self.donate_argnums:
                self._donate(args, out)
            return out

    # -- introspection ---------------------------------------------------------
    def warmup(self, *args, **kwargs) -> str | None:
        """Trace and compile (or fetch the fallback) at these example
        arguments — ShapeDtypeStructs are fine — without executing.
        Returns the resulting status."""
        statics, dyn = self._split(args)
        if self.mode == "jit":
            return None
        return self._get(statics, dyn, kwargs).status

    def eligible(self, *args, **kwargs) -> bool:
        """True when a call with these arguments would execute through the
        compiled artifact (already traced, executable, signature match)."""
        statics, dyn = self._split(args)
        sp = self._specs.get(self._spec_key(statics, dyn, kwargs))
        return (sp is not None and sp.ok
                and sp.in_sig == self._in_sig(dyn, kwargs))

    @property
    def ok(self) -> bool:
        return self._active is not None and self._active.ok

    @property
    def status(self) -> str | None:
        return self._active.status if self._active is not None else None

    @property
    def graph(self):
        return self._active.graph if self._active is not None else None

    @property
    def compiled(self):
        return self._active.compiled if self._active is not None else None

    @property
    def placement(self) -> str:
        return self._active.placement if self._active is not None else ""

    def plan_stats(self) -> dict | None:
        if self._active is None or self._active.compiled is None:
            return None
        return self._plan_stats(self._active)

    @staticmethod
    def _plan_stats(sp: _Specialization) -> dict | None:
        if sp.compiled is None:
            return None
        s = sp.compiled.stats
        return {"mode": s.mode, "n_kernels": s.n_kernels, "n_ops": s.n_ops,
                "pallas_groups": s.pallas_groups,
                "packs": getattr(s, "packs", 0),
                "packed_subgraphs": getattr(s, "packed_subgraphs", 0),
                "modeled_time": s.modeled_time,
                "cache_status": s.cache_status,
                "verify": getattr(s, "verify", None),
                # one stitched call is one program: one launch, no upload;
                # the walk traced into it issued ``walk_launches``
                "launches": 1,
                "uploads": 0,
                "walk_launches": sp.compiled.launches,
                "program_builds": sp.program_builds}

    def land_plans(self, timeout: float | None = None) -> int:
        """Join background compiles and poll EVERY specialization's upgrade
        (``_poll`` only tracks the active one) until no compile is in
        flight; returns how many specializations still lack a stitched
        plan.  Benches and tests use this to read deterministic per-bucket
        kernel counts out of :meth:`report`."""
        if self.mode in ("jit", "offline") or self.service is None:
            return 0
        for _ in range(1 + len(self._specs)):
            pending = 0
            for sp in self._specs.values():
                self._poll(sp)
                if sp.status in ("miss", "pending"):
                    pending += 1
            if not pending:
                break
            self.service.wait(timeout)
        return sum(sp.status in ("miss", "pending", "failed", "error")
                   for sp in self._specs.values())

    def report(self) -> dict:
        """Call routing, plan + kernel stats, cache hit rates, every
        background-compile failure, and measured kernel timing — one dict
        conforming to :data:`repro.obs.EXEC_REPORT_SCHEMA` (see
        :mod:`repro.obs.report` for the documented key table)."""
        out: dict[str, Any] = {
            "schema": obs.EXEC_REPORT_SCHEMA,
            "name": self.name,
            "status": self.status,
            "mode": self.mode,
            "calls": {"stitched": self.stitched_calls,
                      "fallback": self.fallback_calls,
                      "jit": self.jit_calls},
            # compatibility aliases — prefer ``calls``
            "stitched_calls": self.stitched_calls,
            "fallback_calls": self.fallback_calls,
            "jit_calls": self.jit_calls,
            "specializations": len(self._specs),
            "specialization_cap": self.respecialize or None,
            "jit_specializations": len(self._jit_lru),
            "placement": (self._active.placement
                          if self._active is not None else ""),
            "plan": self.plan_stats(),
            # per-specialization plans (placement-keyed) — with
            # ``respecialize`` each shape bucket reports its own
            "plans": {sp.placement: {"status": sp.status,
                                     "plan": self._plan_stats(sp)}
                      for sp in self._specs.values()},
            "error": (self._active.error
                      if self._active is not None else None),
            "errors": {f"exec/{self.name}/{sp.placement}": sp.error
                       for sp in self._specs.values()
                       if sp.status == "error"},
            # structured StitchInfeasible records from tuning: why chosen
            # patterns degraded to fused-jnp (see core.tuner._diagnostic)
            "diagnostics": (list(self._active.compiled.stats.diagnostics)
                            if self._active is not None
                            and self._active.compiled is not None else []),
            "cache": None,
            "service_error": None,
            "measured": ({p: h.summary()
                          for p, h in sorted(self._measured.items())}
                         if self._measured else None),
        }
        if self.service is not None:
            out["cache"] = self.service.cache.report()
            out["service_error"] = self.service.last_error
            out["errors"].update(self.service.error_report())
        return out

    def wait(self, timeout: float | None = None) -> None:
        """Join in-flight background compiles (tests / orderly shutdown)."""
        if self.service is not None:
            self.service.wait(timeout)


def stitch(fn: Callable, *, mode: str = "stitch", service=None,
           mesh: Mesh | None = None, in_specs=None, out_specs=None,
           donate_argnums=(), static_argnums=(), eligibility_argnums=None,
           respecialize: int = 0, placement: str = "",
           name: str | None = None) -> StitchedFunction:
    """Wrap ``fn`` for execution through the FusionStitching pipeline —
    the jit-like public entry point of the repo.

    Args:
      fn: any JAX-traceable function of pytree args/kwargs.
      mode: ``"stitch"`` (execute stitched, miss-then-upgrade),
        ``"shadow"`` (compile + report, serve jit), ``"offline"``
        (blocking compile at first call), ``"jit"`` (no stitching).
      service: :class:`repro.cache.CompilationService`; a default
        (in-memory cache) is created when omitted and mode needs one.
      mesh / in_specs / out_specs: ``shard_map`` dispatch — specs may be
        values or ``callable(*args)`` returning specs (``None`` =
        unshardable signature, plain jit).  Collectives inside ``fn``
        trace via ``axis_env``.  Plans cache under a mesh+spec placement.
      donate_argnums: consumed args: donated on the jit path, deleted
        after dispatch on the stitched path.
      static_argnums: hashable args baked into the trace; a new value
        retraces into a new specialization.
      eligibility_argnums: restrict the per-call shape-drift check to these
        args (default all) — for operands fixed over the function's
        lifetime, keeping the hot-path check cheap.
      respecialize: N > 0 makes a drifted input signature trace a NEW
        specialization (own graph, own placement-keyed plan) instead of
        serving through jit — jit-like shape polymorphism, LRU-bounded at N
        live specializations.  The serving engine routes its pow2-bucketed
        prefills through this.  Incompatible with ``mesh``.
      placement: explicit cache-placement override for bodies that run
        inside someone else's ``shard_map`` (e.g. the packed optimizer).
      name: graph name for dumps, cache records, and warnings.

    Returns a :class:`StitchedFunction`.
    """
    return StitchedFunction(
        fn, mode=mode, service=service, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, donate_argnums=donate_argnums,
        static_argnums=static_argnums, eligibility_argnums=eligibility_argnums,
        respecialize=respecialize, placement=placement, name=name)
