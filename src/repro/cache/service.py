"""StitchCache facade + compilation service (miss-then-upgrade).

:class:`StitchCache` binds the three lower pieces together — signatures
(:mod:`.signature`), bucketing/eviction (:mod:`.policy`), and the two-tier
store (:mod:`.store`) — behind two operations:

* ``lookup(g, compiler)``  — signature the graph, probe the store, and on a
  hit *replay* the record: rebuild executable groups on the new graph
  (canonical indices -> this graph's node names), re-instantiating stitched
  Pallas callables from the recorded ``(row_block, scratch)`` choice.  The
  expensive head of compilation — pattern generation, ILP solving, template
  enumeration — is skipped entirely.
* ``insert(g, compiled)``  — extract a :class:`PlanRecord` in canonical
  coordinates from a freshly compiled graph and write it through both tiers.

:class:`CompilationService` is the serving-path wrapper: ``compile_or_
fallback`` answers *immediately* — with the replayed stitched executable on
a hit, or with a cheap unfused/XLA-mode executable on a miss — while a
background thread runs the full stitch pipeline and populates the cache, so
the *next* request for the same (graph, bucket) upgrades to stitched
kernels.  Tail latency never pays the tuner's cost.
"""

from __future__ import annotations

import copy
import dataclasses
import threading
import time

from repro import obs
from repro.core.compiler import CompiledGraph, FusionStats, StitchCompiler, _Group
from repro.core.cost import HardwareModel, TPU_V5E
from repro.core.ir import Graph
from repro.core.pattern import FusionPattern, PackPattern
from repro.core.tuner import grid_row_block

from .policy import BucketPolicy, BucketStats, EvictionPolicy
from .signature import GraphSignature, compute_signature, config_key
from .store import DiskStore, GroupRecord, MemoryStore, PlanRecord, TwoTierStore

__all__ = ["StitchCache", "CompilationService", "extract_record", "replay_record"]


def extract_record(
    g: Graph,
    sig: GraphSignature,
    compiled: CompiledGraph,
    bucket_key: str,
    hw: str,
    solve_seconds: float = 0.0,
    placement: str = "",
    config: str = "",
) -> PlanRecord:
    """Freeze a compiled plan into canonical coordinates."""
    idx = sig.node_to_index
    groups = []
    for grp in compiled.groups:
        row_block = None
        scratch: tuple[int, ...] = ()
        if grp.tuned is not None:
            row_block = grid_row_block(grp.tuned.template)
            scratch = tuple(sorted(idx[n] for n in grp.tuned.template.scratch_ops))
        pack: tuple[tuple[int, ...], ...] = ()
        if getattr(grp, "pack", None):
            pack = tuple(sorted(
                tuple(sorted(idx[m] for m in gset)) for gset in grp.pack
            ))
        groups.append(
            GroupRecord(
                members=tuple(sorted(idx[m] for m in grp.members)),
                kind=grp.kind,
                row_block=row_block,
                scratch=scratch,
                pack=pack,
            )
        )
    ilp = compiled.stats.ilp
    return PlanRecord(
        graph_key=sig.graph_key,
        bucket_key=bucket_key,
        shape_key=sig.shape_key,
        mode=compiled.stats.mode,
        hw=hw,
        n_nodes=len(sig.canon_order),
        groups=tuple(groups),
        objective=ilp.objective if ilp else 0.0,
        ilp_iterations=ilp.iterations if ilp else 0,
        solve_seconds=solve_seconds,
        placement=placement,
        config=config,
    )


def replay_record(
    g: Graph, sig: GraphSignature, rec: PlanRecord, compiler: StitchCompiler
) -> CompiledGraph | None:
    """Rebuild an executable from a record, skipping search/solve/tune.

    Returns None when the record cannot apply (node-count mismatch from a
    hash collision) — the caller falls back to a cold compile.  Pallas
    groups that fail to re-instantiate at this graph's concrete shapes
    (bucketed hit at a new length outside the kernel's feasible blocks)
    degrade to fused-jnp groups; numerics are unaffected.
    """
    if rec.n_nodes != len(sig.canon_order):
        return None
    names = sig.canon_order
    n = len(names)
    for gr in rec.groups:          # corrupt/hand-edited records: treat as miss
        flat_pack = tuple(i for gset in getattr(gr, "pack", ()) for i in gset)
        if any(not 0 <= i < n for i in gr.members + gr.scratch + flat_pack):
            return None
    stats = FusionStats(
        mode=compiler.mode,
        n_ops=len(g.compute_nodes()),
        n_kernels=0,
        cache_status="hit",
    )
    groups: list[_Group] = []
    covered: set[str] = set()
    diag_start = len(compiler.tuner.diagnostics)
    for gr in rec.groups:
        members = frozenset(names[i] for i in gr.members)
        covered |= members
        if gr.kind == "op" or len(members) == 1 and gr.kind != "pallas":
            groups.append(_Group(members, "op"))
            continue
        pack = tuple(
            frozenset(names[i] for i in gset)
            for gset in getattr(gr, "pack", ())
        ) or None
        if pack:
            try:
                p: FusionPattern = PackPattern(g, members, "cache",
                                               member_groups=pack)
            except ValueError:
                return None        # malformed pack provenance: treat as miss
            stats.packs += 1
            stats.packed_subgraphs += len(pack)
        else:
            p = FusionPattern(g, members, "cache")
        stats.pattern_classes[p.pattern_class] = (
            stats.pattern_classes.get(p.pattern_class, 0) + 1
        )
        tuned = None
        if gr.kind == "pallas" and compiler.use_pallas:
            tuned = compiler.tuner.instantiate(
                p,
                row_block=gr.row_block,
                scratch_names=[names[i] for i in gr.scratch],
            )
        if tuned is not None:
            groups.append(_Group(members, "pallas", tuned, pack))
            stats.pallas_groups += 1
            stats.scratch_requested += sum(compiler.cost.scratch_request(p).values())
            stats.scratch_allocated += tuned.scratch_plan.allocated
            if tuned.scratch_plan.allocated:
                stats.patterns_with_scratch += 1
        else:
            groups.append(_Group(members, "jnp", None, pack))
    # a record always covers every compute node of an isomorphic graph, but
    # degrade gracefully if it somehow doesn't
    for node in g.compute_nodes():
        if node.name not in covered:
            groups.append(_Group(frozenset([node.name]), "op"))
    stats.n_kernels = len(groups)
    stats.diagnostics = list(compiler.tuner.diagnostics[diag_start:])
    stats.modeled_time = compiler.modeled_time(g, [grp.members for grp in groups])
    return CompiledGraph(g, groups, stats)


class StitchCache:
    """Thread-safe two-tier fusion-plan cache with shape bucketing."""

    def __init__(
        self,
        directory: str | None = None,
        bucket_policy: BucketPolicy | None = None,
        eviction: EvictionPolicy | None = None,
    ):
        eviction = eviction or EvictionPolicy()
        self.bucket_policy = bucket_policy or BucketPolicy()
        disk = (
            DiskStore(directory, max_entries=eviction.disk_entries,
                      on_corrupt=self._note_corrupt)
            if directory is not None
            else None
        )
        self.store = TwoTierStore(MemoryStore(eviction.memory_entries), disk)
        self.stats = BucketStats()
        self._lock = threading.RLock()
        # keys whose replayed record failed static verification (warn once)
        self._verify_warned: set[tuple] = set()
        # Live-artifact memo: (id(graph), mode, hw, use_pallas) -> (graph,
        # artifact, bucket, node count at memo time).  Replay on a record rebuilds
        # Pallas callables (cheap but not free); recompiling the *same*
        # unmutated Graph object can skip even that.  The value holds a
        # strong ref to the graph so the id key cannot be recycled.
        self._live: "dict[tuple, tuple[Graph, CompiledGraph, str, int]]" = {}
        self._live_capacity = eviction.memory_entries

    # -- keys -----------------------------------------------------------------
    def key_for(self, sig: GraphSignature, mode: str = "stitch",
                hw: str = "", placement: str = "", config: str = "") -> tuple:
        # hw is part of the durable key: a plan tuned for one chip's launch
        # latency / on-chip budget must not shadow the other chip's optimum.
        # placement (mesh + PartitionSpecs, see signature.placement_key) is
        # too: a plan solved at one mesh's shard-local shapes never replays
        # at another mesh or at the single-device ("") placement.  config is
        # the GenConfig digest (signature.config_key): different
        # pattern-generation knobs legitimately produce different plans.
        return (sig.graph_key, sig.bucket_key(self.bucket_policy), mode, hw,
                placement, config)

    def signature_of(self, g: Graph) -> GraphSignature:
        return compute_signature(g)

    # -- operations -----------------------------------------------------------
    def lookup(
        self,
        g: Graph,
        compiler: StitchCompiler,
        sig: GraphSignature | None = None,
        count: bool = True,
    ) -> CompiledGraph | None:
        placement = getattr(compiler, "placement", "")
        cfg_key = config_key(getattr(compiler, "gen_cfg", None))
        live_key = (id(g), compiler.mode, compiler.hw.name,
                    compiler.use_pallas, placement, cfg_key)
        with self._lock:
            live = self._live.get(live_key)
        if live is not None and live[0] is g and live[3] == len(g.nodes):
            if count:
                with self._lock:
                    self.stats.record(live[2], hit=True, placement=placement)
            art = copy.copy(live[1])   # fresh stats: don't rewrite the miss's
            art.stats = dataclasses.replace(live[1].stats, cache_status="hit")
            return art
        sig = sig or compute_signature(g)
        key = self.key_for(sig, compiler.mode, compiler.hw.name, placement,
                           cfg_key)
        with self._lock:
            rec = self.store.get(key)
        if rec is not None and getattr(compiler, "verify", "plans") != "off":
            # static plan verification against the *live* graph: a stale,
            # corrupt, or hand-edited record is demoted to a miss here —
            # never instantiated — and the recompile overwrites it
            rec = self._verified(g, sig, rec, compiler, key)
        compiled = None
        if rec is not None:
            try:
                compiled = replay_record(g, sig, rec, compiler)
            except Exception:
                compiled = None            # unreplayable record == miss
            if compiled is not None:
                self._remember_live(g, compiled, compiler, key[1])
        if count:
            with self._lock:
                self.stats.record(key[1], hit=compiled is not None,
                                  placement=placement)
        return compiled

    def _note_corrupt(self, key: tuple) -> None:
        """DiskStore callback: count an unreadable record in bucket stats."""
        with self._lock:
            self.stats.record_corrupt(key[1])

    def _verified(self, g: Graph, sig: GraphSignature, rec: PlanRecord,
                  compiler, key: tuple) -> PlanRecord | None:
        from repro.analysis import errors, format_findings, verify_record

        budget = getattr(compiler, "gen_cfg", None)
        budget = budget.scratch_budget if budget is not None else None
        if budget is None:
            budget = compiler.hw.onchip_budget
        findings = verify_record(g, sig.canon_order, rec,
                                 scratch_budget=budget, cost=compiler.cost,
                                 reg_budget=compiler.cost.reg_budget)
        bad = errors(findings)
        if not bad:
            return rec
        with self._lock:
            self.stats.record_demoted(key[1])
            warn = key not in self._verify_warned
            self._verify_warned.add(key)
        if warn:
            import warnings

            warnings.warn(
                f"cached plan for graph {g.name!r} (bucket {key[1][:12]}) "
                f"failed static verification and was demoted to a miss:\n"
                f"{format_findings(bad, limit=5)}",
                RuntimeWarning, stacklevel=4)
        return None

    def _remember_live(self, g: Graph, compiled: CompiledGraph, compiler,
                       bucket: str) -> None:
        with self._lock:
            if len(self._live) >= self._live_capacity:
                self._live.clear()
            self._live[(id(g), compiler.mode, compiler.hw.name,
                        compiler.use_pallas,
                        getattr(compiler, "placement", ""),
                        config_key(getattr(compiler, "gen_cfg", None)))] = (
                g, compiled, bucket, len(g.nodes))

    def insert(
        self,
        g: Graph,
        compiled: CompiledGraph,
        sig: GraphSignature | None = None,
        solve_seconds: float = 0.0,
        compiler: StitchCompiler | None = None,
    ) -> PlanRecord:
        sig = sig or compute_signature(g)
        bucket = sig.bucket_key(self.bucket_policy)
        hw = compiler.hw.name if compiler is not None else ""
        placement = getattr(compiler, "placement", "") if compiler else ""
        cfg_key = (config_key(getattr(compiler, "gen_cfg", None))
                   if compiler is not None else config_key())
        rec = extract_record(g, sig, compiled, bucket, hw, solve_seconds,
                             placement=placement, config=cfg_key)
        with self._lock:
            self.store.put(rec)
        if compiler is not None:
            self._remember_live(g, compiled, compiler, bucket)
        return rec

    def report(self) -> dict:
        with self._lock:
            out = self.stats.as_dict()
            out["memory_entries"] = len(self.store.memory)
            out["memory_evictions"] = self.store.memory.evictions
            out["disk_put_errors"] = self.store.disk_put_errors
            if self.store.disk is not None:
                out["disk_entries"] = len(self.store.disk)
                out["disk_corrupt_reads"] = self.store.disk.corrupt_reads
        return out


class CompilationService:
    """Warm-start compilation frontend for the serving tier."""

    def __init__(
        self,
        cache: StitchCache | None = None,
        hw: HardwareModel = TPU_V5E,
        fallback_mode: str = "xla",
        gen_cfg=None,
        use_pallas: bool = True,
        max_background: int = 2,
        plan_budget: float | None = None,
    ):
        assert fallback_mode in ("off", "xla")
        self.cache = cache or StitchCache()
        self.hw = hw
        self.fallback_mode = fallback_mode
        self.gen_cfg = gen_cfg
        self.use_pallas = use_pallas
        self.max_background = max_background
        # wall-clock budget (seconds) for the fusion-plan ILP of every
        # compile this service spawns — see core.ilp's anytime mode; None
        # means solve to optimality
        self.plan_budget = plan_budget
        self._lock = threading.Lock()
        self._pending: set[tuple] = set()
        self._threads: list[threading.Thread] = []
        self.last_error: str | None = None   # last background-compile failure
        self.errors: dict[tuple, str] = {}   # per-key background failures

    def compiler(self, mode: str, placement: str = "") -> StitchCompiler:
        return StitchCompiler(
            hw=self.hw,
            mode=mode,
            gen_cfg=self.gen_cfg,
            use_pallas=self.use_pallas,
            cache=self.cache if mode == "stitch" else None,
            placement=placement,
            plan_budget=self.plan_budget,
        )

    def error_for(self, sig: GraphSignature, placement: str = "") -> str | None:
        """The recorded background-compile failure for this graph's stitch
        key, or None.  Engines poll it so a doomed compile is surfaced
        (warn-once + report) instead of silently serving the fallback."""
        key = self.cache.key_for(sig, "stitch", self.hw.name, placement,
                                 config_key(self.gen_cfg))
        with self._lock:
            return self.errors.get(key)

    def error_report(self) -> dict[str, str]:
        """Every recorded background failure, keyed by a stable readable
        string (``graph_key/bucket/mode/hw/placement``) — what the unified
        ``StitchedFunction.report()['errors']`` exposes."""
        with self._lock:
            return {"/".join(str(p) for p in key): msg
                    for key, msg in self.errors.items()}

    def compile(self, g: Graph, placement: str = "") -> CompiledGraph:
        """Blocking cache-aware full compile (offline / warmup path)."""
        return self.compiler("stitch", placement).compile(g)

    def compile_or_fallback(self, g: Graph,
                            placement: str = "") -> tuple[CompiledGraph, str]:
        """Never blocks on the stitch pipeline.

        Returns ``(executable, status)`` where status is ``"hit"`` (replayed
        stitched plan), ``"pending"`` (a background compile for this key is
        already in flight, or the worker cap deferred it), or ``"miss"``
        (fallback returned now, upgrade kicked off in the background).

        ``placement`` is the mesh+PartitionSpec key the graph was traced at
        (shard-local shapes); it scopes both the lookup and the background
        compile's insert, so meshes never shadow each other's plans.
        """
        stitch = self.compiler("stitch", placement)
        sig = compute_signature(g)
        hit = self.cache.lookup(g, stitch, sig=sig)
        # one hit-or-miss event per compiled graph: timeline evidence of
        # which requests replayed a plan and which served the fallback
        obs.event("cache.hit" if hit is not None else "cache.miss",
                  cat="cache", graph=g.name, placement=placement,
                  bucket=sig.bucket_key(self.cache.bucket_policy))
        if hit is not None:
            return hit, "hit"
        fallback = self.compiler(self.fallback_mode).compile(g)
        spawned = self.ensure_compiling(g, sig=sig, placement=placement)
        return fallback, "miss" if spawned else "pending"

    def ensure_compiling(self, g: Graph, sig: GraphSignature | None = None,
                         placement: str = "") -> bool:
        """Kick the background stitch compile for ``g`` unless one is already
        in flight for its key.  Returns True when a new compile was spawned.
        A request deferred by the worker cap (cold-start burst) is re-kicked
        by calling this again; a key whose compile *failed* is never retried
        — the failure is recorded in ``errors`` and callers surface it via
        :meth:`error_for`."""
        sig = sig or compute_signature(g)
        key = self.cache.key_for(sig, "stitch", self.hw.name, placement,
                                 config_key(self.gen_cfg))
        with self._lock:
            self._threads = [x for x in self._threads if x.is_alive()]
            if key in self._pending:
                return False
            if key in self.errors:
                # this key's compile already failed: re-running it would fail
                # the same way forever — callers surface it via error_for()
                return False
            if len(self._threads) >= self.max_background:
                # bounded worker count: don't stack N ILP+tuning pipelines on
                # a cold-start burst; this key retries on a later call
                return False
            self._pending.add(key)
        stitch = self.compiler("stitch", placement)
        obs.event("compile.start", cat="compile", graph=g.name,
                  placement=placement, background=True)

        def _upgrade():
            try:
                with obs.span("compile.background", cat="compile",
                              graph=g.name, placement=placement):
                    stitch.compile(g, bypass_cache_lookup=True)
            except Exception as e:          # surfaced via last_error / report
                with self._lock:
                    self.last_error = f"{type(e).__name__}: {e}"
                    self.errors[key] = self.last_error
                obs.event("compile.fail", cat="compile", graph=g.name,
                          placement=placement, error=self.last_error)
            finally:
                with self._lock:
                    self._pending.discard(key)

        t = threading.Thread(target=_upgrade, daemon=True, name="stitch-upgrade")
        with self._lock:
            self._threads.append(t)
        t.start()
        return True

    def wait(self, timeout: float | None = None) -> None:
        """Join in-flight background compiles (tests / orderly shutdown)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._lock:
            threads = list(self._threads)
        for t in threads:
            t.join(
                None if deadline is None else max(0.0, deadline - time.monotonic())
            )
