"""Serving engine: three explicit stages over paged KV.

The public API is the disaggregated serving triple:

* :meth:`Engine.prefill` — run one (possibly ragged) prompt batch through a
  pow2-bucketed prefill and get a :class:`~repro.serve.kv.Prefix`: true
  lengths, the greedy first token per row, and the bucketed KV.  Prefills
  dispatch through :func:`repro.exec.stitch` with ``respecialize``: each
  bucket is its own specialization with its own placement-keyed fusion
  plan, so miss-then-upgrade, plan caching, and :meth:`Engine.report` cover
  prefill exactly like decode.  With ``ServeConfig.prefix_cache`` a
  repeated prompt (content-hashed at page granularity) skips the forward
  pass entirely and returns a page-table reference into cached KV.
* :meth:`Engine.insert` — bind one prefix row to a decode slot.  Paged
  engines splice page tables (shared full pages are refcounted; only the
  partial tail page is copied); dense engines splice the slot rectangle.
* :meth:`Engine.generate_step` — advance every occupied slot ``steps``
  greedy tokens through the one stitched decode step (one host readback
  per chunk).  :meth:`Engine.release` frees a finished slot's pages.

KV lives in :class:`~repro.serve.kv.PagedKV` (fixed-size pages + free-list
allocator; the default off-mesh) or :class:`~repro.serve.kv.DenseKV` (the
legacy per-slot rectangle; required under a mesh, where the decode batch is
``shard_map``-sharded over DP replicas and a shared page pool is not
slot-partitionable).  Where Pallas compiles, the paged decode reads each
slot's live pages through the page table in one kernel per layer (equal to
dense serving within float tolerance); elsewhere it gathers each slot's
pages into a dense view and slices it back to ``max_len``, so paged and
dense serving are token-for-token identical.

Legacy surfaces remain as thin shims: ``submit``/``step``/``drain``
delegate to the :class:`~repro.serve.scheduler.Scheduler` (which itself
drives prefill → insert → generate_step), ``generate(prompts,
prompt_lens=...)`` stages a whole batch through the same three calls, and
``generate(prompts)`` without lengths keeps the old rectangular
cache-splice path behind a one-per-process ``DeprecationWarning``.
"""

from __future__ import annotations

import warnings
from collections import OrderedDict
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.exec import stitch
from repro.models.api import Model
from repro.models.layers import attention_kind, attention_path

from .kv import DenseKV, PagedKV, Prefix

_LEGACY_RECT_WARNED = False


@dataclass
class ServeConfig:
    batch: int           # static batch size == continuous-batching slot count
    max_len: int
    max_new_tokens: int = 32
    eos_id: int = -1     # -1: never stop early (fixed-length benchmark mode)
    stitch_execute: bool = False   # run decode through the stitched artifact
    # -- KV layout -------------------------------------------------------------
    # None resolves to paged off-mesh (when the family has a paged layout)
    # and dense under a mesh; True forces paged (errors with a mesh), False
    # forces the legacy dense rectangles.
    paged: bool | None = None
    page_size: int = 16
    # pool size; default slots*ceil(max_len/ps)+1 (doubled when the prefix
    # cache is on, so cached pages aren't evicted by slot-demand pressure)
    num_pages: int | None = None
    # -- prefix cache ----------------------------------------------------------
    prefix_cache: bool = False     # content-hashed prompt KV reuse (paged only)
    prefix_cache_entries: int = 64
    # -- prefill dispatch ------------------------------------------------------
    # live prefill specializations (pow2 buckets x extra-structures), LRU
    prefill_cache_size: int = 8
    # -- debug -----------------------------------------------------------------
    # run the repro.analysis KV/refcount audit after every release() and
    # drain() (paged engines); a leaked or double-owned page raises
    # VerificationError at the call that created it instead of surfacing
    # later as pool exhaustion.  Off by default: the audit walks the whole
    # page pool.
    debug_kv: bool = False


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig,
                 stitch_service=None, mesh: Mesh | None = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.stitch_service = stitch_service
        self._scheduler = None
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self._slot_axes: tuple[str, ...] | None = None
        if self.mesh is not None:
            from repro.models.sharding import batch_shard_axes
            axes = batch_shard_axes(self.mesh, cfg.batch)
            if not axes:
                raise ValueError(
                    f"slots/batch={cfg.batch} does not divide across mesh "
                    f"{dict(self.mesh.shape)}: the DP-replica dispatch needs "
                    f"the slot count to be a multiple of the DP size (or of "
                    f"the whole mesh)")
            self._slot_axes = axes
        if cfg.paged and self.mesh is not None:
            raise ValueError(
                "paged KV is not supported under a mesh: the shared page "
                "pool is not slot-partitionable across DP replicas (use "
                "paged=False / the dense layout)")
        self.paged = (cfg.paged if cfg.paged is not None
                      else self.mesh is None
                      and model.init_paged_cache is not None)
        if cfg.prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires the paged KV layout")
        # slot state (host-authoritative): last emitted token per slot and
        # which slots hold a live request
        self._tok = np.zeros((cfg.batch, 1), np.int32)
        self._occupied: set[int] = set()
        self._rid = [-1] * cfg.batch     # request id per slot, for spans
        self._kv: PagedKV | DenseKV | None = None
        self._prefix = None
        # how the traced paged decode step attends: "kernel" | "gather",
        # or "mla-kernel" | "mla-gather" for latent attention
        self._paged_attention: str | None = None
        # a dropless expert share reads its load back with the tokens
        moe = model.cfg.moe
        self._moe_share = moe is not None and moe.held is not None
        self._exec = self._build_exec()
        self._prefill_exec = self._build_prefill_exec()

    # -- the one decode dispatch ----------------------------------------------
    def _build_exec(self):
        """The stitch()-produced decode step every serving path shares.

        ``extra`` (family-specific decode inputs, e.g. encoder outputs) is a
        real traced argument, not a closure capture, so later calls' values
        flow through the stitched graph; a *structure* change is an ordinary
        signature drift and serves through jit.  Under a mesh the partition
        specs are derived per signature from the concrete cache pytree
        (leaf-name based slot specs); signatures with a non-empty ``extra``
        resolve to ``None`` — their slot layout is family-specific and not
        worth a wrong guess — which the exec layer serves via plain jit.
        """
        model = self.model
        mode = ("jit" if self.stitch_service is None
                else "stitch" if self.cfg.stitch_execute else "shadow")
        # python int closure constant: the paged decode slices its gathered
        # per-slot KV view back to max_len so the attention reduction shape
        # matches the dense layout bitwise (ignored by non-paged caches)
        kvl = self.cfg.max_len if self.paged else None

        def decode_step(params, cache, tok, extra):
            if "page_table" in cache:
                self._paged_attention = attention_path(model.cfg, cache)
            return model.decode_step(params, cache, tok, kv_limit=kvl, **extra)

        # eligibility covers only (cache, tok, extra): params are fixed for
        # an engine's lifetime, so the per-token drift check stays cheap
        elig = (1, 2, 3)
        if self.mesh is None:
            return stitch(decode_step, mode=mode, service=self.stitch_service,
                          eligibility_argnums=elig, name="decode_step")

        mesh, axes = self.mesh, self._slot_axes

        def in_specs(params, cache, tok, extra):
            if extra:
                return None
            from repro.models.sharding import slot_pspecs
            return (P(), slot_pspecs(cache, mesh, axes), P(axes, None), P())

        def out_specs(params, cache, tok, extra):
            from repro.models.sharding import slot_pspecs
            return (P(axes), slot_pspecs(cache, mesh, axes))

        return stitch(decode_step, mode=mode, service=self.stitch_service,
                      mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      eligibility_argnums=elig, name="decode_step")

    def _build_prefill_exec(self):
        """The stitched prefill dispatch: one StitchedFunction whose
        ``respecialize`` cap holds the live (bucket, extra-structure)
        specializations — each pow2 bucket traces its own graph under its
        own placement-keyed plan, LRU-bounded so a long-lived server with
        drifting prompt lengths cannot accumulate compiles without bound
        (the old per-bucket jit memo never evicted).  Admission prefills are
        per-request and unsharded even on mesh engines, so this path never
        takes the mesh."""
        model = self.model
        mode = ("jit" if self.stitch_service is None
                else "stitch" if self.cfg.stitch_execute else "shadow")

        def prefill_step(params, tokens, true_len, extra):
            return model.prefill(params, tokens, true_len=true_len, **extra)

        return stitch(prefill_step, mode=mode, service=self.stitch_service,
                      eligibility_argnums=(1, 2, 3),
                      respecialize=self.cfg.prefill_cache_size,
                      name="prefill")

    def _decode_dispatch(self, cache, tok, extra):
        """One decode step through the shared execution layer — stitched
        artifact when eligible, jit otherwise, polling the background
        upgrade each call (so a request stream upgrades mid-stream).  Both
        routes are DP-replica sharded when the engine has a mesh."""
        return self._exec(self.params, cache, jnp.asarray(tok), extra)

    @property
    def dp_replicas(self) -> int:
        """Replica count the decode batch is spread over (1 when unsharded)."""
        if self._slot_axes is None:
            return 1
        n = 1
        for a in self._slot_axes:
            n *= self.mesh.shape[a]
        return n

    # -- KV state --------------------------------------------------------------
    @property
    def kv(self) -> PagedKV | DenseKV:
        """Slot KV, built lazily (rect-only engines never allocate it)."""
        if self._kv is None:
            if self.paged:
                num_pages = self.cfg.num_pages
                if num_pages is None and self.cfg.prefix_cache:
                    # double the worst-case slot demand: without headroom
                    # every insert's pool pressure would immediately evict
                    # the entry the preceding prefill just registered
                    import math
                    pps = math.ceil(self.cfg.max_len / self.cfg.page_size)
                    num_pages = 2 * self.cfg.batch * pps + 1
                self._kv = PagedKV(self.model, self.cfg.batch,
                                   self.cfg.max_len, self.cfg.page_size,
                                   num_pages=num_pages)
                if self.cfg.prefix_cache:
                    from .prefix import PrefixCache
                    self._prefix = PrefixCache(
                        self._kv, max_entries=self.cfg.prefix_cache_entries)
                    # pool pressure evicts cold prefix entries before failing
                    self._kv.reclaim = self._prefix.evict_one
            else:
                self._kv = DenseKV(self.model, self.cfg.batch,
                                   self.cfg.max_len)
        return self._kv

    @property
    def prefix_cache(self):
        """The content-hashed prompt-KV cache, or None when disabled."""
        if self.cfg.prefix_cache and self._prefix is None:
            _ = self.kv                     # builds the cache alongside KV
        return self._prefix

    # -- stage 1: prefill ------------------------------------------------------
    def prefill(self, tokens, prompt_lens=None, extra=None,
                rid: int | None = None) -> Prefix:
        """Run a prompt batch (2-D, or a single 1-D prompt) through the
        bucketed prefill; returns the :class:`Prefix` that ``insert`` binds
        to a slot.  Single-row prompts first consult the prefix cache."""
        from .scheduler import ADMISSION_BUCKET
        extra = dict(extra or {})
        rid = -1 if rid is None else rid
        toks = np.asarray(tokens, np.int32)
        if toks.ndim == 1:
            toks = toks[None]
        B, Pn = toks.shape
        if Pn == 0:
            raise ValueError("prefill: empty prompt")
        lens = (np.full((B,), Pn, np.int32) if prompt_lens is None
                else np.asarray(prompt_lens, np.int32).reshape(-1))
        if lens.shape != (B,) or int(lens.max()) > Pn or int(lens.min()) < 1:
            raise ValueError(f"prompt_lens {lens!r} inconsistent with "
                             f"prompts of shape {toks.shape}")
        cacheable = (self.cfg.prefix_cache and B == 1 and not extra)
        if cacheable:
            hit = self.prefix_cache.lookup(toks[0, :int(lens[0])])
            if hit is not None:
                obs.event("serve.prefill", cat="serve", rid=rid,
                          prompt_len=int(lens[0]), cached=True)
                hit.rid = rid
                return hit
        pb = min(ADMISSION_BUCKET.bucket_dim(Pn), self.cfg.max_len)
        padded = np.zeros((B, pb), np.int32)
        padded[:, :Pn] = toks
        with obs.span("serve.prefill", cat="serve", rid=rid,
                      prompt_len=int(lens.max()), bucket=pb, batch=B,
                      cached=False):
            logits, cache = self._prefill_exec(
                self.params, jnp.asarray(padded), jnp.asarray(lens), extra)
        with obs.span("serve.prefill.readback", cat="serve", rid=rid):
            first = np.asarray(jnp.argmax(logits, axis=-1)).astype(np.int64)
        px = Prefix(lengths=lens, first_tokens=first, bucket=pb, kv=cache,
                    rid=rid)
        if cacheable:
            self.prefix_cache.register(toks[0, :int(lens[0])], cache,
                                       row=0, first_token=int(first[0]),
                                       length=int(lens[0]))
        return px

    # -- stage 2: insert -------------------------------------------------------
    def insert(self, prefix: Prefix, slot: int, row: int = 0) -> None:
        """Bind row ``row`` of a prefix to decode slot ``slot``: a
        page-table splice (shared pages refcounted, tail copied) for a
        cached prefix, a KV splice otherwise."""
        if not 0 <= slot < self.cfg.batch:
            raise IndexError(f"slot {slot} out of range 0..{self.cfg.batch-1}")
        if slot in self._occupied:
            raise RuntimeError(f"slot {slot} already holds a request "
                               f"(release it first)")
        true_len = int(prefix.lengths[row])
        with obs.span("serve.insert", cat="serve", rid=prefix.rid, slot=slot):
            if prefix.pages is not None:
                self.kv.insert_shared(prefix.pages, prefix.tail, true_len,
                                      slot)
            else:
                self.kv.insert_kv(prefix.kv, row, true_len, slot)
        self._tok[slot, 0] = int(prefix.first_tokens[row])
        self._rid[slot] = prefix.rid
        self._occupied.add(slot)

    # -- stage 3: generate -----------------------------------------------------
    def generate_step(self, steps: int = 1, extra: dict | None = None
                      ) -> np.ndarray:
        """Advance every occupied slot ``steps`` greedy tokens; returns the
        (slots, steps) token matrix (free slots' rows are ride-along noise).
        One host readback per call regardless of ``steps``."""
        if not self._occupied:
            raise RuntimeError("generate_step: no occupied slots "
                               "(insert a prefix first)")
        extra = dict(extra or {})
        occ = sorted(self._occupied)
        pages = {}
        if self.paged:
            live = attention_kind(self.model.cfg).live_pages
            pages = {live: self.kv.live_pages(occ, steps),
                     "kv_view_pages": steps * self.kv.slots
                     * self.kv.pages_per_slot}
        with obs.span("serve.decode", cat="serve", slots=len(occ),
                      steps=steps, **pages) as dsp:
            with obs.span("serve.decode.prep", cat="serve"):
                if self.paged:
                    for s in occ:
                        self.kv.ensure(s, steps)
                cache = self.kv.decode_cache()
                # copy: jnp.asarray may alias the numpy buffer, which is
                # mutated below while the chunk is still in flight on some
                # backends
                tok = jnp.asarray(self._tok.copy())
            toks_dev, loads = [], []
            for _ in range(steps):
                logits, cache = self._decode_dispatch(cache, tok, extra)
                if self._moe_share:
                    loads.append(cache.pop("moe_load"))
                with obs.span("serve.decode.sample", cat="serve"):
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
                    toks_dev.append(tok)
                    if len(toks_dev) == steps:
                        toks = jnp.concatenate(toks_dev, axis=1)
                        if loads:
                            # the expert load rides the tokens' one copy
                            toks = jnp.concatenate(
                                [toks.reshape(-1), jnp.stack(loads).reshape(-1)])
            with obs.span("serve.decode.readback", cat="serve"):
                out = np.asarray(toks)
            if loads:
                out, load = (out[:-2 * steps].reshape(-1, steps),
                             out[-2 * steps:].reshape(steps, 2))
                dsp.set(moe_pairs_here=int(load[:, 0].sum()),
                        moe_max_load=int(load[:, 1].max()))
            with obs.span("serve.decode.commit", cat="serve"):
                self.kv.absorb(cache)
                self.kv.advance(occ, steps)
                for s in occ:
                    self._tok[s, 0] = int(out[s, -1])
        return out

    def release(self, slot: int) -> None:
        """Free a finished slot: paged engines return its pages to the
        allocator (decref for prefix-shared ones) immediately."""
        with obs.span("serve.release", cat="serve", rid=self._rid[slot],
                      slot=slot):
            self.kv.free(slot)
        self._tok[slot, 0] = 0
        self._rid[slot] = -1
        self._occupied.discard(slot)
        self._debug_audit(f"release(slot={slot})")

    # -- KV conservation audit (repro.analysis pass 4) -------------------------
    def audit_kv(self) -> list:
        """Snapshot the page allocator + page table + prefix cache and run
        the static conservation audit; returns the ``Finding`` list (empty
        for a healthy pool, or on dense/unbuilt KV where there is nothing
        to audit)."""
        if not self.paged or self._kv is None:
            return []
        from repro.analysis import audit_kv, snapshot
        return audit_kv(snapshot(kv=self._kv, prefix=self._prefix))

    def _debug_audit(self, what: str) -> None:
        if not self.cfg.debug_kv:
            return
        from repro.analysis import VerificationError, errors
        bad = errors(self.audit_kv())
        if bad:
            raise VerificationError(f"KV audit after {what}", bad)

    @property
    def occupied(self) -> frozenset[int]:
        return frozenset(self._occupied)

    # -- observability ---------------------------------------------------------
    @property
    def stitch_status(self) -> str | None:
        """None before the first decode (or without a service), else the
        exec layer's status: hit | miss | pending | failed | error."""
        if self.stitch_service is None:
            return None
        return self._exec.status

    @property
    def _stitch(self) -> dict | None:
        """Test/debug view of the active stitched decode specialization."""
        sp = self._exec._active
        if sp is None:
            return None
        if sp.graph is None:
            return {}
        return {"graph": sp.graph, "compiled": sp.compiled,
                "placement": sp.placement, "sharded": sp.sharded,
                "executable": sp.executable}

    def stitch_report(self) -> dict:
        """The decode step's :data:`repro.obs.EXEC_REPORT_SCHEMA` dict —
        upgrade status, plan stats, call counts, cache hit rates, and every
        background-compile failure (also in pure-jit mode) — plus
        ``paged_attention``: how the traced paged step attends
        (:func:`repro.models.layers.attention_path`; None before a paged
        step is traced), ``attention`` (the same, else how the dense
        rectangle is read) and ``moe`` (a dropless expert share's
        ``{"held": [first, end), "experts": E, "dropless": True}``, else
        None)."""
        rep = self._exec.report()
        rep["paged_attention"] = self._paged_attention
        cfg = self.model.cfg
        rep["attention"] = self._paged_attention or attention_path(cfg)
        rep["moe"] = None
        if self._moe_share:
            e0, n = cfg.moe.held
            rep["moe"] = {"held": [e0, e0 + n], "experts": cfg.moe.n_experts,
                          "dropless": True}
        return rep

    def land_plans(self, timeout: float | None = None) -> int:
        """Join background compiles for decode AND every live prefill
        specialization; returns how many still lack a stitched plan
        (benches use this before reading kernel counts)."""
        return (self._exec.land_plans(timeout)
                + self._prefill_exec.land_plans(timeout))

    def report(self) -> dict:
        """Engine-wide report: decode + prefill exec reports (the prefill
        one carries per-bucket placement-keyed plans), KV/page-pool state,
        prefix-cache hit rates, and the bounded prefill-memo size."""
        prefill = self._prefill_exec.report()
        entries = (prefill["specializations"] or
                   prefill.get("jit_specializations", 0))
        return {
            "decode": self._exec.report(),
            "prefill": prefill,
            "kv": self._kv.report() if self._kv is not None else None,
            "prefix_cache": (self._prefix.report()
                             if self._prefix is not None else None),
            "cache": {"prefill_entries": entries,
                      "prefill_cap": self.cfg.prefill_cache_size},
        }

    # -- continuous batching (shim over the three-stage API) -------------------
    @property
    def scheduler(self):
        """Lazy slot scheduler driving prefill → insert → generate_step."""
        if self._scheduler is None:
            from .scheduler import Scheduler, SchedulerConfig
            cfg = SchedulerConfig(
                slots=self.cfg.batch, max_len=self.cfg.max_len,
                max_new_tokens=self.cfg.max_new_tokens, eos_id=self.cfg.eos_id)
            self._scheduler = Scheduler(self, cfg)
        return self._scheduler

    def submit(self, prompt, max_new_tokens: int | None = None, **kw) -> int:
        """Enqueue one request (1-D prompt); returns its request id."""
        return self.scheduler.submit(prompt, max_new_tokens=max_new_tokens, **kw)

    def step(self):
        """Run one scheduler step (refill -> batched decode -> evict);
        returns its :class:`~repro.serve.metrics.StepMetrics`."""
        return self.scheduler.step()

    def drain(self, max_steps: int | None = None):
        """Step until all submitted requests finish; returns the
        :class:`~repro.serve.queue.FinishedRequest` list in completion order."""
        out = self.scheduler.drain(max_steps=max_steps)
        self._debug_audit("drain()")
        return out

    def serve_report(self) -> dict:
        """Aggregate scheduler metrics (empty if continuous mode unused)."""
        if self._scheduler is None:
            return {}
        return self._scheduler.metrics.summary()

    # -- static serving (shims) ------------------------------------------------
    def generate(self, prompts: np.ndarray, prompt_lens=None, **extra) -> np.ndarray:
        """prompts: (batch, prompt_len) int32 -> (batch, max_new_tokens).

        With ``prompt_lens`` (per-row true lengths) the batch stages through
        the three-stage API: one bucketed prefill, per-row slot inserts, a
        chunked generate, then release — the per-request reference the
        scheduler is tested token-for-token against.  Without it, the
        legacy rectangular cache-splice path still serves (deprecated; it
        bypasses paged KV, the prefix cache, and the stitched prefill)."""
        B, Pn = prompts.shape
        assert B == self.cfg.batch
        if prompt_lens is not None:
            return self._generate_staged(prompts, prompt_lens, extra)
        global _LEGACY_RECT_WARNED
        if not _LEGACY_RECT_WARNED:
            _LEGACY_RECT_WARNED = True
            warnings.warn(
                "Engine.generate(prompts) without prompt_lens uses the "
                "legacy rectangular cache-splice path; migrate to "
                "prefill()/insert()/generate_step() (or pass prompt_lens) — "
                "see the README 'Serving' section", DeprecationWarning,
                stacklevel=2)
        logits, cache = self.model.prefill(
            self.params, jnp.asarray(prompts, jnp.int32), **extra)
        # decode cache from prefill may be shorter than max_len; re-home it
        if "k" in cache and cache["k"].ndim == 5 and cache["k"].shape[2] < self.cfg.max_len:
            pad = self.cfg.max_len - cache["k"].shape[2]
            cache = dict(cache)
            cache["k"] = jnp.pad(cache["k"], [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])
            cache["v"] = jnp.pad(cache["v"], [(0, 0), (0, 0), (0, pad), (0, 0), (0, 0)])

        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        out = []
        for _ in range(self.cfg.max_new_tokens):
            out.append(np.asarray(tok))
            logits, cache = self._decode_dispatch(cache, tok, extra)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
        return np.concatenate(out, axis=1)

    def _generate_staged(self, prompts: np.ndarray, prompt_lens, extra) -> np.ndarray:
        from .scheduler import RAGGED_FAMILIES
        if self.model.cfg.family not in RAGGED_FAMILIES:
            raise NotImplementedError(
                f"ragged generate (prompt_lens) supports families "
                f"{RAGGED_FAMILIES}, got {self.model.cfg.family!r}")
        if self._occupied:
            raise RuntimeError(
                "generate(prompt_lens=...) needs an idle engine; "
                f"slots {sorted(self._occupied)} hold live requests")
        B, _ = prompts.shape
        px = self.prefill(prompts, prompt_lens=prompt_lens, extra=extra)
        for row in range(B):
            self.insert(px, slot=row, row=row)
        out = [px.first_tokens.astype(np.int32)[:, None]]
        if self.cfg.max_new_tokens > 1:
            out.append(self.generate_step(steps=self.cfg.max_new_tokens - 1,
                                          extra=extra))
        for row in range(B):
            self.release(row)
        return np.concatenate(out, axis=1)
