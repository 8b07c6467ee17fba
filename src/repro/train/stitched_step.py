"""Stitched training step — two :func:`repro.exec.stitch` callables plus
training-specific glue.

Training is the paper's canonical memory-intensive workload: the backward
pass of norms/softmax/cross-entropy and the AdamW+clip update are pure
elementwise+reduction traffic over every parameter.  Since the
``repro.exec`` refactor this module owns only the *training* decisions —
everything about tracing, compile-or-fallback, miss-then-upgrade polling,
shard_map construction, and placement-keyed caching lives in the shared
execution layer:

* **Backward phase** — the ``jax.value_and_grad``-built loss+grad function
  (:func:`~repro.train.step.make_loss_and_grad`, including microbatch
  accumulation) becomes one ``stitch()``-produced callable.  Under a mesh
  the stitched function is the *shard-local* body with the DP ``pmean`` of
  loss/aux/grads written at its tail (the psum-mean placement is glue; the
  collectives trace via ``axis_env`` into executable CUSTOM fusion
  partitions), ``in_specs=(P(), batch_specs)`` so params arrive gathered
  (TP-at-rest storage fine) and batch rows split over every dividing mesh
  axis.
* **Optimizer phase** — :class:`repro.optim.packed.PackedAdamW` (itself
  built on ``stitch()``): the whole AdamW+global-norm-clip update is ONE
  packed kernel over shared-row panels.  Under a mesh the packed kernel
  updates TP-shard-local param panels inside a
  :func:`repro.exec.shard_wrap` dispatch (rebuilt when an upgrade swaps
  the artifact), with the clip scale fed as a scalar from the reduced full
  gradients (``external_ssq=True``); m/v stay co-located with params via
  :meth:`state_shardings` — no ZeRO on this path.

The consumed ``TrainState`` is donated by default (``donate=False`` opts
out): the jit fallback uses ``donate_argnums`` and the stitched dispatch
deletes the old params/moments once the update has been dispatched, so peak
memory holds one copy of params+opt, not two.

If tracing or compilation fails outright the step degrades to the plain
jitted reference; a per-call shape drift (e.g. a last-partial batch) falls
back to the jitted step for that call only (``fallback_steps`` counts
both).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.exec import shard_wrap, stitch, tree_avals as _avals
from repro.models.api import Model
from repro.optim import adamw
from repro.optim.packed import PackedAdamW

from .step import TrainState, make_loss_and_grad, make_train_step


class StitchedTrainStep:
    """Drop-in for :func:`make_train_step`'s returned callable:
    ``step(state, batch) -> (state, metrics)`` with identical numerics, the
    backward pass and the packed optimizer executing through stitched
    artifacts (upgrading from the XLA fallback as background compiles land).

    With ``mesh`` (size > 1) both phases run under ``shard_map`` on
    per-shard graphs — see the module docstring.  ``param_specs`` overrides
    the TP rule table; ``donate=False`` keeps the consumed state alive.
    """

    def __init__(self, model: Model, opt_cfg: adamw.AdamWConfig,
                 microbatches: int = 1, service=None,
                 rows: int = 8, mesh: Mesh | None = None,
                 param_specs=None, donate: bool = True):
        if service is None:
            from repro.cache import CompilationService
            service = CompilationService()
        self.model = model
        self.opt_cfg = opt_cfg
        self.microbatches = microbatches
        self.service = service
        self.rows = rows
        self.donate = donate
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        self.param_specs = None
        if self.mesh is not None:
            from repro.models.sharding import param_pspecs
            self.param_specs = (param_specs if param_specs is not None else
                                param_pspecs(model.abstract_params(),
                                             model.cfg, self.mesh))
        self._grad_fn = make_loss_and_grad(model, microbatches)
        # reference step: full-jit fallback for trace failures / shape drift
        # (donating, like the launcher's jit path; under a mesh it picks the
        # sharded layout up from its operands via GSPMD)
        self._jit_step = jax.jit(make_train_step(model, opt_cfg, microbatches),
                                 donate_argnums=(0,) if donate else ())
        self._prepared = False
        self._grad = None                    # stitch()-produced backward phase
        self._packed: PackedAdamW | None = None
        self._upd_dispatch = None            # shard_wrap'd optimizer dispatch
        self._sharded_ok = False
        self._global_avals = None            # sharded-path eligibility key
        self.fallback_steps = 0              # calls served by the jitted step

    # -- lazy preparation ------------------------------------------------------
    def _prepare(self, state: TrainState, batch) -> None:
        self._prepared = True
        if self.mesh is not None:
            self._prepare_sharded(state, batch)
            return
        self._grad = stitch(self._grad_fn, service=self.service,
                            name="train_grad")
        self._grad.warmup(state.params, batch)
        self._packed = PackedAdamW(self.opt_cfg, state.params,
                                   rows=self.rows, service=self.service)

    def _prepare_sharded(self, state: TrainState, batch) -> None:
        from repro.cache.signature import placement_key
        from repro.models.sharding import batch_shard_axes, local_avals

        mesh = self.mesh
        self._global_avals = _avals((state.params, batch))
        aparams = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), state.params)
        pspecs = self.param_specs
        B = jax.tree_util.tree_leaves(batch)[0].shape[0]
        axes = batch_shard_axes(mesh, B)
        bspecs = jax.tree.map(
            lambda l: P() if l.ndim == 0 else
            P(*((axes if axes else None,) + (None,) * (l.ndim - 1))),
            batch)
        local_batch = local_avals(batch, bspecs, mesh)
        B_local = jax.tree_util.tree_leaves(local_batch)[0].shape[0]
        if self.microbatches > 1 and B_local % self.microbatches:
            # shard-local rows don't split into microbatches: serve the
            # sharded jit fallback rather than change the accumulation math
            return

        # backward: the shard-local body ends with the DP psum-mean — the
        # training-specific placement decision — and stitch() traces the
        # collectives via axis_env into executable CUSTOM fusion partitions
        allax = tuple(mesh.axis_names)
        grad_fn = self._grad_fn

        def local_grad(params, b):
            loss, aux, grads = grad_fn(params, b)
            loss = jax.lax.pmean(loss, allax)
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, allax), aux)
            grads = jax.tree.map(
                lambda g: jax.lax.pmean(g.astype(jnp.float32), allax), grads)
            return loss, aux, grads

        self._grad = stitch(local_grad, service=self.service, mesh=mesh,
                            in_specs=(P(), bspecs),
                            out_specs=(P(), P(), P()), name="train_grad")
        self._grad.warmup(state.params, batch)

        # optimizer: per-shard packed panels over TP-local param slices
        local_params = local_avals(aparams, pspecs, mesh)
        self._packed = PackedAdamW(
            self.opt_cfg, local_params, rows=self.rows,
            service=self.service, external_ssq=True,
            placement=placement_key(mesh, pspecs))
        if not self._grad.ok:
            return

        packed = self._packed

        def local_update(params, grads, m, v, lr, b1c, b2c, gss):
            return packed.update_local(params, grads, m, v,
                                       lr, b1c, b2c, gss=gss)

        sc = P()
        self._upd_dispatch = shard_wrap(
            local_update, mesh,
            in_specs=(pspecs, pspecs, pspecs, pspecs, sc, sc, sc, sc),
            out_specs=(pspecs, pspecs, pspecs, sc),
            refresh_key=lambda: packed._compiled)
        self._sharded_ok = True

    # -- mesh placement for the launcher --------------------------------------
    def state_shardings(self) -> TrainState:
        """NamedSharding placement for the ``TrainState`` this step expects:
        params TP-sharded per the rule table, opt m/v co-located with their
        params (the packed panels update shard-local slices of both — a
        ZeRO offset would misalign them), count/step replicated."""
        assert self.mesh is not None, "state_shardings requires a mesh"
        sh = lambda s: NamedSharding(self.mesh, s)
        pshard = jax.tree.map(sh, self.param_specs,
                              is_leaf=lambda x: isinstance(x, P))
        return TrainState(
            params=pshard,
            opt=adamw.AdamWState(m=pshard, v=pshard, count=sh(P())),
            step=sh(P()))

    # -- donation --------------------------------------------------------------
    def _delete_consumed(self, state: TrainState) -> None:
        """Free the old params and moments once the update is dispatched —
        the stitched analogue of the jit path's ``donate_argnums=(0,)``.
        Without it the consumed state stays alive across the step and peak
        memory holds params+opt twice."""
        for leaf in jax.tree_util.tree_leaves(
                (state.params, state.opt.m, state.opt.v)):
            if isinstance(leaf, jax.Array) and not leaf.is_deleted():
                leaf.delete()

    # -- observability --------------------------------------------------------
    def report(self) -> dict:
        """``grad`` and ``optimizer`` are each a full unified exec report
        (:data:`repro.obs.EXEC_REPORT_SCHEMA`) — the same shape the serving
        engine's ``stitch_report()`` returns — plus the training-level
        ``fallback_steps`` / ``mesh`` / ``cache`` context."""
        out: dict[str, Any] = {
            "grad": (self._grad.report() if self._grad is not None
                     else {"status": None}),
            "optimizer": (self._packed.report() if self._packed is not None
                          else {"status": None}),
            "fallback_steps": self.fallback_steps,
        }
        if self.mesh is not None:
            out["mesh"] = dict(self.mesh.shape)
        if self.service is not None:
            out["cache"] = self.service.cache.report()
            out["service_error"] = self.service.last_error
        return out

    # -- the step --------------------------------------------------------------
    def __call__(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        if not obs.tracer.enabled:
            return self._step(state, batch)
        with obs.span("train.step", cat="train") as s:
            out = self._step(state, batch)
            s.set(fallback_steps=self.fallback_steps)
            return out

    def _step(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        if not self._prepared:
            self._prepare(state, batch)
        if self.mesh is not None:
            return self._call_sharded(state, batch)
        if (self._grad is None or self._packed is None
                or not self._grad.eligible(state.params, batch)):
            self.fallback_steps += 1
            return self._jit_step(state, batch)
        loss, aux, grads = self._grad(state.params, batch)
        new_params, new_opt, opt_metrics = self._packed.update(
            grads, state.opt, state.params)
        metrics = {"loss": loss, "step": state.step + 1, **opt_metrics, **aux}
        out = TrainState(new_params, new_opt, state.step + 1), metrics
        if self.donate:
            self._delete_consumed(state)
        return out

    def _call_sharded(self, state: TrainState, batch) -> tuple[TrainState, dict]:
        ok = (self._sharded_ok and self._grad is not None and self._grad.ok
              and _avals((state.params, batch)) == self._global_avals)
        if not ok:
            self.fallback_steps += 1
            return self._jit_step(state, batch)
        self._packed.poll_upgrade()
        loss, aux, grads = self._grad(state.params, batch)
        cfg = self.opt_cfg
        count = state.opt.count + 1
        lr = adamw.schedule(cfg, count)
        cf = count.astype(jnp.float32)
        b1c = 1 - cfg.b1 ** cf
        b2c = 1 - cfg.b2 ** cf
        # global clip scale from the reduced full grads — replicated, so
        # every shard's packed kernel sees the same scalar
        gss = functools.reduce(
            jnp.add, [jnp.sum(jnp.square(g))
                      for g in jax.tree_util.tree_leaves(grads)])
        new_p, new_m, new_v, gnorm = self._upd_dispatch(
            state.params, grads, state.opt.m, state.opt.v,
            jnp.asarray(lr, jnp.float32), jnp.asarray(b1c, jnp.float32),
            jnp.asarray(b2c, jnp.float32), gss)
        metrics = {"loss": loss, "step": state.step + 1, "grad_norm": gnorm,
                   "lr": lr, **aux}
        out = (TrainState(new_p, adamw.AdamWState(new_m, new_v, count),
                          state.step + 1), metrics)
        if self.donate:
            self._delete_consumed(state)
        return out

    # -- orderly shutdown ------------------------------------------------------
    def wait(self, timeout: float | None = None) -> None:
        """Join in-flight background compiles (tests / clean exit)."""
        self.service.wait(timeout)
