"""Slot-based continuous-batching scheduler.

The scheduler is a thin orchestrator over the engine's three-stage API:
admission is :meth:`Engine.prefill` (bucketed, stitched, prefix-cached) +
:meth:`Engine.insert` (a page-table splice on paged engines), the decode is
:meth:`Engine.generate_step` (one batched chunk over all slots), and
eviction is :meth:`Engine.release` (pages return to the free list).  All
model and KV mechanics live behind the engine; the scheduler owns only the
FIFO queue, the per-slot request lifecycle, and the metrics stream.

Lifecycle per :meth:`Scheduler.step`:

1. **Refill** — free slots are filled from the FIFO queue.  Admission runs
   a *bucketed* prefill: the prompt is right-padded to the next power-of-two
   length (same :class:`~repro.cache.policy.BucketPolicy` rule the
   StitchCache keys on), so a refill at a nearby prompt length replays the
   already-compiled prefill specialization — and, because the decode
   graph's shapes never change, the stitched decode plan — instead of
   forcing a recompile.  Causal masking makes the pad positions inert, and
   logits are gathered at the true last position, so bucketing never
   changes tokens (dense/vlm; see the moe capacity caveat on
   :data:`RAGGED_FAMILIES`).
2. **Decode** — one batched chunk over all slots (inactive slots ride
   along; their rows are ignored, and insert resets them).
3. **Evict** — slots whose request hit EOS (``eos_id >= 0``) or its
   per-request ``max_new_tokens`` are completed and released; the next
   step's refill reuses them immediately.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from repro import obs
from repro.cache.policy import BucketPolicy

from .metrics import ServeMetrics, StepMetrics
from .queue import FinishedRequest, Request, RequestQueue

__all__ = ["SchedulerConfig", "Scheduler", "RAGGED_FAMILIES",
           "ADMISSION_BUCKET"]

# families whose decode step supports a per-slot length vector AND whose
# prefill is pad-invariant under causal masking (SSM/hybrid state mixes pad
# tokens in, so bucketed admission would change numerics there).  Caveat:
# moe on the capacity dispatch (``apply_moe``) is pad-invariant only while
# no expert overflows its capacity — GShard token-choice dispatch couples
# rows through the shared capacity budget (pad/ride-along tokens can
# displace real ones on overflow), the same coupling a static moe batch
# already has.  moe on the dropless expert share (``MoEConfig.held``,
# ``apply_moe_share``) computes every (token, expert) pair with no
# capacity, so no row depends on another: exact, as dense/vlm are.
RAGGED_FAMILIES = ("dense", "moe", "vlm")

# the admission bucket rule, shared with Engine.prefill so the static
# reference path pads exactly like the scheduler
ADMISSION_BUCKET = BucketPolicy(mode="pow2", min_dim=1)


@dataclass
class SchedulerConfig:
    slots: int
    max_len: int                        # KV capacity per slot
    max_new_tokens: int = 32            # default per-request budget
    eos_id: int = -1                    # -1: never stop early
    # pow2 admission buckets; min_dim=1 so even short prompts coalesce
    # (the cache-key default of 16 would give every short length its own
    # compile)
    bucket: BucketPolicy = field(default_factory=lambda: ADMISSION_BUCKET)


@dataclass
class _Slot:
    req: Request
    tokens: list[int]
    admit_time: float
    admit_step: int
    prefix_cached: bool = False


class Scheduler:
    def __init__(self, engine, cfg: SchedulerConfig | None = None,
                 clock: Callable[[], float] = time.monotonic):
        if engine.model.cfg.family not in RAGGED_FAMILIES:
            raise NotImplementedError(
                f"continuous batching supports families {RAGGED_FAMILIES}, "
                f"got {engine.model.cfg.family!r} (its decode state is not "
                f"pad-invariant / per-slot addressable)")
        if cfg is None:
            cfg = SchedulerConfig(
                slots=engine.cfg.batch, max_len=engine.cfg.max_len,
                max_new_tokens=engine.cfg.max_new_tokens,
                eos_id=engine.cfg.eos_id)
        self.engine = engine
        self.cfg = cfg
        self.clock = clock

        self.queue = RequestQueue()
        self.metrics = ServeMetrics()
        self.slots: list[_Slot | None] = [None] * cfg.slots
        self.step_count = 0

    # -- admission -------------------------------------------------------------
    def bucket_len(self, prompt_len: int) -> int:
        return min(self.cfg.bucket.bucket_dim(prompt_len), self.cfg.max_len)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    def submit(self, prompt, max_new_tokens: int | None = None,
               rid: int | None = None, arrival_time: float | None = None,
               extra: dict | None = None) -> int:
        """Enqueue one request; returns its id."""
        n_new = self.cfg.max_new_tokens if max_new_tokens is None else max_new_tokens
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + n_new > self.cfg.max_len:
            raise ValueError(
                f"prompt_len={len(prompt)} + max_new_tokens={n_new} exceeds "
                f"max_len={self.cfg.max_len}")
        at = self.clock() if arrival_time is None else arrival_time
        return self.queue.submit(prompt, n_new, rid=rid, arrival_time=at,
                                 extra=extra)

    def _finish(self, slot_state: _Slot, reason: str, step: int) -> FinishedRequest:
        req = slot_state.req
        fin = FinishedRequest(
            rid=req.rid, prompt_len=len(req.prompt),
            tokens=np.asarray(slot_state.tokens, np.int32),
            finish_reason=reason,
            arrival_time=req.arrival_time,
            admit_time=slot_state.admit_time,
            first_token_time=slot_state.admit_time,
            finish_time=self.clock(),
            admit_step=slot_state.admit_step, finish_step=step,
            prefix_cached=slot_state.prefix_cached)
        self.metrics.record_finished(fin)
        obs.event("serve.evict", cat="serve", rid=req.rid, reason=reason,
                  step=step, tokens=len(slot_state.tokens))
        return fin

    def _admit(self, slot: int, req: Request) -> tuple[int, int, int]:
        """Prefill + insert into ``slot``; returns (tokens_emitted,
        evictions, prefix_hits) — a request whose budget is 1 (or whose
        first token is EOS) finishes at admission without ever occupying
        the slot."""
        px = self.engine.prefill(req.prompt, extra=req.extra, rid=req.rid)
        first = int(px.first_tokens[0])
        state = _Slot(req=req, tokens=[first], admit_time=self.clock(),
                      admit_step=self.step_count, prefix_cached=px.cached)
        hits = int(px.cached)
        eos = self.cfg.eos_id >= 0 and first == self.cfg.eos_id
        if eos or req.max_new_tokens == 1:
            self._finish(state, "eos" if eos else "length", self.step_count)
            return 1, 1, hits
        self.engine.insert(px, slot)
        self.slots[slot] = state
        return 1, 0, hits

    def _refill(self) -> tuple[int, int, int, int]:
        """Fill free slots from the queue; returns (admissions, tokens,
        evictions, prefix_hits)."""
        admissions = tokens = evictions = hits = 0
        for slot in range(self.cfg.slots):
            while self.slots[slot] is None and self.queue:
                req = self.queue.pop()
                t, e, h = self._admit(slot, req)
                admissions += 1
                tokens += t
                evictions += e
                hits += h
                if e == 0:
                    break               # slot now occupied
        return admissions, tokens, evictions, hits

    def _chunk_len(self) -> int:
        """Decode steps safely runnable before the next scheduling decision.

        With EOS off, evictions are budget-exhaustions — predictable on the
        host — and after a refill either the queue is empty or every slot is
        full, so no admission can happen before the earliest budget runs
        out.  Chunking those steps keeps the decode stream on device (one
        argmax readback per chunk instead of per token).  With EOS on, every
        token is a potential eviction: chunk = 1."""
        if self.cfg.eos_id >= 0:
            return 1
        return min(s.req.max_new_tokens - len(s.tokens)
                   for s in self.slots if s is not None)

    # -- one scheduling iteration ---------------------------------------------
    def step(self) -> StepMetrics:
        t0 = self.clock()
        step = self.step_count
        ssp = obs.span("serve.step", cat="serve", step=step)
        ssp.__enter__()
        admissions, tokens, evictions, prefix_hits = self._refill()
        active = self.n_active

        if active:
            chunk = self._chunk_len()
            # the chunk's one host sync: token ids are needed for EOS /
            # budget checks and the next iteration's input.  Free slots
            # ride along inside the engine (their rows are ignored).
            nxt = self.engine.generate_step(steps=chunk)   # (slots, chunk)
            for slot, state in enumerate(self.slots):
                if state is None:
                    continue
                done = None
                for tok_i in nxt[slot].tolist():
                    state.tokens.append(int(tok_i))
                    tokens += 1
                    if self.cfg.eos_id >= 0 and tok_i == self.cfg.eos_id:
                        done = "eos"
                        break
                    if len(state.tokens) >= state.req.max_new_tokens:
                        done = "length"
                        break
                if done is not None:
                    self._finish(state, done, step)
                    self.slots[slot] = None
                    self.engine.release(slot)
                    evictions += 1

        m = StepMetrics(
            step=step, active=active, slots=self.cfg.slots,
            queue_depth=len(self.queue), admissions=admissions,
            evictions=evictions, tokens=tokens,
            step_seconds=self.clock() - t0,
            stitch_status=self.engine.stitch_status,
            prefix_hits=prefix_hits)
        self.metrics.record_step(m)
        self.step_count += 1
        ssp.set(active=active, admissions=admissions, evictions=evictions,
                tokens=tokens, queue_depth=m.queue_depth,
                stitch_status=m.stitch_status)
        ssp.__exit__(None, None, None)
        # a Perfetto counter track of page-pool occupancy over time
        if self.engine.paged and self.engine._kv is not None:
            alloc = self.engine.kv.allocator
            obs.counter_event("serve.pages", cat="serve", used=alloc.used,
                              free=alloc.free_count)
        return m

    def drain(self, max_steps: int | None = None) -> list[FinishedRequest]:
        """Step until queue and slots are empty; returns finished requests in
        completion order."""
        already = len(self.metrics.finished)
        budget = max_steps if max_steps is not None else (
            10 * self.cfg.max_len * (len(self.queue) + self.n_active + 1))
        for _ in range(budget):
            if not self.queue and not self.n_active:
                break
            self.step()
        if self.queue or self.n_active:
            raise RuntimeError(f"drain did not converge in {budget} steps")
        return self.metrics.finished[already:]
