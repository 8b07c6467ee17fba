"""The comparison that decides ``correct`` for a serving cell.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the one with
the most served tokens, is run through the plain reference of the
configuration's architecture (``arch.load(cfg).logits_at``): one forward
pass over each prompt with its served tokens.  At each served position the
reference's float32 logits give the gap by which the served token's logit
lies below the reference's best logit (0 where the served token is the
reference's own greedy choice).  The number compared is the widest such gap
over the sample, in logits.

Every served token is greedy, so a served token that the reference ranks
far below its best means the timed path computed something else: a wrong
kernel, a stale or misplaced KV page, a token altered on its way out.  The
control (the reference with float8 weights, ``quant="fp8"``) reads the same
gap for the token that it puts first at each of those positions.
"""

from __future__ import annotations

import numpy as np

import arch


def sample(finished: list, seed: int, max_requests: int,
           min_tokens: int) -> list:
    """Requests to check: the one with the most served tokens, then others
    in an order drawn from ``seed`` until ``min_tokens`` served tokens or
    ``max_requests`` requests are in the sample."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng(seed).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if len(out) >= max_requests or n >= min_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _rows(req) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sequence, rows, served): row r of the sequence predicts served[i]."""
    served = np.asarray(req.tokens, np.int32)
    seq = np.concatenate([req.prompt, served[:-1]]).astype(np.int32)
    rows = len(req.prompt) - 1 + np.arange(len(served))
    return seq, rows, served


def widest_gap(weights, cfg: dict, reqs: list, quant: str | None = None
               ) -> tuple[float, int]:
    """(widest gap, positions compared).  With ``quant`` the gap is read for
    the token the lower-precision reference puts first, not the served
    one."""
    logits_at = arch.load(cfg).logits_at
    worst, n = 0.0, 0
    for req in reqs:
        seq, rows, served = _rows(req)
        ref = np.asarray(logits_at(weights, seq, rows, cfg), np.float64)
        if quant is not None:
            low = np.asarray(logits_at(weights, seq, rows, cfg, quant))
            served = low.argmax(axis=-1)
        gap = ref.max(axis=-1) - ref[np.arange(len(rows)), served]
        if not np.isfinite(gap).all():
            return float("inf"), n + len(rows)
        worst = max(worst, float(gap.max()))
        n += len(rows)
    return worst, n
