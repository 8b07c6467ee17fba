"""The one traffic generator: turns a traffic mix (``traffic/<name>.json``)
and a seed into a list of requests.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps, in its own order: sizes are the quantiles of the mix's
distributions at ``(i + 0.5) / n``, and the seed only permutes them and
draws the prompt tokens.  So two seeds do the same amount of work; in an
open loop the order still moves the tails, since a seed that puts long
answers close together fills every slot.

Mix keys:

* ``loop``: ``"open"`` (requests arrive on a schedule whether or not the
  server keeps up) or ``"closed"`` (``slots`` clients, each sending its next
  request when the last one finished).
* ``prompt`` / ``output``: ``{"dist": "lognormal", "median", "sigma", "min",
  "max"}`` or ``{"dist": "uniform", "min", "max"}``; lengths in tokens,
  clipped to ``[min, max]``.
* ``rate_per_s`` (open loop): mean arrival rate; the gaps are exponential
  quantiles scaled so that the last request arrives inside the window.
* ``requests`` (closed loop): how many requests the clients draw from.
* ``note``: where the numbers come from.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np


@dataclass
class Request:
    rid: int
    prompt: np.ndarray          # int32 token ids
    out_len: int                # tokens to serve, the first one included
    arrival: float              # seconds after the window opens (open loop)
    tokens: list = field(default_factory=list)     # served token ids
    times: list = field(default_factory=list)      # host clock of each token
    admitted: float | None = None                  # host clock of prefill call


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``spec``."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        vals = spec["min"] + q * (spec["max"] + 1 - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(np.int64)


def mean_length(spec: dict, n: int = 4096) -> float:
    return float(_quantiles(spec, n).mean())


def count(mix: dict, seconds: float) -> int:
    """Requests in one run of ``seconds``."""
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    return int(mix["requests"])


def generate(mix: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    n = count(mix, seconds)
    rng = np.random.default_rng(seed)
    prompts = rng.permutation(_quantiles(mix["prompt"], n))
    outs = rng.permutation(_quantiles(mix["output"], n))
    if mix["loop"] == "open":
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q))
        # scale so the n-th arrival falls at seconds * (n - 0.5) / n: the
        # offered rate over the window is the mix's rate for every seed
        arrivals = np.cumsum(gaps) * (seconds * (n - 0.5) / n) / gaps.sum()
    elif mix["loop"] == "closed":
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [Request(rid=i,
                    prompt=rng.integers(0, vocab, int(p), dtype=np.int32),
                    out_len=int(o), arrival=float(a))
            for i, (p, o, a) in enumerate(zip(prompts, outs, arrivals))]


def percentile(values, p: float) -> float:
    """Linear-interpolation percentile (numpy's default), the one every
    reader of this benchmark shares; ``nan`` for an empty stream."""
    values = np.asarray(list(values), np.float64)
    if values.size == 0:
        return math.nan
    return float(np.percentile(values, p))
