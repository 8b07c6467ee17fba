"""The traffic generator: every seed gets the same work."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402


def _mix(name):
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def test_every_seed_gets_the_same_sizes_and_window():
    mix = _mix("chat")
    a = loadgen.generate(mix, 1, 50, 1000)
    b = loadgen.generate(mix, 2 ** 31 + 5, 50, 1000)
    for key in (lambda r: len(r.prompt), lambda r: r.out_len):
        assert sorted(map(key, a)) == sorted(map(key, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert a[-1].arrival < 50 and b[-1].arrival < 50
    assert len(a) == round(mix["rate_per_s"] * 50)


def test_the_seed_orders_the_schedule_and_draws_the_tokens():
    mix = _mix("chat")
    a = loadgen.generate(mix, 1, 50, 1000)
    b = loadgen.generate(mix, 2, 50, 1000)
    assert [(len(r.prompt), r.out_len, r.arrival) for r in a] != \
        [(len(r.prompt), r.out_len, r.arrival) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    c = loadgen.generate(mix, 1, 50, 1000)
    assert [(len(r.prompt), r.out_len, r.arrival) for r in a] == \
        [(len(r.prompt), r.out_len, r.arrival) for r in c]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_lengths_stay_in_the_mix_bounds():
    for name in ("chat", "chat-closed"):
        mix = _mix(name)
        reqs = loadgen.generate(mix, 3, 50, 1000)
        assert all(mix["prompt"]["min"] <= len(r.prompt) <= mix["prompt"]["max"]
                   for r in reqs)
        assert all(mix["output"]["min"] <= r.out_len <= mix["output"]["max"]
                   for r in reqs)
