"""The trace reduction: on hand-made events, and on a small trace recorded
on a TPU v5e (``fixtures/decode_v5e.xplane.pb``: two stitched decode steps
and one prefill of full-width qwen3-1.7b, each inside a harness
annotation).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "decode_v5e.xplane.pb"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_busy_idle_and_gaps_by_annotation():
    ops = [[tr.Op("a", 100, 50), tr.Op("b", 120, 60), tr.Op("c", 300, 100)]]
    host = [("decode", 100, 450), ("prefill", 450, 500)]
    s = tr.reduce(ops, host)
    assert s.window_s == pytest.approx(400e-9)
    assert s.busy_s == pytest.approx((80 + 100) * 1e-9)       # [100,180) u [300,400)
    # a gap goes whole to the annotation open at its midpoint
    assert s.idle["decode"] == pytest.approx(120e-9)
    assert s.idle["prefill"] == pytest.approx(100e-9)
    assert s.host_only["decode"] == [pytest.approx(170e-9)]
    assert s.ops["b"] == [pytest.approx(60e-9), 1]
    assert s.breakdown()["device_ops"][0][0] == "c"


def test_innermost_annotation_names_a_gap():
    ops = [[tr.Op("a", 0, 10), tr.Op("b", 90, 10)]]
    host = [("admit", 0, 100), ("prefill", 20, 80)]
    assert set(tr.reduce(ops, host).idle) == {"prefill"}


def test_ops_and_bytes_from_hlo_text():
    dot = ("%dot.1 = bf16[16,6144]{1,0} dot(bf16[16,2048]{1,0} %p0, "
           "bf16[2048,6144]{1,0} %p1), lhs_contracting_dims={1}")
    flops, byts = tr.ops_and_bytes(dot)
    assert flops == pytest.approx(2 * 16 * 6144 * 2048)
    assert byts == 2 * (16 * 6144 + 16 * 2048 + 2048 * 6144)
    add = "%add.3 = f32[8,128]{1,0} add(f32[8,128]{1,0} %a, f32[8,128]{1,0} %b)"
    assert tr.ops_and_bytes(add) == (8 * 128, 3 * 4 * 8 * 128)
    assert tr.ops_and_bytes("%copy-start") is None


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_recorded_v5e_trace():
    chips, host = tr.load(FIXTURE)
    assert len(chips) == 1 and len(chips[0]) > 100
    assert {n for n, _, _ in host} == {"decode", "prefill"}
    s = tr.reduce(chips, host, PEAKS)
    assert 0 < s.busy_s < s.window_s
    assert len(s.host_only["decode"]) == 2
    assert 0 < s.roofline <= 100
    assert s.roofline_s > 0.5 * s.busy_s
