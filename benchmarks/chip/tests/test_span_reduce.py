"""The span reduction (``span_reduce``): device idle time cut at the
program's span boundaries, on hand-made events and on the recorded v5e
trace; and a pin of what ``trace_reduce`` and the trace-read metrics give
on that trace.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q
"""

from __future__ import annotations

import importlib.util
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import span_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "decode_v5e.xplane.pb"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MS = 1_000_000          # ns


def _decode_step(t0: int, launches: int) -> list[sr.Span]:
    """The spans of one served decode step starting at ``t0`` (ms)."""
    at = [("serve.decode", 0, 100), ("serve.decode.prep", 0, 10),
          ("exec.decode_step", 10, 60), ("exec.check", 10, 15),
          ("exec.walk", 15, 55), ("serve.decode.sample", 60, 65),
          ("serve.decode.readback", 65, 90), ("serve.decode.commit", 90, 98)]
    spans = [sr.Span(n, (t0 + a) * MS, (t0 + b) * MS) for n, a, b in at]
    spans[4].args["launches"] = launches
    return spans


def _trace():
    """Two decode steps and a prefill.  The device runs [20, 80) of the
    first step's walk, so the stretch [80, 125) runs from its read-back
    through its commit and the next step's prep and check into its walk."""
    host = [("decode", 0, 100 * MS), ("decode", 100 * MS, 200 * MS),
            ("prefill", 200 * MS, 300 * MS)]
    spans = _decode_step(0, 29) + _decode_step(100, 31) + [
        sr.Span("serve.prefill", 200 * MS, 280 * MS),
        sr.Span("exec.prefill", 205 * MS, 275 * MS),
        sr.Span("exec.check", 205 * MS, 210 * MS),
        sr.Span("exec.walk", 210 * MS, 270 * MS, {"launches": 40}),
        sr.Span("serve.prefill.readback", 280 * MS, 295 * MS)]
    ops = [[tr.Op("a", 20 * MS, 60 * MS), tr.Op("b", 125 * MS, 55 * MS),
            tr.Op("c", 230 * MS, 60 * MS)]]
    return ops, host, spans


def test_idle_stretch_is_cut_at_span_boundaries():
    ops, host, spans = _trace()
    s = sr.reduce(ops, host, spans, modules=[20 * MS, 125 * MS, 230 * MS])
    first, second, prefill = s.steps
    ms = {k: pytest.approx(v * 1e-3) for k, v in {
        "serve.decode.prep": 10, "exec.check": 5, "exec.walk": 5,
        "serve.decode.readback": 10, "serve.decode.commit": 8,
        "serve.decode": 2}.items()}
    assert dict(first.idle) == ms
    # [80, 125) split: readback 10, commit 8, serve.decode 2, then the
    # second step's prep 10, check 5 and walk 10
    assert second.idle["serve.decode.prep"] == pytest.approx(10e-3)
    assert second.idle["exec.walk"] == pytest.approx(10e-3)
    assert second.idle["serve.decode.readback"] == pytest.approx(10e-3)
    assert prefill.idle["exec.walk"] == pytest.approx(20e-3)
    assert (first.launches, second.launches) == ([29], [31])
    assert [st.modules for st in s.steps] == [1, 1, 1]
    # each step's idle, its parts and what no child covers, is what the
    # harness's annotation holds with no op on the device
    host_only = tr.reduce(ops, host).host_only["decode"]
    assert [sum(st.idle.values()) for st in (first, second)] == [
        pytest.approx(v) for v in host_only]
    assert s.idle["prefill/-"] == pytest.approx(5e-3)    # after the span
    assert s.idle["decode/serve.decode.commit"] == pytest.approx(16e-3)


def test_metrics_are_medians_per_step():
    ops, host, spans = _trace()
    m = sr.reduce(ops, host, spans).metrics()
    assert m["decode_walk_ms"] == pytest.approx(7.5)          # 5 and 10
    assert m["decode_prep_ms"] == pytest.approx(23.0)         # 10 + 5 + 8
    assert m["decode_sync_ms"] == pytest.approx(10.0)
    assert m["decode_launches"] == pytest.approx(30.0)
    assert m["prefill_walk_ms"] == pytest.approx(20.0)


def test_spans_outside_a_step_go_to_no_metric():
    ops = [[tr.Op("a", 50 * MS, 10 * MS)]]
    host = [("insert", 0, 100 * MS)]
    spans = [sr.Span("serve.insert", 0, 100 * MS)]
    s = sr.reduce(ops, host, spans)
    assert s.steps == []
    assert s.idle == {"insert/serve.insert": pytest.approx(90e-3)}
    assert set(s.metrics().values()) == {None}


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_recorded_trace_has_no_program_spans():
    spans, modules = sr.load(FIXTURE)
    assert spans == []
    assert len(modules) == 95             # 29 per decode step, 37 prefill
    s = sr.reduce_file(FIXTURE)
    assert set(s.metrics().values()) == {None}
    assert s.idle_spans() == []


@pytest.mark.skipif(not FIXTURE.exists(), reason="fixture not recorded")
def test_trace_read_metrics_pinned_on_recorded_trace():
    """What ``trace_reduce`` and the metrics that read it give on the
    recorded trace, to the bit."""
    chips, host = tr.load(FIXTURE)
    s = tr.reduce(chips, host, PEAKS)
    assert (s.busy_s, s.window_s) == (0.06442236, 0.104996644)
    assert s.idle == {"decode": 0.02514058199999998,
                      "prefill": 0.015433702000000004}
    assert s.host_only == {"decode": [0.014064620000000002, 0.013510476],
                           "prefill": [0.012990058]}
    b = s.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert b["idle_gaps"] == [["decode", 0.02514058199999998],
                              ["prefill", 0.015433702000000004]]
    assert b["device_ops"][:3] == [
        ["jit_scan(4519957622893761636)/broadcast_select_fusion.4",
         0.014419306000000002],
        ["jit_transpose(10079967051918073833)/copy", 0.005523887000000001],
        ["jit_scan(4519957622893761636)/bitcast_add_fusion.4", 0.003825545]]
    assert len(b["device_ops"]) == 10
    win = types.SimpleNamespace(trace=s)
    pinned = {"decode_host_ms": 13.787548000000001,
              "idle_share.decode": 38.643410355096684,
              "kernel_roofline.decode": 80.40680317192705}
    for name, value in pinned.items():
        spec = importlib.util.spec_from_file_location(
            f"metric_{name}", HERE / "metrics" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(win) == value, name
