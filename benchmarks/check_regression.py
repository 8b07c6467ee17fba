"""Perf-regression gate: compare a fresh BENCH record against the committed
baseline.

    PYTHONPATH=src python -m benchmarks.check_regression \
        --baseline benchmarks/baseline.json --candidate bench.json

Metric specs are **direction-aware** — ``(json path, label, direction)``
where direction is ``"lower"`` (kernel counts, modeled times: growth beyond
tolerance fails), ``"higher"`` (throughputs: a drop beyond tolerance
fails), ``"exact"`` (structural counts that must not drift at all),
``"positive"`` (liveness gates: the candidate value must be > 0 regardless
of the baseline — a zero prefix-cache hit rate or zero stitched-prefill
kernels means the feature silently stopped engaging), or ``"max:<bound>"``
(absolute ceiling judged on the candidate alone — verifier findings must
stay at 0, verify overhead under 5% of the cold compile).

Gated sections:

* per-workload **stitched kernel count** and **modeled stitch step time**
  (lower) — the paper's kernel-compression win eroding / a slower plan;
* **training** — stitched kernel count / modeled time of the traced
  backward graph, and the packed AdamW+clip update's kernel count (1 when
  the whole multi-tensor update shares a single packed kernel);
* **serving** — continuous- and static-mode ``tokens_per_sec`` (higher):
  a throughput drop beyond tolerance fails, an improvement passes.  These
  are the only *wall-clock* gated metrics: best-of-reps in the harness
  damps within-machine jitter, and ``--serving-tolerance`` (default: the
  global tolerance) lets CI widen just these against a baseline recorded
  on different hardware without loosening the deterministic gates.  The
  prefix-heavy sub-run adds two liveness gates (positive): the
  prefix-cache hit rate and the stitched-prefill kernel count;
* **packing** — horizontal FFD packing on the wide-expert MoE block:
  packed-plan kernel count (lower), packs formed / subgraphs packed
  (positive liveness — a zero means the packer silently stopped
  engaging), and the packed-vs-unpacked kernel-reduction ratio (higher);
* **verify** — the static verifier's total ERROR findings across workload
  plans (``max:0`` — any finding on a healthy build is a verifier or
  compiler bug) and its worst in-compile overhead fraction (``max:0.05``);
* **sharding** — per-shard stitched kernel counts / modeled times of the
  mesh-placed backward and packed-update graphs (lower), and the count of
  distinct mesh-keyed cache entries (exact: losing a placement means two
  meshes started sharing one plan).

A candidate fails when a gated metric moves beyond ``--tolerance`` (default
10%) in the bad direction.  Workloads present only in the candidate are
reported as new (not gated); workloads or sections missing from the
candidate fail the gate — losing coverage silently is itself a regression.
"""

from __future__ import annotations

import argparse
import json
import sys

TOLERANCE = 0.10

# (json path inside workloads[name], label, direction)
METRICS = (
    (("kernels", "stitch"), "stitched_kernels", "lower"),
    (("modeled_time_s", "stitch"), "modeled_stitch_time_s", "lower"),
)

# json paths inside the top-level "training" section
TRAINING_METRICS = (
    (("grad", "kernels", "stitch"), "grad_stitched_kernels", "lower"),
    (("grad", "modeled_time_s", "stitch"), "grad_modeled_stitch_time_s", "lower"),
    (("packed_update", "kernels", "stitch"), "packed_update_kernels", "lower"),
    (("packed_update", "modeled_time_s", "stitch"),
     "packed_update_modeled_time_s", "lower"),
)

# json paths inside the top-level "serving" section — throughputs, so a
# DROP beyond tolerance is the regression
SERVING_METRICS = (
    (("continuous", "tokens_per_sec"), "continuous_tokens_per_sec", "higher"),
    (("static", "tokens_per_sec"), "static_tokens_per_sec", "higher"),
    # liveness, not wall clock: the prefix cache must actually hit and the
    # bucketed prefills must actually carry stitched plans
    (("prefix", "prefix_cache", "hit_rate"), "prefix_cache_hit_rate",
     "positive"),
    (("prefix", "prefill", "n_kernels"), "prefill_stitched_kernels",
     "positive"),
)

# json paths inside the top-level "compute_stitching" section — kernel
# counts after GEMM admission into stitched partitions.
# Deterministic (no wall clock): the block must stay collapsed and both
# plans must actually carry Pallas groups.
COMPUTE_METRICS = (
    (("block_fn", "n_kernels"), "block_fn_kernels", "lower"),
    (("block_fn", "pallas_groups"), "block_fn_pallas_groups", "positive"),
    (("decode", "n_kernels"), "decode_kernels", "lower"),
    (("decode", "pallas_groups"), "decode_pallas_groups", "positive"),
)

# json paths inside the top-level "packing" section — horizontal FFD
# packing on the wide-expert MoE block.  Deterministic: the packed plan's
# kernel count must not grow and the packer must actually form packs (a
# zero means horizontal packing silently stopped engaging).
PACKING_METRICS = (
    (("packed", "n_kernels"), "packed_kernels", "lower"),
    (("packed", "packs"), "packs_formed", "positive"),
    (("packed", "packed_subgraphs"), "packed_subgraphs", "positive"),
    (("kernel_reduction",), "pack_kernel_reduction", "higher"),
)

# The "measured" section is schema-checked, not value-gated: interpret-mode
# wall clock is too noisy to gate, but losing the measured-timing record
# entirely (the timer silently disabled, the section dropped from the
# harness) must fail loudly — it is the perf trajectory's ground truth.
MEASURED_REQUIRED_KEYS = ("rmsnorm_us", "softmax_us", "exec")
MEASURED_EXEC_KEYS = ("measured_s", "modeled_time_s", "calls")

# json paths inside the top-level "verify" section — the static verifier
# must stay clean (zero ERROR findings across every workload plan) and
# cheap (in-compile verification bounded at 5% of the cold compile)
VERIFY_METRICS = (
    (("findings_total",), "verify_findings_total", "max:0"),
    (("max_overhead_frac",), "verify_max_overhead_frac", "max:0.05"),
)

# json paths inside the top-level "sharding" section
SHARDING_METRICS = (
    (("grad_local", "kernels", "stitch"), "grad_local_stitched_kernels", "lower"),
    (("grad_local", "modeled_time_s", "stitch"),
     "grad_local_modeled_stitch_time_s", "lower"),
    (("packed_local", "kernels", "stitch"), "packed_local_kernels", "lower"),
    (("cache", "mesh_keyed_entries"), "mesh_keyed_entries", "exact"),
)


def _get(d: dict, path) -> float | None:
    for k in path:
        if not isinstance(d, dict) or k not in d:
            return None
        d = d[k]
    return d


def _gate_metric(b, c, label, direction, tolerance, failures, lines,
                 row_name):
    """One direction-aware comparison; appends to failures/lines."""
    if direction == "positive":
        # liveness gates judge the candidate alone; a baseline that
        # predates the metric skips it (same rule as whole sections),
        # but a candidate that lost it is lost coverage
        if b is None and c is None:
            return
        if c is None:
            failures.append(f"{row_name}.{label}: metric missing "
                            f"from candidate (baseline={b:g})")
            return
        verdict = "OK"
        if c <= 0:
            verdict = "REGRESSION"
            failures.append(f"{row_name}.{label}: candidate {c:g} "
                            f"(must be > 0)")
        b_str = f"{b:g}" if b is not None else "-"
        lines.append(f"{row_name},{label},{b_str},{c:g},-,{verdict}")
        return
    if direction.startswith("max:"):
        # absolute ceiling on the candidate; the baseline only decides
        # whether a missing candidate value is lost coverage
        bound = float(direction[4:])
        if b is None and c is None:
            return
        if c is None:
            failures.append(f"{row_name}.{label}: metric missing "
                            f"from candidate (bound <= {bound:g})")
            return
        verdict = "OK"
        if c > bound:
            verdict = "REGRESSION"
            failures.append(f"{row_name}.{label}: candidate {c:g} "
                            f"exceeds bound {bound:g}")
        b_str = f"{b:g}" if b is not None else "-"
        lines.append(f"{row_name},{label},{b_str},{c:g},-,{verdict}")
        return
    if b is None or c is None:
        failures.append(f"{row_name}.{label}: metric missing "
                        f"(baseline={b}, candidate={c})")
        return
    ratio = c / b if b else float("inf") if c else 1.0
    verdict = "OK"
    if direction == "lower" and ratio > 1.0 + tolerance:
        verdict = "REGRESSION"
        failures.append(
            f"{row_name}.{label}: {b:g} -> {c:g} "
            f"(+{100 * (ratio - 1):.1f}% > {100 * tolerance:.0f}%)")
    elif direction == "higher" and ratio < 1.0 - tolerance:
        verdict = "REGRESSION"
        failures.append(
            f"{row_name}.{label}: {b:g} -> {c:g} "
            f"(-{100 * (1 - ratio):.1f}% drop > {100 * tolerance:.0f}%)")
    elif direction == "exact" and c != b:
        verdict = "REGRESSION"
        failures.append(f"{row_name}.{label}: {b:g} -> {c:g} "
                        f"(must match exactly)")
    lines.append(f"{row_name},{label},{b:g},{c:g},{ratio:.3f},{verdict}")


def _gate_section(baseline: dict, candidate: dict, section: str, specs,
                  tolerance, failures, lines) -> None:
    """Gate one top-level record section; a section in the baseline but not
    the candidate is lost coverage (fails)."""
    base = baseline.get(section)
    if base is None:
        return                            # baseline predates this section
    cand = candidate.get(section)
    if cand is None:
        failures.append(f"{section}: section missing from candidate record")
        return
    for path, label, direction in specs:
        _gate_metric(_get(base, path), _get(cand, path), label, direction,
                     tolerance, failures, lines, section)


def compare(baseline: dict, candidate: dict, tolerance: float = TOLERANCE,
            serving_tolerance: float | None = None):
    """Returns (failures, lines): failure strings (empty = pass) and the
    full per-metric report.  ``serving_tolerance`` overrides ``tolerance``
    for the wall-clock serving section only (cross-machine baselines)."""
    failures, lines = [], []
    base_wl = baseline.get("workloads", {})
    cand_wl = candidate.get("workloads", {})
    for name in sorted(base_wl):
        if name not in cand_wl:
            failures.append(f"{name}: missing from candidate record")
            continue
        for path, label, direction in METRICS:
            _gate_metric(_get(base_wl[name], path), _get(cand_wl[name], path),
                         label, direction, tolerance, failures, lines, name)
    for name in sorted(set(cand_wl) - set(base_wl)):
        lines.append(f"{name},-,-,-,-,NEW (not gated)")

    _gate_section(baseline, candidate, "training", TRAINING_METRICS,
                  tolerance, failures, lines)
    _gate_section(baseline, candidate, "serving", SERVING_METRICS,
                  tolerance if serving_tolerance is None else serving_tolerance,
                  failures, lines)
    _gate_section(baseline, candidate, "sharding", SHARDING_METRICS,
                  tolerance, failures, lines)
    _gate_section(baseline, candidate, "verify", VERIFY_METRICS,
                  tolerance, failures, lines)
    _gate_section(baseline, candidate, "compute_stitching", COMPUTE_METRICS,
                  tolerance, failures, lines)
    _gate_section(baseline, candidate, "packing", PACKING_METRICS,
                  tolerance, failures, lines)
    check_measured_schema(baseline, candidate, failures, lines)
    return failures, lines


def check_measured_schema(baseline: dict, candidate: dict, failures,
                          lines) -> None:
    """Fail loudly when the candidate lacks the measured-timing section (or
    its required keys); the values themselves stay ungated."""
    if not isinstance(baseline.get("measured"), dict):
        return                            # baseline predates this section
    meas = candidate.get("measured")
    if not isinstance(meas, dict):
        failures.append("measured: section missing from candidate record "
                        "(measured-kernel timing was not captured)")
        return
    missing = [k for k in MEASURED_REQUIRED_KEYS if k not in meas]
    exec_rec = meas.get("exec")
    if isinstance(exec_rec, dict):
        missing += [f"exec.{k}" for k in MEASURED_EXEC_KEYS
                    if k not in exec_rec]
    if missing:
        failures.append(f"measured: keys missing from candidate record: "
                        f"{', '.join(missing)}")
        return
    lines.append("measured,schema,-,-,-,OK (values not gated)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default="benchmarks/baseline.json")
    ap.add_argument("--candidate", required=True)
    ap.add_argument("--tolerance", type=float, default=TOLERANCE)
    ap.add_argument("--serving-tolerance", type=float, default=None,
                    help="wider tolerance for the wall-clock serving "
                         "throughput gate only (default: --tolerance); use "
                         "when the committed baseline was recorded on "
                         "different hardware")
    args = ap.parse_args(argv)

    with open(args.baseline) as f:
        baseline = json.load(f)
    with open(args.candidate) as f:
        candidate = json.load(f)

    failures, lines = compare(baseline, candidate, args.tolerance,
                              serving_tolerance=args.serving_tolerance)
    print("workload,metric,baseline,candidate,ratio,verdict")
    for line in lines:
        print(line)
    if failures:
        print(f"\nFAIL — {len(failures)} perf regression(s) "
              f"beyond {100 * args.tolerance:.0f}%:", file=sys.stderr)
        for f_ in failures:
            print(f"  {f_}", file=sys.stderr)
        return 1
    print(f"\nPASS — no metric regressed beyond {100 * args.tolerance:.0f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
