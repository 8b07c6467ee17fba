"""Chip benchmark: one run of one cell.

    python benchmarks/chip/harness.py --workload qwen3-1.7b.chat \\
        --seed 7 --seconds 30 --trace 0

Reads the cell from ``workloads/<name>.json``, its model configuration from
``configs/<config>.json`` and its traffic mix from ``traffic/<traffic>.json``
(all beside this file), makes the weights and the requests from ``--seed``,
sets up and warms the system under test (``repro``, from the checkout's
``src``), measures for ``--seconds``, checks what the timed path produced
against the plain reference, and prints one JSON object as the last line of
standard output.  With ``--trace 1`` the window runs under the JAX profiler
and the line carries the cell's per-layer metrics instead of its end-to-end
ones.

Which metrics a cell reports comes from ``BENCHMARK.json`` at the checkout
root; each metric is read by ``metrics/<name>.py``.  A cell that is not
listed there (such as the ``.jit`` variants) names its end-to-end metrics in
its workload file.

Adding a cell, a configuration, a traffic mix or a metric is adding files,
and editing none: nothing here names one.  A new configuration brings
``configs/<name>.json`` (the published ``config.json`` with its
``model_type``, cut as its ``reduced`` says), ``workloads/<cell>.json`` and,
if new, ``traffic/<mix>.json``; where no ``arch/<model_type>.py`` serves its
``model_type`` yet, it brings that module and the module's plain reference
under ``reference/``.  The module is everything the harness knows of an
architecture: weights layout, the program's model, what traffic it can
serve, the reference and its control, and the FLOP count (``arch``).  A
configuration whose ``model_type`` has no module fails before any device
work, naming the file expected.

Runs only on a TPU: on any other platform, or with fewer chips than the
cell asks for, it exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import arch  # noqa: E402

EXIT_NO_CHIP = 3


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """(workload, config, traffic mix) for the cell ``name``; raises
    ``arch.MissingArch`` where no module serves the configuration's
    architecture."""
    workload = json.loads((HERE / "workloads" / f"{name}.json").read_text())
    cfg = json.loads((HERE / "configs" / f"{workload['config']}.json").read_text())
    mix = json.loads((HERE / "traffic" / f"{workload['traffic']}.json").read_text())
    arch.load(cfg)
    workload["engine"] = dict(workload["engine"])
    workload["engine"]["stitch_cache_dir"] = str(HERE / ".cache" / "stitch")
    return workload, cfg, mix


def cell_metrics(name: str, workload: dict, bench: dict | None
                 ) -> tuple[list[dict], list[dict]]:
    """(end-to-end, per-layer) metric entries that the cell reports."""
    bench = bench or {"end_to_end": [], "per_layer": [], "workloads": []}
    listed = any(w["name"] == name for w in bench["workloads"])

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    if listed:
        e2e = [m for m in bench["end_to_end"] if mine(m)]
    else:
        e2e = [{"name": n, "unit": _reader(n).UNIT}
               for n in workload["end_to_end"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in names
                 and (name in m.get("workloads", ()) or
                      ("workloads" not in m))]
    return e2e, per_layer


def _reader(name: str):
    """``metrics/<name>.py``: its ``read(window)`` gives the value, or None
    where it finds nothing to read; ``UNIT`` names the unit."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    workload, cfg, mix = load_cell(args.workload)
    bench_path = ROOT / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text()) if bench_path.exists() else None

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"harness: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devices) < workload["chips"]:
        print(f"harness: the cell needs {workload['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP
    from repro.launch.hostenv import use_compile_cache

    use_compile_cache()
    # cache every compile, the quick ones of the eager stitched walk too, so
    # that a cell's runs after its first in a checkout compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(args.workload, workload, cfg, mix, bench, args.seed,
                      args.seconds, bool(args.trace))
    for k, v in result["compared"].items():
        print(f"compared {k}: {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def run_cell(name: str, workload: dict, cfg: dict, mix: dict, bench,
             seed: int, seconds: float, trace: bool, *, faults=None,
             peaks: dict | None = None) -> dict:
    """One run of a cell, past the look for a chip: the result's dict."""
    import jax

    import serving

    e2e, per_layer = cell_metrics(name, workload, bench)
    trace_dir = None
    if trace:
        trace_dir = HERE / ".traces" / name
        shutil.rmtree(trace_dir, ignore_errors=True)
    win, correct, compared, attempted, failed, mem = serving.run(
        workload, cfg, mix, seed, seconds, trace_dir, T_START,
        workload["check"], faults=faults)
    win.peaks = peaks or _peaks(jax.devices()[0].device_kind)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": attempted, "failed": failed}
    breakdown = None
    if trace:
        import trace_reduce

        win.trace = trace_reduce.reduce_dir(trace_dir, win.peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
        breakdown = win.trace.breakdown()
    metrics = {}
    for m in (per_layer if trace else e2e):
        v = _reader(m["name"]).read(win)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["run"] = {"compiles_in_window": win.compiles,
                  "land_s": win.land_s, **win.checked}
    out["compared"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in compared.items()}
    return out


def _peaks(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


if __name__ == "__main__":
    sys.exit(main())
