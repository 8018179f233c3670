"""Run one cell of the benchmark and print its result as the last line.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a run whose window is traced. The cell, its
deployment and its traffic come from ``BENCHMARK.json`` and the files
it names. Runs only on a TPU: with no accelerator, or fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from benchmark import harness, roofline

    harness.enable_compile_cache()
    spec = harness.cell_spec(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU found (JAX backend: {devices[0].platform})")
    chips = spec["cell"]["chips"]
    if len(devices) < chips:
        return fail(f"{args.workload} needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    peaks = roofline.peaks(kind)
    print(f"device: {len(devices)} x {kind} ({devices[0].platform})", flush=True)

    out = harness.run_cell(
        spec, args.seed, args.seconds,
        trace_dir=TRACE_DIR if args.trace else None, peaks=peaks,
    )
    run = out["run"]
    device = {
        "platform": devices[0].platform, "kind": kind, "count": chips,
        "memory_peak_bytes": out["memory_peak_bytes"],
    }
    if args.trace:
        names = [m["name"] for m in spec["per_layer"]]
        values = {n: harness.read_metric(n, run) for n in names}
        units = {m["name"]: m["unit"] for m in spec["per_layer"]}
        metrics = {n: {"value": v, "unit": units[n]} for n, v in values.items() if v is not None}
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
    else:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        values = dict(out["metrics"], setup_s=out["setup_s"])
        metrics = {n: {"value": values[n], "unit": u} for n, u in units.items()
                   if values.get(n) is not None}
    checks = {k: {"value": v, "limit": harness.LIMIT} for k, v in out["checks"].items()}
    correct = harness.correct(out["checks"])
    result = {
        "correct": correct, "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": device,
    }
    if args.trace:
        result["breakdown"] = run.trace.breakdown()
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if not __package__:
        sys.path.insert(0, ROOT)
    sys.exit(main())
