"""The control of ``correct``: run a cell with one stated guarantee
broken and print what its checks read, for each seed. Every seed must
come out not correct; the smallest reading over the seeds is the upper
reading a check's limit is set below.

    python -m benchmark.control --workload <cell> --seeds 1,2,3 --seconds 30

The broken guarantee is LWW by timestamp: every slice delivered to a
joiner has each live entry's stamp replaced by the time it arrived.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    from benchmark import harness, roofline, run as runmod

    harness.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return runmod.fail("no TPU found")
    peaks = roofline.peaks(devices[0].device_kind)
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = harness.run_cell(harness.cell_spec(args.workload), seed, args.seconds,
                               control=True, peaks=peaks)
        correct = harness.correct(out["checks"])
        print(json.dumps({"workload": args.workload, "seed": seed, "control": True,
                          "correct": correct, "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
