"""The readings a cell's limits are set from: for each seed, the numbers the
program gives against the plain reference, and the numbers the control
gives in its place (see ``compare`` of the cell's driver), all in one
process so set-up compiles once.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 5

One JSON line per seed on standard output.  The benchmark's own runs never
run the control; this is for setting and re-checking limits on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from run import ROOT, Refused, check_devices, enable_compile_cache  # noqa: I001
from bench import harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload, ROOT)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    try:
        check_devices(jax, cell.chips)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(jax)
    drv, ref = harness.driver(cell), harness.reference(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        run = harness.run_cell(cell, seed=seed, seconds=args.seconds,
                               trace=False, started=t0)
        t1 = time.time()
        control = drv.compare(ref, cell.config, run.evidence, control=True)
        print(json.dumps({
            "seed": seed, "setup_s": run.setup_s, "window_s": run.window_s,
            "work": run.work, "compare_s": time.time() - t1,
            "program": {k: v["value"] for k, v in run.checks.items()},
            "control": {k: v["value"] for k, v in control.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
