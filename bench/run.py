"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips the
cell asks for.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number compared
with the reference beside its limit, also printed last on standard error).
Exits non-zero and prints no result without a TPU, with fewer chips than
the cell asks for, on a device kind the peaks table does not know, or
without the program (``src/repro``) beside the benchmark.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.peaks import peaks_for  # noqa: E402


class Refused(RuntimeError):
    """The run cannot be measured here."""


def check_devices(jax, chips: int) -> dict:
    """The device the result names; refuses anything but enough TPUs of a
    kind with published peaks."""
    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise Refused(f"no TPU: JAX's first device is {first.platform!r}")
    if len(devices) < chips:
        raise Refused(f"{len(devices)} TPU device(s); the cell needs {chips}")
    try:
        peaks_for(first.device_kind)
    except ValueError as e:
        raise Refused(str(e)) from None
    return {"platform": first.platform, "kind": first.device_kind,
            "count": chips}


def enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    started = harness.process_start_time()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.find_cell(args.workload, ROOT)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no program beside the benchmark: {ROOT / 'src' / 'repro'} "
              "is missing", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    try:
        device = check_devices(jax, cell.chips)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    enable_compile_cache(jax)

    run = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace), started=started)
    line = harness.result_line(run, trace=bool(args.trace), device=device)
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
