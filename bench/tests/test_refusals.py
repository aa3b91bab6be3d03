"""The command measures nothing it cannot measure: without a TPU, with too
few chips, on a device kind without published peaks, or without the
program beside the benchmark, it exits non-zero and prints no result."""
import shutil
import subprocess
import sys
import types

import pytest

from bench import run as bench_run
from bench.harness import CHECKOUT


def _jax(platform, kind, count):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return types.SimpleNamespace(devices=lambda: [dev] * count)


@pytest.mark.parametrize("platform,kind,count,chips", [
    ("cpu", "cpu", 1, 1),                 # no accelerator
    ("tpu", "TPU v5 lite", 1, 4),         # fewer chips than the cell asks
    ("tpu", "TPU v99", 1, 1),             # no published peaks
])
def test_check_devices_refuses(platform, kind, count, chips):
    with pytest.raises(bench_run.Refused):
        bench_run.check_devices(_jax(platform, kind, count), chips)


def test_check_devices_names_the_device():
    got = bench_run.check_devices(_jax("tpu", "TPU v5 lite", 4), 1)
    assert got == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def test_command_on_the_cpu_prints_no_result(capsys):
    rc = bench_run.main(["--workload", "svc4096-ingest-active256",
                         "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_command_without_the_program_prints_no_result(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "svc4096-ingest-active256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert proc.stdout == ""
