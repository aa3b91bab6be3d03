"""A configuration, a traffic mix, a tenant pick, a cycle op and a per-layer
metric are added as new files and new ``BENCHMARK.json`` entries only: the
toy entries below exist in these tests alone and run through the unchanged
harness, generator, driver and reference."""
import json
import shutil
import time

from bench import harness
from bench.harness import CHECKOUT

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _checkout(tmp_path, *, tenants, window_epochs, traffic):
    """A copy of the benchmark with one toy configuration, one toy mix and
    one toy cell ``toy-cell`` added beside the committed ones."""
    shutil.copytree(CHECKOUT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    config = json.loads(
        (CHECKOUT / "bench/configs/sjpc-paper-4096.json").read_text())
    config.update(name="toy-sjpc", tenants=tenants)
    config["service"]["window_epochs"] = window_epochs
    (tmp_path / "bench/configs/toy-sjpc.json").write_text(json.dumps(config))
    (tmp_path / "bench/traffic/toy-mix.json").write_text(json.dumps(traffic))
    spec["configs"].append({"name": "toy-sjpc", "source": "test",
                            "file": "bench/configs/toy-sjpc.json",
                            "reduced": ["tenants"], "why": "test"})
    spec["workloads"].append({"name": "toy-cell", "config": "toy-sjpc",
                              "traffic": "toy-mix", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("toy-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return spec


def _mix(pick, ops, **extra):
    base = json.loads(
        (CHECKOUT / "bench/traffic/ingest-active256.json").read_text())
    base.update(plan_cycles=6, warmup_cycles=1, compare={"tenants": 6},
                pool={"records": 49152, "block": 1024}, **extra)
    base["cycle"].update(tenants=pick, ops=ops)
    return base


def _run(tmp_path):
    cell = harness.find_cell("toy-cell", tmp_path)
    return harness.run_cell(cell, seed=2 ** 31 + 7, seconds=1.0, trace=False,
                            started=time.time())


def test_new_cell_with_its_own_pick_op_and_metric(tmp_path):
    spec = _checkout(tmp_path, tenants=48, window_epochs=4, traffic=_mix(
        {"pick": "toy_first", "count": 3}, ["toy_flush_twice"]))
    (tmp_path / "bench/picks/toy_first.py").write_text(
        "import numpy as np\n"
        "def picks(rng, cycle, *, cycles, tenants, self_tenants, "
        "join_pairs):\n"
        "    k = cycle['tenants']['count']\n"
        "    return [(np.arange(c % 5, c % 5 + k), np.full(k, 700))\n"
        "            for c in range(cycles)]\n")
    (tmp_path / "bench/ops/toy_flush_twice.py").write_text(
        "def run(svc):\n    svc.flush()\n    svc.flush()\n"
        "    return {'commit': True}\n")
    (tmp_path / "bench/metrics/toy.cycles.py").write_text(
        "def read(run):\n    return run.work['cycles']\n")
    spec["per_layer"].append({"name": "toy.cycles", "unit": "cycles",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": "setup_s",
                              "workloads": ["toy-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    # the toy op is not timed: the rate still counts its commits
    run = _run(tmp_path)
    plain = harness.result_line(run, trace=False, device=CPU)
    assert plain["correct"] is True, plain["checks"]
    assert set(plain["metrics"]) == {"ingest_records_per_s", "setup_s"}
    assert run.work["records"] == 3 * 700 * run.work["cycles"]
    layered = harness.result_line(run, trace=True, device=CPU)
    assert layered["metrics"]["toy.cycles"]["value"] == run.work["cycles"] >= 1


def test_skewed_mix_with_epoch_rotation_is_data_only(tmp_path):
    """The committed ``zipf`` pick and ``advance_epoch`` op, driven by a mix
    file alone; a two-epoch window expires data every other cycle, and the
    reference follows the expiry bit for bit."""
    _checkout(tmp_path, tenants=40, window_epochs=2, traffic=_mix(
        {"pick": "zipf", "active": 12, "exponent": 1.1},
        ["flush", {"op": "advance_epoch", "every": 2}], **{"cycle": {
            "tenants": {}, "records": 3000, "ops": [],
            "timed": ["flush"]}}))
    run = _run(tmp_path)
    assert run.correct, run.checks
    assert run.checks["counters_differing"]["value"] == 0
    assert run.work["records"] == 3000 * run.work["cycles"]
