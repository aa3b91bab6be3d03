"""The mixed-estimator cell and the epoch-rotation cell at toy size on the
CPU: the same drivers, references and readers as on the chip, with the
deployment and the traffic shrunk as data here.  The mixed cell's
control and planted faults have to come out as not correct, and its
reference alone has to follow the program's sample windows through
merges and expiry bit for bit."""
import copy
import dataclasses
import json
import time

import numpy as np
import pytest

from bench import harness
from bench.tests import toy

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
MIXED = "svc4224-poll-samples128"
ROTATE = "svc4096-rotate"
CHECKS = {"sample_slots_differing", "lsh_state_differing", "answers_missing",
          "estimate_rel_gap", "stderr_rel_gap"}
# equal-space sizes at width 256: 12,288 counter bytes
TOY_SIZES = {"reservoir": {"capacity": 438},
             "lsh_ss": {"num_hash_cols": 1, "num_buckets": 512,
                        "record_capacity": 192, "pair_capacity": 192}}


def _mixed_cell() -> harness.Cell:
    c = harness.find_cell(MIXED)
    config, traffic = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    config["tenants"] = {"sjpc": 24, "reservoir": 4, "lsh_ss": 4}
    config["sketch"]["width"] = 256
    config["sizes"] = TOY_SIZES
    traffic.update(plan_cycles=8, prefill_records=1024,
                   queries={"all_thresholds": 8},
                   pool={"records": 16384, "block": 512})
    traffic["cycle"]["tenants"]["each"] = 1
    return dataclasses.replace(c, config=config, traffic=traffic)


def _run(cell, *, trace=False):
    return harness.run_cell(cell, seed=toy.SEED, seconds=toy.SECONDS,
                            trace=trace, started=time.time())


@pytest.fixture(scope="module")
def mixed():
    return _run(_mixed_cell())


def test_mixed_result_line_holds_the_contract(mixed):
    line = harness.result_line(mixed, trace=False, device=CPU)
    json.dumps(line)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"poll_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # set-up warmed every shape the window uses
    assert line["window_compiles"] == 0
    # every poll answered both sample cohorts (4 streams each): 32
    # bootstrap replicates a stream, and two pairs-kernel dispatches (the
    # histogram and the replicates)
    assert mixed.counters["bootstrap_replicates_total"] == \
        mixed.work["polls"] * (4 + 4) * 32
    assert sum(v for k, v in mixed.counters.items()
               if k.startswith("fused_pairs_dispatch.")) == \
        2 * mixed.work["polls"]


def test_mixed_check_names_are_pinned(mixed):
    assert set(mixed.checks) == CHECKS
    assert all(v["value"] <= v["limit"] for v in mixed.checks.values())


def test_mixed_control_comes_out_not_correct(mixed):
    drv, ref = harness.driver(mixed.cell), harness.reference(mixed.cell)
    control = drv.compare(ref, mixed.cell.config, mixed.evidence,
                          control=True)
    failed = {k for k, v in control.items() if v["value"] > v["limit"]}
    # a record left out per round moves both kinds' states; float32
    # answers miss both float64 gaps
    assert failed == CHECKS - {"answers_missing"}, control


def _flipped_reservoir_tag(monkeypatch):
    from repro.estimators.reservoir import ReservoirEstimator
    real = ReservoirEstimator.ingest_rounds

    def flipped(self, *args):
        st = real(self, *args)
        return st._replace(tags=st.tags.at[:, 0].set(-1))

    monkeypatch.setattr(ReservoirEstimator, "ingest_rounds", flipped)


def _changed_pair_match(monkeypatch):
    from repro.estimators.lsh_ss import LSHSSEstimator
    real = LSHSSEstimator.ingest_rounds

    def changed(self, *args):
        st = real(self, *args)
        return st._replace(cross_sim=st.cross_sim.at[:, 0].add(1))

    monkeypatch.setattr(LSHSSEstimator, "ingest_rounds", changed)


def _stderr_off(monkeypatch):
    from repro.estimators import uncertainty
    real = uncertainty.bootstrap_pair_stderr
    monkeypatch.setattr(uncertainty, "bootstrap_pair_stderr",
                        lambda *a, **kw: real(*a, **kw) * 1.01)


FAULTS = {
    "flipped_reservoir_tag": (_flipped_reservoir_tag,
                              "sample_slots_differing"),
    "changed_pair_match_count": (_changed_pair_match, "lsh_state_differing"),
    "stderr_off_by_1pct": (_stderr_off, "stderr_rel_gap"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    plant, check = FAULTS[fault]
    plant(monkeypatch)
    run = _run(_mixed_cell())
    assert not run.correct, run.checks
    assert run.checks[check]["value"] > run.checks[check]["limit"]


def test_traced_mixed_run_reports_the_sample_metrics():
    run = _run(_mixed_cell(), trace=True)
    line = harness.result_line(run, trace=True, device=CPU)
    assert line["correct"] is True
    # the device readers (pairs.roofline_pct, idle_pct.poll) find no TPU
    # plane on the CPU and stay silent
    assert set(line["metrics"]) == {
        "query.pairs_ms", "query.bootstrap_ms", "query.strata_ms",
        "query.batch_ms", "poll.outside_batch_ms"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # the sample spans are parts of the batch
    m = line["metrics"]
    assert (m["query.pairs_ms"]["value"] + m["query.bootstrap_ms"]["value"]
            + m["query.strata_ms"]["value"]) < m["query.batch_ms"]["value"]


def test_rotation_cell_at_toy_size_is_correct():
    c = harness.find_cell(ROTATE)
    config, traffic = copy.deepcopy(c.config), copy.deepcopy(c.traffic)
    config["tenants"] = 32
    traffic.update(plan_cycles=8, pool={"records": 65536, "block": 1024},
                   compare={"tenants": 8})
    traffic["cycle"]["tenants"]["count"] = 4
    traffic["cycle"]["ops"][1]["every"] = 2      # windows expire sooner
    run = _run(dataclasses.replace(c, config=config, traffic=traffic))
    line = harness.result_line(run, trace=False, device=CPU)
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"ingest_records_per_s", "setup_s"}
    assert run.checks["counters_differing"]["value"] == 0
    # more advances than the 4 epochs a window holds: epochs expired
    advances = sum(e[0] == "advance" for e in run.evidence["history"])
    assert advances > config["service"]["window_epochs"]


def test_reference_follows_the_program_through_merges_and_expiry():
    """Sample windows of two epochs: every commit after the first advance
    refolds two slots through the priority union, and every advance from
    the second on expires one; the reference replays the same rounds on
    its own and ends with the program's states, bit for bit."""
    from repro.core.sjpc import SJPCConfig
    from repro.service import EstimationService, ServiceConfig

    cell = _mixed_cell()
    conf = copy.deepcopy(cell.config)
    conf["service"] = {"batch_rows": 64, "window_epochs": 2}
    ref_mod = harness.reference(cell)
    ref = ref_mod.Samples(conf)
    svc = EstimationService(ServiceConfig(**conf["service"]))
    svc.create_group("g", SJPCConfig(**conf["sketch"]))
    kinds = ["reservoir", "reservoir", "lsh_ss", "lsh_ss"]
    wins = []
    for i, kind in enumerate(kinds):
        svc.create_stream(f"s{i}", "g", estimator=kind)
        wins.append(ref_mod.Window(ref, kind, 2))
    rng = np.random.default_rng(11)
    done = [0] * len(kinds)
    for step in range(7):
        jobs = {}
        for i, kind in enumerate(kinds):
            if (i + step) % 3 == 0:
                continue                       # idle this flush
            recs = rng.integers(0, 30, size=(rng.integers(40, 700), 6),
                                dtype=np.uint32)
            svc.ingest(f"s{i}", recs)
            rounds = []
            for lo in range(0, len(recs), 64):
                values = np.zeros((64, 6), np.uint32)
                chunk = recs[lo:lo + 64]
                values[:len(chunk)] = chunk
                mask = (np.arange(64) < len(chunk)).astype(np.int32)
                rounds.append((done[i] + len(rounds), values, mask))
            done[i] += len(rounds)
            jobs.setdefault(kind, []).append((wins[i].open, i, rounds))
        svc.flush()
        for kind, todo in jobs.items():
            ref.ingest(kind, todo)
        for todo in jobs.values():
            for _, i, _ in todo:
                wins[i].refold()
        if step % 2:
            svc.advance_epoch()
            for w in wins:
                w.advance()
    expired = 0
    for i, kind in enumerate(kinds):
        window = svc.registry.stream(f"s{i}").window
        expired += window.epoch >= 2
        got = window.window_state()._asdict()
        want = wins[i].total
        for field, value in want.items():
            if field == "sid":
                continue
            a = np.asarray(got[field])
            keep = None
            if field in ("items", "rec_items", "rec_bucket", "same_sim",
                         "cross_sim"):
                tags = {"items": "tags", "same_sim": "same_tags",
                        "cross_sim": "cross_tags"}.get(field, "rec_tags")
                keep = want[tags] >= 0
            b = np.asarray(value)
            if keep is not None:
                a, b = a[keep], b[keep]
            assert np.array_equal(a, b), (kind, i, field)
        g, err = ref.answer(kind, want)
        served = svc.snapshot().all_thresholds(f"s{i}")
        np.testing.assert_allclose([served[k].estimate for k in served], g,
                                   rtol=1e-12)
        np.testing.assert_allclose([served[k].stderr for k in served], err,
                                   rtol=1e-12)
    assert expired == len(kinds)
