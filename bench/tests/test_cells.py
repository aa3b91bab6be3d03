"""Each service cell's set-up, loop and comparison at toy size on the CPU,
ending in the contract's result line; and its control, which has to come
out as not correct."""
import json

import pytest

from bench import generate, harness
from bench.tests import toy

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
END_TO_END = {toy.ZIPF: "ingest_records_per_s",
              toy.INGEST: "ingest_records_per_s", toy.POLL: "poll_p95_ms"}


@pytest.fixture(scope="module", params=[toy.ZIPF, toy.INGEST, toy.POLL])
def plain(request):
    return toy.run(request.param)


def test_result_line_holds_the_contract_keys(plain):
    line = harness.result_line(plain, trace=False, device=CPU)
    json.dumps(line)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    metrics = line["metrics"]
    assert set(metrics) == {END_TO_END[plain.cell.name], "setup_s"}
    assert all(m["value"] > 0 for m in metrics.values())
    assert "memory_peak_bytes" in line["device"]
    # set-up warmed every shape the window uses
    assert line["window_compiles"] == 0


def test_every_compared_number_has_a_limit(plain):
    names = {"counters_differing", "n_differing"}
    if plain.cell.name == toy.POLL:
        names |= {"answers_missing", "estimate_rel_gap", "stderr_rel_gap"}
    assert set(plain.checks) == names
    for v in plain.checks.values():
        assert v["value"] <= v["limit"]


def test_control_comes_out_not_correct(plain):
    drv, ref = harness.driver(plain.cell), harness.reference(plain.cell)
    control = drv.compare(ref, plain.cell.config, plain.evidence,
                          control=True)
    failed = [k for k, v in control.items() if v["value"] > v["limit"]]
    assert failed, control


@pytest.mark.parametrize("name", [toy.ZIPF, toy.INGEST, toy.POLL])
def test_traced_run_reports_span_and_counter_metrics(name):
    run = toy.run(name, trace=True)
    line = harness.result_line(run, trace=True, device=CPU)
    assert line["correct"] is True
    assert line["device"]["window_s"] > 0
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    want = ({"flush.host_self_ms", "ingest.cohort_ms",
             "ingest.useful_rows_pct"} if name != toy.POLL
            else {"poll.outside_batch_ms", "query.batch_ms"})
    # the device readers find no TPU plane on the CPU and stay silent
    assert set(line["metrics"]) == want
    if name == toy.INGEST:
        # 4 of 32 tenants carry records: an eighth of the rows
        assert line["metrics"]["ingest.useful_rows_pct"]["value"] == 12.5
    if name == toy.ZIPF:
        # 4000 records in 32 streams padded to the hot tenant's rounds
        hot = generate.load_pick("zipf").shares(4000, 8, 0.99).max()
        useful = 100 * 4000 / (32 * 512 * -(-hot // 512))
        assert line["metrics"]["ingest.useful_rows_pct"]["value"] == useful
