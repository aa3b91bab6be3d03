"""The reduction from trace events to busy time, program time and the
breakdown, on a hand-made trace whose answers are known."""
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DEV0, DEV1 = "/device:TPU:0", "/device:TPU:1"


def _events():
    ms = 1e6
    return [
        Event(tr.HOST_PLANE, "python", "bench.window", 0, 100 * ms),
        Event(tr.HOST_PLANE, "python", "bench.flush", 10 * ms, 60 * ms),
        Event(tr.HOST_PLANE, "python", "service.flush", 12 * ms, 55 * ms),
        Event(tr.HOST_PLANE, "python", "bench.submit", 60 * ms, 40 * ms),
        # device 0: ops at [20, 30] and [25, 40] overlap -> busy 20 ms
        Event(DEV0, tr.OPS_LINE, "fusion.1", 20 * ms, 10 * ms),
        Event(DEV0, tr.OPS_LINE, "fusion.2", 25 * ms, 15 * ms),
        Event(DEV0, tr.MODULES_LINE, "jit_multi_round_update(7)", 20 * ms,
              20 * ms),
        # an op outside the window does not count
        Event(DEV0, tr.OPS_LINE, "fusion.1", 150 * ms, 10 * ms),
        # device 1: busy 40 ms
        Event(DEV1, tr.OPS_LINE, "fusion.3", 50 * ms, 40 * ms),
    ]


def test_busy_is_the_union_of_op_intervals_averaged_over_devices():
    s = tr.reduce_events(_events())
    assert s.window_s == pytest.approx(0.1)
    assert s.devices == 2
    assert s.busy_s == pytest.approx((0.020 + 0.040) / 2)
    assert s.idle_pct == pytest.approx(70.0)


def test_program_time_is_found_by_the_jitted_name():
    s = tr.reduce_events(_events())
    assert s.program("multi_round_update") == (pytest.approx(0.020), 1)
    assert s.program("_estimate_batch_core") == (0.0, 0)


def test_idle_gaps_are_named_by_the_innermost_host_annotation():
    gaps = dict(map(tuple, tr.reduce_events(_events()).idle_gaps))
    # device 0 idles over [0, 20) and [40, 100).  The first gap's middle
    # (10 ms) lies in bench.flush alone (service.flush starts at 12); the
    # second's (70 ms) in bench.flush and bench.submit, and the shorter,
    # bench.submit, is the innermost
    assert gaps == {"bench.flush": pytest.approx(0.020),
                    "bench.submit": pytest.approx(0.060)}


def test_a_trace_without_the_window_annotation_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events(_events()[1:])


def test_a_recorded_trace_is_read(tmp_path):
    """A window recorded by the harness's ``Window`` on the CPU: three
    ``bench.flush`` and three ``bench.submit`` steps.  The CPU has no TPU
    plane, so nothing counts as device time."""
    import gzip
    import pathlib
    import shutil
    data = pathlib.Path(__file__).parent / "data" / "cpu_window.xplane.pb.gz"
    path = tmp_path / "cpu_window.xplane.pb"
    with gzip.open(data) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    events = tr.load_events(str(path))
    names = [e.name for e in events if e.plane == tr.HOST_PLANE]
    assert names.count("bench.flush") == 3
    assert names.count("bench.submit") == 3
    s = tr.reduce_events(events)
    assert 0 < s.window_s < 10
    assert s.devices == 0 and s.busy_s == 0.0
    assert s.program("multi_round_update") == (0.0, 0)
