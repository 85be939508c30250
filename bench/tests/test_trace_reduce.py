"""The trace reduction on a trace recorded on one v5e (three runs of a
small jitted matmul, 2 ms apart, two of them inside the window) and on a
hand-made trace with collectives; every expected number is counted by
hand from the events."""
import json

import pytest

from bench import trace_reduce as tr
from bench.tests.conftest import ROOT

NS = 1e-9


@pytest.fixture
def recorded():
    return json.loads((ROOT / "bench" / "tests" / "data" /
                       "tpu_trace.json").read_text())


def test_recorded_trace(recorded):
    out = tr.reduce(recorded)
    assert out["window_s"] == pytest.approx((56616145 - 46826406) * NS)
    # runs 2 and 3 only; each is copy-start, copy-done and the fusion:
    # (13 + 2 + 91033) + (13 + 3 + 91057) ns
    assert out["busy_s"] == pytest.approx(182121 * NS)
    assert out["collective_s"] == 0 and out["exposed_collective_s"] == 0
    ops = dict(out["device_ops"])
    assert ops["fusion"] == pytest.approx((91033 + 91057) * NS)
    assert ops["copy-start"] == pytest.approx(26 * NS)
    assert ops["copy-done"] == pytest.approx(5 * NS)
    assert out["top_module"] == {"name": "jit__lambda(17364529655920221258)",
                                 "executions": 2,
                                 "device_s": pytest.approx(182136 * NS)}
    gaps = out["idle_gaps"]
    # each named by the host's innermost event at its middle and its start
    assert gaps[0] == ["$time sleep @0.005s", pytest.approx(4369020 * NS)]
    assert gaps[1] == ["no host event @0.002s", pytest.approx(3051450 * NS)]
    assert gaps[2] == ["$time sleep @0.000s", pytest.approx(2187142 * NS)]


def _ev(name, a, b, line=tr.OPS_LINE, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start": a, "end": b}


def test_collectives_exposed_and_hidden():
    evs = [_ev("%fusion.1 = f32[8] fusion(%x)", 0, 100),
           _ev("%all-reduce.1 = f32[8] all-reduce(%fusion.1)", 50, 150),
           _ev("%fusion.2 = f32[8] fusion(%all-reduce.1)", 200, 300),
           _ev("%all-gather-start = f32[8] all-gather-start(%y)", 320, 400),
           _ev("bench.window", 0, 500, line="python", plane="/host:CPU")]
    out = tr.reduce(evs)
    assert out["busy_s"] == pytest.approx(330 * NS)        # 150 + 100 + 80
    assert out["collective_s"] == pytest.approx(180 * NS)  # 100 + 80
    assert out["exposed_collective_s"] == pytest.approx(130 * NS)  # 50 + 80
    # fusion.2 reads all-reduce.1 but is no collective
    assert dict(out["device_ops"])["fusion.2"] == pytest.approx(100 * NS)


def test_loops_count_as_busy_but_list_their_bodies():
    evs = [_ev("%while.3 = (s32[]) while(%t)", 0, 100),
           _ev("%fusion.7 = f32[8] fusion(%p)", 10, 40),
           _ev("bench.window", 0, 200, line="python", plane="/host:CPU")]
    out = tr.reduce(evs)
    assert out["busy_s"] == pytest.approx(100 * NS)
    assert out["device_ops"] == [["fusion.7", pytest.approx(30 * NS)]]


def test_two_chips_are_averaged_and_clipped_to_the_window():
    evs = [_ev("f", -50, 50), _ev("g", 60, 80, plane="/device:TPU:1"),
           _ev("bench.window", 0, 100, line="python", plane="/host:CPU")]
    out = tr.reduce(evs)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((50 + 20) / 2 * NS)


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        tr.reduce([_ev("bench.window", 0, 1, line="python",
                       plane="/host:CPU")])
