"""``serve_prefill_ms`` on hand-made intervals: device time under the
``prefill`` scope per execution of the prefill program, each instant to
the innermost operation, and None for a program without a prefill."""
import pytest

from bench import run
from bench import trace_reduce as tr


def _ev(line, name, a, b, op_name=None, plane="/device:TPU:0"):
    e = {"plane": plane, "line": line, "name": name, "start": float(a),
         "end": float(b)}
    if op_name is not None:
        e["op_name"] = op_name
    return e


def reader():
    return run.load_module(run.BENCH / "metrics" / "serve_prefill_ms.py",
                           "bench_metric_serve_prefill_ms")


def test_prefill_time_per_call():
    """Two prefill calls (a scope-less ``while`` around a scoped dot, and a
    scatter) and a decode step between them; the call outside the window
    and the step's operations do not count."""
    body = "jit(_prefill)/prefill/while/body/closed_call"
    evs = [_ev("host", "bench.window", 0, 100, plane="/host:CPU"),
           _ev(tr.MODULES_LINE, "jit__prefill", 10, 30),
           _ev(tr.MODULES_LINE, "jit__serve_step", 40, 60),
           _ev(tr.MODULES_LINE, "jit__prefill", 70, 80),
           _ev(tr.MODULES_LINE, "jit__prefill", 95, 105),
           _ev(tr.OPS_LINE, "%while.1 = (", 10, 30, "jit(_prefill)/while"),
           _ev(tr.OPS_LINE, "%fusion.1 = f32[2]", 12, 28,
               f"{body}/attention/dot_general"),
           _ev(tr.OPS_LINE, "%fusion.2 = f32[2]", 40, 60,
               "jit(_serve_step)/attention/dot_general"),
           _ev(tr.OPS_LINE, "%scatter.1 = f32[2]", 70, 76,
               "jit(_prefill)/prefill/kv_ring/scatter"),
           _ev(tr.OPS_LINE, "%fusion.3 = f32[2]", 95, 105,
               "jit(_prefill)/prefill/dot_general")]
    rec = {"driver": "serve_bank", "events": evs}
    assert reader().read(rec) == pytest.approx(1e3 * (16 + 6) * 1e-9 / 2)


def test_none_without_a_prefill():
    evs = [_ev("host", "bench.window", 0, 100, plane="/host:CPU"),
           _ev(tr.MODULES_LINE, "jit__serve_step", 40, 60),
           _ev(tr.OPS_LINE, "%fusion.2 = f32[2]", 40, 60,
               "jit(_serve_step)/attention/dot_general")]
    assert reader().read({"driver": "serve_bank", "events": evs}) is None
    assert reader().read({"driver": "other"}) is None
