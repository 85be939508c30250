"""The reduction of the serving engine's spans, scopes and counters
(``bench/spans.py``) and the six metric readers that read it: on a trace
recorded on one v5e (an engine of the configuration at ``tiny`` widths, 3
slots, 4 requests, 6 steps inside the window), on hand-made intervals, on
a hand-encoded ``.xplane.pb``, and on the older recorded trace, which
holds no ``serve.*`` span."""
import json

import pytest

from bench import run, spans
from bench import trace_reduce as tr
from bench.tests.conftest import ROOT

DATA = ROOT / "bench" / "tests" / "data"
READERS = ["serve_idle_host_ms", "serve_idle_sync_ms", "serve_emit_share",
           "serve_prefill_share", "serve_ring_ms", "serve_adapter_ms"]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "serve_trace.json").read_text())


def reader(name):
    return run.load_module(run.BENCH / "metrics" / f"{name}.py",
                           f"bench_metric_{name}")


def record(evs):
    return {"driver": "serve_bank", "events": evs,
            "trace": tr.reduce(evs)}


def test_every_operation_lands_in_one_bucket(recorded):
    red = spans.reduce(recorded)
    busy = tr.reduce(recorded)["busy_s"]
    assert set(red["scopes"]) == set(spans.SCOPES) | {spans.UNSCOPED}
    assert sum(red["scopes"].values()) == pytest.approx(busy, rel=1e-9)
    assert red["scoped"] == sorted(spans.SCOPES)
    assert all(v > 0 for v in red["scopes"].values())
    # the step's executions hold all of it but the ring's first write
    step = red["step"]
    assert step["executions"] == red["stats"]["steps"] == 6
    assert sum(step["scopes"].values()) <= busy
    assert step["scopes"]["tri_lora"] == pytest.approx(
        red["scopes"]["tri_lora"], rel=1e-9)


def test_idle_time_is_held_by_the_spans_or_unattributed(recorded):
    red = spans.reduce(recorded)
    t = tr.reduce(recorded)
    idle = t["window_s"] - t["busy_s"]
    sp = red["spans"]
    assert sum(v["idle_s"] for v in sp.values()) == pytest.approx(
        idle, rel=1e-9)
    assert sp[spans.UNATTRIBUTED]["idle_s"] < 0.1 * idle
    for name in ("serve.step", "serve.admit", "serve.dispatch",
                 "serve.sync", "serve.bookkeep"):
        assert sp[name]["count"] == 6, name
        assert 0 <= sp[name]["idle_s"] <= sp[name]["self_s"] + 1e-12
    assert sp["serve.run"]["count"] == sp["serve.ring_init"]["count"] == 1
    # self times tile the outermost span
    run_span = [e for e in recorded if e["name"] == "serve.run"][0]
    assert sum(v["self_s"] for k, v in sp.items()
               if k != spans.UNATTRIBUTED) == pytest.approx(
        (run_span["end"] - run_span["start"]) * 1e-9, rel=1e-9)


def test_counters_ride_on_the_run_span(recorded):
    st = spans.reduce(recorded)["stats"]
    assert st == {"slots": 3, "kv_ring": "3x8x2x16", "steps": 6,
                  "slot_steps_prefill": 6, "slot_steps_emit": 8,
                  "slot_steps_empty": 4, "admitted": 4, "finished": 4}


def test_readers_on_the_recorded_trace(recorded):
    rec = record(recorded)
    red = spans.reduce(recorded)
    sp, step = red["spans"], red["step"]
    want = {
        "serve_idle_host_ms": 1e3 * sum(
            sp[n]["idle_s"] for n in ("serve.admit", "serve.dispatch",
                                      "serve.bookkeep")) / 6,
        "serve_idle_sync_ms": 1e3 * sp["serve.sync"]["idle_s"] / 6,
        "serve_emit_share": 100.0 * 8 / 18,
        "serve_prefill_share": 100.0 * 6 / 18,
        "serve_ring_ms": 1e3 * step["scopes"]["kv_ring"] / 6,
        "serve_adapter_ms": 1e3 * step["scopes"]["tri_lora"] / 6,
    }
    for name in READERS:
        assert reader(name).read(rec) == pytest.approx(want[name]), name


def test_readers_find_nothing_in_a_trace_without_the_engines_spans():
    evs = json.loads((DATA / "tpu_trace.json").read_text())
    red = spans.reduce(evs)
    assert red["stats"] == {} and red["scoped"] == []
    assert set(red["spans"]) == {spans.UNATTRIBUTED}
    for name in READERS:
        assert reader(name).read(record(evs)) is None, name
        assert reader(name).read(dict(record(evs), driver="other")) is None


def test_the_readers_look_where_the_harness_writes_its_traces():
    assert spans.TRACE_ROOT == run.CACHE / "trace"


def test_innermost_gives_each_instant_to_the_latest_open_interval():
    segs = spans.innermost([(0, 10, "run"), (2, 8, "step"), (3, 4, "sync"),
                            (6, 12, "late"), (20, 25, "alone")])
    assert segs == [(0, 2, "run"), (2, 3, "step"), (3, 4, "sync"),
                    (4, 6, "step"), (6, 12, "late"), (20, 25, "alone")]
    assert spans.overlap(segs, [(1, 5), (7, 21)]) == {
        "run": 1, "step": 2, "sync": 1, "late": 5, "alone": 1}


def test_a_scope_wins_over_the_rings_shape():
    ring = {(3, 8, 2, 16)}

    def op(name, op_name=""):
        return {"name": name, "op_name": op_name}
    assert spans.bucket(op("%f = bf16[3,8,2,16]",
                           "jit(s)/while/body/attention/convert:"),
                        ring) == "attention"
    assert spans.bucket(op("%f = bf16[3,8,2,16]", "jit(s)/while/body/"
                           "closed_call/kv_ring/tri_lora/x:"),
                        ring) == "tri_lora"
    assert spans.bucket(op("%ds = bf16[3,8,2,16]", "jit(s)/squeeze:"),
                        ring) == "kv_ring"
    assert spans.bucket(op("%dus = bf16[24,3,8,2,16]"), ring) == "kv_ring"
    assert spans.bucket(op("%w = bf16[1,64,64]"), ring) == "unscoped"
    assert spans.bucket(op("%t = ("), ring) == "unscoped"
    assert spans.bucket(op("%ds = bf16[3,8,2,16]"), set()) == "unscoped"
    # a scope name inside another word is not that scope
    assert spans.bucket(op("%m = f32[2]", "jit(s)/my_logits/add:"),
                        ring) == "unscoped"


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, value):
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _entry(map_field, key, value):
    return _field(map_field, _field(1, key) + _field(2, value))


def test_op_names_read_from_the_event_metadata(tmp_path):
    """XSpace > XPlane (name 2, lines 3, event_metadata 4, stat_metadata
    5) > XEventMetadata (id 1, name 2, stats 5) > XStat (metadata_id 1,
    str_value 5 or ref_value 7)."""
    fusion = "%fusion.1 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop"
    copy = "%copy.2 = bf16[4]{0} copy(bf16[4]{0} %q)"
    device = (_field(1, 7) + _field(2, "/device:TPU:0")
              + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1)))
              + _entry(5, 1, _field(1, 1) + _field(2, "tf_op"))
              + _entry(5, 2, _field(1, 2) + _field(2, "flops"))
              + _entry(5, 3, _field(1, 3) + _field(2, "jit(s)/kv_ring/s:"))
              + _entry(4, 1, _field(1, 1) + _field(2, fusion)
                       + _field(5, _field(1, 2) + _field(4, 6))
                       + _field(5, _field(1, 1)
                                + _field(5, "jit(s)/tri_lora/dot:")))
              + _entry(4, 2, _field(1, 2) + _field(2, copy)
                       + _field(5, _field(1, 1) + _field(7, 3)))
              + _entry(4, 3, _field(1, 3) + _field(2, "no op name")))
    host = (_field(2, "/host:CPU")
            + _entry(5, 1, _field(1, 1) + _field(2, "tf_op"))
            + _entry(4, 1, _field(1, 1) + _field(2, "serve.run")
                     + _field(5, _field(1, 1) + _field(5, "x"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, device) + _field(1, host)
                     + _field(4, "hostname"))
    assert spans.op_names(path) == {"/device:TPU:0": {
        fusion: "jit(s)/tri_lora/dot:", copy: "jit(s)/kv_ring/s:"}}
