"""From a profiler trace to the numbers the per-layer metrics read.

``events`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes into
plain records; ``reduce`` works on those records alone, so it can be
checked on a small recorded trace.  Device planes are ``/device:...``;
their operations are the events of the ``XLA Ops`` line, their program
executions those of ``XLA Modules``.  Host planes carry the benchmark's
``bench.*`` annotations, which mark the window, and the host's own events,
which name what the host was doing in each idle gap.
"""
from __future__ import annotations

import re
from pathlib import Path

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# control-flow ops whose events span the ops of their bodies
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"\bsend\b|\brecv\b|send-done|recv-done", re.I)


def op_name(name: str) -> str:
    """An operation event's instruction name: ``%fusion.12 = bf16[...]
    fusion(...)`` gives ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def events(trace_dir) -> list[dict]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start": float(ev.start_ns),
                            "end": float(ev.start_ns + ev.duration_ns)})
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a_list, b_list) -> list:
    """Parts of the union of ``a_list`` that no interval of ``b_list``
    covers."""
    out = []
    b_list = union(b_list)
    for a, b in union(a_list):
        cur = a
        for c, d in b_list:
            if d <= cur or c >= b:
                continue
            if c > cur:
                out.append((cur, c))
            cur = max(cur, d)
            if cur >= b:
                break
        if cur < b:
            out.append((cur, b))
    return out


def is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane.upper()


def window_of(evs, name: str = "bench.window") -> tuple[float, float]:
    """The span of the host annotation ``name`` (the first, if several)."""
    spans = [(e["start"], e["end"]) for e in evs
             if e["name"] == name and not is_device(e["plane"])]
    if not spans:
        raise ValueError(f"no host span {name!r} in the trace")
    return min(spans)


def reduce(evs: list[dict], window: tuple[float, float] | None = None,
           top: int = 10) -> dict:
    """Busy, idle and collective time of each device over the window
    (seconds), the device operations that took most time (loops and
    calls left out, their bodies' operations are listed), the program
    that ran most often with its executions, and the longest idle gaps
    named by the innermost host event that covers each gap's middle and
    the gap's start within the window."""
    lo, hi = window if window is not None else window_of(evs)
    planes = sorted({e["plane"] for e in evs if is_device(e["plane"])
                     and e["line"] == OPS_LINE})
    if not planes:
        raise ValueError("the trace holds no device operations")
    per_dev, op_time, gaps = [], {}, []
    modules: dict = {}
    host = [e for e in evs if not is_device(e["plane"])
            and not e["name"].startswith("bench.")
            and e["end"] > lo and e["start"] < hi]
    for plane in planes:
        ops = [e for e in evs if e["plane"] == plane and e["line"] == OPS_LINE]
        busy = union(clip([(e["start"], e["end"]) for e in ops], lo, hi))
        is_coll = [bool(COLLECTIVE.search(op_name(e["name"]))) for e in ops]
        coll = union(clip([(e["start"], e["end"])
                           for e, c in zip(ops, is_coll) if c], lo, hi))
        comp = union(clip([(e["start"], e["end"])
                           for e, c in zip(ops, is_coll) if not c], lo, hi))
        per_dev.append({"plane": plane, "busy_s": length(busy) * 1e-9,
                        "collective_s": length(coll) * 1e-9,
                        "exposed_collective_s":
                            length(subtract(coll, comp)) * 1e-9})
        for e in ops:
            d = min(e["end"], hi) - max(e["start"], lo)
            k = op_name(e["name"])
            if d > 0 and not CONTAINER.match(k):
                op_time[k] = op_time.get(k, 0.0) + d * 1e-9
        for e in evs:
            if (e["plane"] == plane and e["line"] == MODULES_LINE
                    and e["start"] >= lo and e["end"] <= hi):
                m = modules.setdefault(e["name"], [0, 0.0])
                m[0] += 1
                m[1] += (e["end"] - e["start"]) * 1e-9
        for a, b in subtract([(lo, hi)], busy):
            gaps.append((b - a, a, b))
    gaps.sort(reverse=True)
    named = []
    for d, a, b in gaps[:top]:
        mid = (a + b) / 2
        cover = [e for e in host if e["start"] <= mid <= e["end"]]
        label = (min(cover, key=lambda e: e["end"] - e["start"])["name"]
                 if cover else "no host event")
        named.append([f"{label} @{(a - lo) * 1e-9:.3f}s", d * 1e-9])
    n = len(per_dev)
    most = max(modules.items(), key=lambda kv: kv[1][0]) if modules else None
    return {
        "window_s": (hi - lo) * 1e-9,
        "devices": n,
        "busy_s": sum(p["busy_s"] for p in per_dev) / n,
        "collective_s": sum(p["collective_s"] for p in per_dev) / n,
        "exposed_collective_s":
            sum(p["exposed_collective_s"] for p in per_dev) / n,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": named,
        "top_module": None if most is None else {
            "name": most[0], "executions": most[1][0] // n,
            "device_s": most[1][1] / n},
    }
