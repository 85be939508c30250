"""serve_prefill_ms (ms): device time under the ``prefill`` scope (the
engine's prefill program: one slot's prompt chunk through every layer and
into the ring) per execution of that program, each instant to the
innermost operation, from the trace of a ``serve_bank`` window.  The
executions are the program runs inside the window whose module name holds
``prefill``; an operation counts when ``prefill`` is any component of its
``op_name``, as ``bench/layer_scopes.py`` counts its scopes.  None where no
operation carries the scope, as in a program without a prefill."""
from __future__ import annotations

import functools
import re
from pathlib import Path

from bench import spans
from bench import trace_reduce as tr

SCOPE = re.compile(r"(?:^|/)prefill(?=/|$)")


def reduce(evs: list[dict]) -> dict:
    """Over the window, averaged over chips: the device seconds under the
    scope inside the prefill program's executions, their number, and
    whether any operation carries the scope."""
    lo, hi = tr.window_of(evs)
    ops = [e for e in evs if tr.is_device(e["plane"])
           and e["line"] == tr.OPS_LINE]
    planes = sorted({e["plane"] for e in ops})
    if not planes:
        raise ValueError("the trace holds no device operations")
    calls = [e for e in evs if tr.is_device(e["plane"])
             and e["line"] == tr.MODULES_LINE and "prefill" in e["name"]]
    seconds, executions = 0.0, 0
    for plane in planes:
        runs = [(m["start"], m["end"]) for m in calls
                if m["plane"] == plane and m["start"] >= lo
                and m["end"] <= hi]
        executions += len(runs)
        segs = spans.innermost([
            (max(e["start"], lo), min(e["end"], hi),
             bool(SCOPE.search(e.get("op_name", ""))))
            for e in ops if e["plane"] == plane])
        seconds += (spans.overlap(segs, runs).get(True, 0.0) * 1e-9
                    / len(planes))
    names = " ".join({e.get("op_name", "") for e in ops})
    return {"window_s": (hi - lo) * 1e-9, "seconds": seconds,
            "executions": executions // len(planes),
            "found": bool(SCOPE.search(names))}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> dict:
    return reduce(spans.events(Path(path).parent))


def read(rec):
    if rec.get("driver") != "serve_bank":
        return None
    if "events" in rec:
        red = reduce(rec["events"])
    else:
        files = sorted(spans.TRACE_ROOT.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if not files:
            return None
        red = _reduced(str(files[-1]), files[-1].stat().st_mtime_ns)
        if abs(red["window_s"] - rec["trace"]["window_s"]) > 1e-9:
            return None
    if not red["found"] or not red["executions"]:
        return None
    return 1e3 * red["seconds"] / red["executions"]
