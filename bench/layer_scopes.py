"""Device time per decode step under the model's outer named scopes
(``mla``: an MLA attention sub-block; ``moe``: an expert layer's router,
experts and shared experts), from the profiler trace of a ``serve_bank``
window.

An operation is under a scope when the scope is any component of its
``op_name`` (``bench/spans.py`` reads that from the ``.xplane.pb``).  Each
instant of device time counts once, to the innermost operation running
then, and is summed over the executions of the program that ran most
often (the engine's decode step), as ``spans.reduce`` sums its scopes.
"""
from __future__ import annotations

import functools
import re
from pathlib import Path

from bench import spans
from bench import trace_reduce as tr

OUTER = ("mla", "moe")
_PATTERN = {s: re.compile(r"(?:^|/)" + s + r"(?=/|$)") for s in OUTER}


def reduce(evs: list[dict]) -> dict:
    """Over the window, averaged over chips: per scope in ``OUTER`` the
    device seconds inside the most-run program's executions, whether any
    operation carries it, and the number of executions."""
    lo, hi = tr.window_of(evs)
    ops = [e for e in evs if tr.is_device(e["plane"])
           and e["line"] == tr.OPS_LINE]
    planes = sorted({e["plane"] for e in ops})
    if not planes:
        raise ValueError("the trace holds no device operations")
    modules: dict = {}
    for e in evs:
        if tr.is_device(e["plane"]) and e["line"] == tr.MODULES_LINE:
            modules.setdefault(e["name"], []).append(e)
    most = max(modules, key=lambda k: len(modules[k])) if modules else None
    seconds = dict.fromkeys(OUTER, 0.0)
    executions = 0
    for plane in planes:
        mine = [e for e in ops if e["plane"] == plane]
        runs = [(m["start"], m["end"]) for m in modules.get(most, [])
                if m["plane"] == plane and m["start"] >= lo
                and m["end"] <= hi]
        executions += len(runs)
        for scope, pat in _PATTERN.items():
            segs = spans.innermost([
                (max(e["start"], lo), min(e["end"], hi),
                 bool(pat.search(e.get("op_name", "")))) for e in mine])
            seconds[scope] += (spans.overlap(segs, runs).get(True, 0.0)
                               * 1e-9 / len(planes))
    names = " ".join({e.get("op_name", "") for e in ops})
    return {"window_s": (hi - lo) * 1e-9,
            "executions": executions // len(planes), "seconds": seconds,
            "found": {s: bool(p.search(names)) for s, p in _PATTERN.items()}}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> dict:
    return reduce(spans.events(Path(path).parent))


def of_run(rec: dict) -> dict | None:
    """The reduction of the traced ``serve_bank`` run ``rec`` describes,
    found as ``spans.of_run`` finds its trace; None when there is none."""
    if rec.get("driver") != "serve_bank":
        return None
    if "events" in rec:
        return reduce(rec["events"])
    files = sorted(spans.TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    got = _reduced(str(files[-1]), files[-1].stat().st_mtime_ns)
    same = abs(got["window_s"] - rec["trace"]["window_s"]) <= 1e-9
    return got if same else None


def per_step_ms(rec: dict, scope: str) -> float | None:
    """Device time under ``scope`` per execution of the decode step (ms);
    None unless some operation carries the scope."""
    red = of_run(rec)
    if red is None or not red["found"][scope] or not red["executions"]:
        return None
    return 1e3 * red["seconds"][scope] / red["executions"]
