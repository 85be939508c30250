"""Where the serving engine's own spans, scopes and counters put the
device's time, from the profiler trace of a ``serve_bank`` window.

``ServeEngine.run`` (``src/repro/launch/serve.py``) writes host spans on
the profiler's clock: ``serve.run`` holds ``serve.ring_init`` and one
``serve.step`` per loop iteration, which holds ``serve.admit``,
``serve.dispatch``, ``serve.sync`` and ``serve.bookkeep``.  ``serve.run``
carries the engine's counters (``ServeEngine.stats``), its slot count and
the per-layer KV ring shape as metadata.  The decode step's operations
carry the named scopes ``kv_ring``, ``tri_lora``, ``attention`` and
``logits`` in their ``op_name`` metadata.  On a TPU that is the ``tf_op``
stat of each operation's event metadata in the device plane, which
``jax.profiler.ProfileData`` does not expose, so ``op_names`` reads it
from the ``.xplane.pb`` itself.

``events`` keeps from a trace only what this reduction reads; ``reduce``
works on those records alone, so it can be checked on a small recorded
trace.  ``of_run`` finds and reduces the trace of the traced run whose
metric record it is given, once for all the readers of that run.
"""
from __future__ import annotations

import functools
import mmap
import re
from pathlib import Path

from bench import trace_reduce as tr

SPAN = "serve."
SCOPES = ("kv_ring", "tri_lora", "attention", "logits")
UNSCOPED = "unscoped"
UNATTRIBUTED = "unattributed"
OP_NAME_STAT = "tf_op"
# bench/run.py writes each cell's trace under <checkout>/.bench_cache/trace
TRACE_ROOT = Path(__file__).resolve().parents[1] / ".bench_cache" / "trace"
RESULT = re.compile(r"^%?(\S+) = (\w+\[[\d,]*\]|\()")
SCOPE = re.compile(r"(?:^|/)(" + "|".join(SCOPES) + r")(?=/|$)")


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def _result(name: str) -> str:
    """``%copy.3 = bf16[2,4]{1,0} copy(...)`` gives ``%copy.3 = bf16[2,4]``;
    a tuple result is written ``(``."""
    m = RESULT.match(name)
    return f"%{m.group(1)} = {m.group(2)}" if m else name


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """The (field number, value) pairs of the protobuf message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_names(path) -> dict:
    """Per device plane of an ``.xplane.pb``, each operation's name (its
    HLO text, as ``ProfileData`` names the event) to its ``tf_op`` stat.
    Reads the planes' names and metadata and skips their events
    (``XSpace.planes`` 1; ``XPlane`` name 2, event_metadata 4,
    stat_metadata 5; map entries key 1, value 2; ``XEventMetadata`` name
    2, stats 5; ``XStat`` metadata_id 1, str_value 5, ref_value 7;
    ``XStatMetadata`` id 1, name 2)."""
    out: dict = {}
    with open(path, "rb") as f, \
            mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as buf:
        for num, plane in _fields(buf, 0, len(buf)):
            if num != 1:
                continue
            parts: dict = {2: [], 4: [], 5: []}
            for k, v in _fields(buf, *plane):
                if k in parts:
                    parts[k].append(v)
            name = _text(buf, parts[2][0]) if parts[2] else ""
            if not tr.is_device(name):
                continue
            stat_names = {}
            for entry in parts[5]:
                md = dict(_fields(buf, *entry)).get(2)
                if md is not None:
                    f2 = dict(_fields(buf, *md))
                    stat_names[f2.get(1, 0)] = (_text(buf, f2[2])
                                                if 2 in f2 else "")
            tf_op = [i for i, n in stat_names.items() if n == OP_NAME_STAT]
            names = out.setdefault(name, {})
            for entry in parts[4]:
                md = dict(_fields(buf, *entry)).get(2)
                if md is None:
                    continue
                ev_name, op = "", ""
                for k, v in _fields(buf, *md):
                    if k == 2:
                        ev_name = _text(buf, v)
                    elif k == 5:
                        st = dict(_fields(buf, *v))
                        if st.get(1) in tf_op:
                            op = (_text(buf, st[5]) if 5 in st
                                  else stat_names.get(st.get(7), ""))
                if op:
                    names[ev_name] = op
    return out


def events(trace_dir) -> list[dict]:
    """The records this reduction reads, from the newest ``.xplane.pb``
    under ``trace_dir``: device operations (their name cut after the
    result type, with their ``op_name``) and program executions, and the
    host's ``serve.*`` spans (with their metadata as ``args``) and
    ``bench.*`` annotations."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    ops_of = op_names(files[-1])
    out = []
    for plane in ProfileData.from_file(str(files[-1])).planes:
        device = tr.is_device(plane.name)
        for line in plane.lines:
            if device and line.name not in (tr.OPS_LINE, tr.MODULES_LINE):
                continue
            ops = device and line.name == tr.OPS_LINE
            names = ops_of.get(plane.name, {})
            seen: dict = {}
            for ev in line.events:
                name = ev.name
                if not device and not (name.startswith(SPAN)
                                       or name.startswith("bench.")):
                    continue
                rec = {"plane": plane.name, "line": line.name,
                       "start": float(ev.start_ns),
                       "end": float(ev.start_ns + ev.duration_ns)}
                if ops:
                    if name not in seen:
                        seen[name] = (_result(name), names.get(name, ""))
                    rec["name"], rec["op_name"] = seen[name]
                else:
                    rec["name"] = name
                    if name == SPAN + "run":
                        rec["args"] = _stats(ev)
                out.append(rec)
    return out


def innermost(intervals) -> list[tuple[float, float, object]]:
    """Cut the union of ``(start, end, key)`` intervals into
    non-overlapping segments, each given the key of the innermost (latest
    started) interval open over it."""
    segs: list = []
    stack: list = []
    t = float("-inf")

    def advance(until):
        nonlocal t
        while stack and t < until:
            a, b, k = stack[-1]
            if b <= t:
                stack.pop()
                continue
            nxt = min(b, until)
            if segs and segs[-1][1] == t and segs[-1][2] == k:
                segs[-1] = (segs[-1][0], nxt, k)
            else:
                segs.append((t, nxt, k))
            t = nxt
        t = max(t, until)

    for a, b, k in sorted(intervals, key=lambda x: (x[0], -x[1])):
        if b <= a:
            continue
        advance(a)
        stack.append((a, b, k))
    advance(float("inf"))
    return segs


def overlap(segs, intervals) -> dict:
    """Per key of ``segs``, the length of its segments that the union of
    ``intervals`` covers."""
    out: dict = {}
    ivs = tr.union(intervals)
    j = 0
    for a, b, k in segs:
        while j < len(ivs) and ivs[j][1] <= a:
            j += 1
        i = j
        while i < len(ivs) and ivs[i][0] < b:
            d = min(b, ivs[i][1]) - max(a, ivs[i][0])
            if d > 0:
                out[k] = out.get(k, 0.0) + d
            i += 1
    return out


def _ring_dims(kv_ring: str) -> set:
    return {tuple(int(n) for n in s.split("x"))
            for s in kv_ring.split(";") if s}


def bucket(rec: dict, ring: set) -> str:
    """An operation's scope: the innermost of ``SCOPES`` in its
    ``op_name``; else ``kv_ring`` if its result has a ring's shape, per
    layer or with a leading layer axis (the scan's slice and write-back
    of the stacked ring, and its copies); else ``unscoped``."""
    found = SCOPE.findall(rec.get("op_name", ""))
    if found:
        return found[-1]
    m = re.search(r"= \w+\[([\d,]*)\]$", rec["name"])
    if m and ring:
        dims = tuple(int(n) for n in m.group(1).split(",") if n)
        if dims in ring or dims[1:] in ring:
            return "kv_ring"
    return UNSCOPED


def reduce(evs: list[dict], window: tuple[float, float] | None = None
           ) -> dict:
    """Over the window, averaged over chips (seconds):

    - ``spans``: per ``serve.*`` name its count, its self time and the
      device idle time in that self time; ``unattributed`` holds the idle
      time outside every ``serve.*`` span.  The idle times sum to the
      window less the busy time.
    - ``scopes``: device busy time per scope bucket, each instant given
      to the innermost operation running; the buckets sum to the busy
      time.  ``step`` holds the same for the executions of the program
      that ran most often, with their count.
    - ``stats``: the metadata of ``serve.run`` (the engine's counters,
      ``slots``, ``kv_ring``), or ``{}``.
    - ``scoped``: the scope names found in any operation's ``op_name``.
    """
    lo, hi = window if window is not None else tr.window_of(evs)
    host = [e for e in evs if not tr.is_device(e["plane"])
            and e["name"].startswith(SPAN)
            and e["end"] > lo and e["start"] < hi]
    runs = [e for e in host if e["name"] == SPAN + "run" and e.get("args")]
    stats = dict(runs[0]["args"]) if runs else {}
    ring = _ring_dims(str(stats.get("kv_ring", "")))
    planes = sorted({e["plane"] for e in evs if tr.is_device(e["plane"])
                     and e["line"] == tr.OPS_LINE})
    if not planes:
        raise ValueError("the trace holds no device operations")

    span_segs = innermost([(max(e["start"], lo), min(e["end"], hi),
                            e["name"]) for e in host])
    spans = {e["name"]: {"count": 0, "self_s": 0.0, "idle_s": 0.0}
             for e in host}
    for e in host:
        spans[e["name"]]["count"] += 1
    for a, b, k in span_segs:
        spans[k]["self_s"] += (b - a) * 1e-9
    spans[UNATTRIBUTED] = {"count": 0, "self_s": 0.0, "idle_s": 0.0}

    n = len(planes)
    scopes = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    step = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    scoped: set = set()
    modules: dict = {}
    for e in evs:
        if tr.is_device(e["plane"]) and e["line"] == tr.MODULES_LINE:
            modules.setdefault(e["name"], []).append(e)
    most = max(modules, key=lambda k: len(modules[k])) if modules else None
    executions = 0
    for plane in planes:
        ops = [e for e in evs if e["plane"] == plane
               and e["line"] == tr.OPS_LINE]
        scoped.update(SCOPE.findall(" ".join(
            {e.get("op_name", "") for e in ops})))
        kinds: dict = {}        # an operation repeats every step
        for e in ops:
            key = (e["name"], e.get("op_name", ""))
            if key not in kinds:
                kinds[key] = bucket(e, ring)
        op_segs = innermost([(max(e["start"], lo), min(e["end"], hi),
                              kinds[e["name"], e.get("op_name", "")])
                             for e in ops])
        for a, b, k in op_segs:
            scopes[k] += (b - a) * 1e-9 / n
        runs_here = [(m["start"], m["end"]) for m in modules.get(most, [])
                     if m["plane"] == plane and m["start"] >= lo
                     and m["end"] <= hi]
        executions += len(runs_here)
        for k, d in overlap(op_segs, runs_here).items():
            step[k] += d * 1e-9 / n
        busy = tr.union((a, b) for a, b, _ in op_segs)
        idle = tr.subtract([(lo, hi)], busy)
        held = overlap(span_segs, idle)
        for k, d in held.items():
            spans[k]["idle_s"] += d * 1e-9 / n
        spans[UNATTRIBUTED]["idle_s"] += (tr.length(idle)
                                          - sum(held.values())) * 1e-9 / n
    return {"window_s": (hi - lo) * 1e-9, "spans": spans, "scopes": scopes,
            "step": {"module": most, "executions": executions // n,
                     "scopes": step},
            "stats": stats, "scoped": sorted(scoped)}


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> dict:
    return reduce(events(Path(path).parent))


def of_run(rec: dict) -> dict | None:
    """The reduction of the traced ``serve_bank`` run ``rec`` describes:
    ``rec["events"]`` where the caller holds them, else the newest trace
    under ``TRACE_ROOT`` whose window is the one the run reduced.  None
    when there is no such trace."""
    if rec.get("driver") != "serve_bank":
        return None
    if "events" in rec:
        return reduce(rec["events"])
    files = sorted(TRACE_ROOT.rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    got = _reduced(str(files[-1]), files[-1].stat().st_mtime_ns)
    same = abs(got["window_s"] - rec["trace"]["window_s"]) <= 1e-9
    return got if same else None


def idle_per_step_ms(red: dict | None, names: tuple) -> float | None:
    """Device idle time in the spans ``names`` per ``serve.step`` (ms);
    None unless every one of them is in the trace."""
    if red is None:
        return None
    sp = red["spans"]
    steps = sp.get(SPAN + "step", {}).get("count", 0)
    if not steps or any(n not in sp for n in names):
        return None
    return 1e3 * sum(sp[n]["idle_s"] for n in names) / steps


def slot_step_share(red: dict | None, counter: str) -> float | None:
    """The engine's counter ``counter`` over slots x steps (%); None
    unless the engine's counters are in the trace."""
    st = {} if red is None else red["stats"]
    if counter not in st or not st.get("slots") or not st.get("steps"):
        return None
    return 100.0 * st[counter] / (st["slots"] * st["steps"])


def scope_per_step_ms(red: dict | None, scope: str) -> float | None:
    """Device time in ``scope`` per execution of the program that ran
    most often (ms); None unless some operation carries the scope."""
    if red is None or scope not in red["scoped"]:
        return None
    step = red["step"]
    if not step["executions"]:
        return None
    return 1e3 * step["scopes"][scope] / step["executions"]
