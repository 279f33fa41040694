"""The program's own spans and device scopes in a traced run, reduced to
what the per-layer metrics of the query stages, the fold's phases, the
store refresh and the service call read.

The program writes host spans named ``lsh.<layer>[.<stage>]`` on the
profiler's clock (``repro.tracing``; args as the event's stats) and names
the query program's stages with ``jax.named_scope`` (``STAGES``). A TPU
trace does not carry an op's scope on the op's event: the device plane's
event metadata holds it, as the ``tf_op`` stat (the HLO ``op_name``),
keyed by the program's id and the op's HLO text. ``jax.profiler.
ProfileData`` does not expose event metadata, so ``op_scopes`` reads it
from the ``.xplane.pb`` file's protobuf encoding directly.

``extract`` returns the events ``trace.extract`` returns (device ops and
program executions, ``bench.*`` spans; ``trace.reduce`` reads them
unchanged) plus, per device, each op's program and stage
(``op_stage``), and the ``lsh.*`` spans with their host line (thread)
and args. ``reduce`` turns those into:

* ``spans``: per span name, its count, total and self seconds (duration
  less the time its children on the same thread cover), and the same
  per enclosing span name (``within``);
* ``scope_s``: device seconds per program and stage, ``unscoped`` for
  the rest: each stretch of a program execution goes to the innermost op
  running then, so nested ops (a ``while`` and its body) count once;
* ``idle``: idle device time by what the host was doing then: the
  innermost ``lsh.*`` span open on any thread, else the innermost
  ``bench.*`` span, else ``other``; it sums to the window less the busy
  time ``trace.reduce`` gives;
* ``stalls``: the load generator's longest turns (from one of its
  ``bench.wait_due`` or ``bench.submit`` spans to the next), with the
  ``lsh.*`` spans open then.

A per-layer reader calls ``of_run(ctx)``: the reduction of the trace the
run just wrote (the newest ``.xplane.pb`` under ``bench/.out``, checked
against the run's own window), or None where the run has none.

    python3 bench/spans.py <xplane file or directory> [--save events.json.gz]

prints the reduction as JSON and can save the extracted events.
"""

from __future__ import annotations

import bisect
import functools
import glob
import heapq
import json
import os
import re
import sys
from collections import defaultdict
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import stats, trace  # noqa: E402

STAGES = ("hash", "probe", "norms", "rerank", "select")
UNSCOPED = "unscoped"
PREFIX = "lsh."
GENERATOR = ("bench.wait_due", "bench.submit")
OUT_DIR = Path(__file__).resolve().parent / ".out"

_STAGE_RE = [(s, re.compile(rf"(\w*\()*{s}\)*")) for s in STAGES]
_PROGRAM_ID = re.compile(r"\((\d+)\)\s*$")


# ---------------------------------------------------------------------------
# The xplane's event metadata, read from the protobuf wire format
# ---------------------------------------------------------------------------


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited and fixed-width fields."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        field, kind = key >> 3, key & 7
        if kind == 0:
            value, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            value, i = b[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield field, value


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def op_scopes(xplane_path: str) -> dict[tuple[int, str], str]:
    """{(program id, op's HLO text): scope path} for every op of the
    accelerator planes whose event metadata carries a ``tf_op`` stat.

    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map entry:
    value = 2), stat_metadata = 5 (map entry: value = 2); XEventMetadata:
    name = 2, stats = 5; XStatMetadata: id = 1, name = 2; XStat:
    metadata_id = 1, uint64 = 3, int64 = 4, str = 5, ref = 7 (the id of
    a stat metadata whose name is the value)."""
    with open(xplane_path, "rb") as f:
        data = memoryview(f.read())
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        parts = defaultdict(list)
        for pf, pv in _fields(plane):
            if pf in (2, 4, 5):
                parts[pf].append(pv)
        name = _text(parts[2][0]) if parts[2] else ""
        if not name.startswith("/device:") or "CPU" in name:
            continue
        stat_names = {}
        for entry in parts[5]:
            for ef, ev in _fields(entry):
                if ef == 2:
                    meta = dict(_fields(ev))
                    stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
        for entry in parts[4]:
            for ef, ev in _fields(entry):
                if ef != 2:
                    continue
                op, program, scope = None, None, None
                for mf, mv in _fields(ev):
                    if mf == 2:
                        op = _text(mv)
                    elif mf == 5:
                        stat = dict(_fields(mv))
                        which = stat_names.get(stat.get(1))
                        if which == "program_id":
                            program = stat.get(3, stat.get(4))
                        elif which == "tf_op":
                            scope = (_text(stat[5]) if 5 in stat else
                                     stat_names.get(stat.get(7), ""))
                if op and program is not None and scope:
                    out[(int(program), op)] = scope
    return out


def stage_of(scope: str) -> str:
    """The query stage a scope path names (``jit(segmented_query)/probe/
    vmap(...)/gather`` -> ``probe``), or ``unscoped``. A ``tf_op`` value
    may end in ``:<op type>``."""
    for comp in scope.rsplit(":", 1)[0].split("/"):
        for stage, pattern in _STAGE_RE:
            if pattern.fullmatch(comp):
                return stage
    return UNSCOPED


# ---------------------------------------------------------------------------
# Extract
# ---------------------------------------------------------------------------


def extract(xplane_path: str) -> dict:
    """``trace.extract``'s events plus ``op_stage`` per device (one
    [program, stage] per op, in the order of ``ops``) and ``spans``:
    [[name, start_ns, end_ns, host line, {arg: value}]] of ``lsh.*``."""
    from jax.profiler import ProfileData
    scopes = op_scopes(xplane_path)
    data = ProfileData.from_file(xplane_path)
    devices, host, spans = {}, [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            raw_ops, modules = [], []
            for line in plane.lines:
                dest = (raw_ops if line.name == trace._OPS_LINE else
                        modules if line.name == trace._MODULES_LINE else None)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append([e.name, float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)])
            if not (raw_ops or modules):
                continue
            order = sorted(range(len(modules)), key=lambda k: modules[k][1])
            starts = [modules[k][1] for k in order]
            ops, op_stage = [], []
            for text, s, e in raw_ops:
                k = bisect.bisect_right(starts, s) - 1
                module = modules[order[k]] if k >= 0 else None
                program, stage = "", UNSCOPED
                if module is not None and s < module[2]:
                    program = trace.program_name(module[0])
                    pid = _PROGRAM_ID.search(module[0])
                    if pid:
                        stage = stage_of(scopes.get(
                            (int(pid.group(1)), text), ""))
                ops.append([trace.op_name(text), s, e])
                op_stage.append([program, stage])
            devices[plane.name] = {"ops": ops, "modules": modules,
                                   "op_stage": op_stage}
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)])
                    elif e.name.startswith(PREFIX):
                        spans.append([e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns), li,
                                      {k: v for k, v in e.stats}])
    return {"devices": devices, "host": host, "spans": spans}


# ---------------------------------------------------------------------------
# Reduce
# ---------------------------------------------------------------------------


def _window(events: dict) -> tuple[float, float]:
    w = [(s, e) for n, s, e in events["host"] if n == trace.WINDOW_SPAN]
    if not w:
        raise ValueError("the trace holds no window span")
    return min(s for s, _ in w), max(e for _, e in w)


def _innermost(items) -> list[tuple[float, float, object]]:
    """``items``: (start, end, tag, rank). -> [(a, b, tag)]: the union of
    the [start, end) intervals cut where any starts or ends, each piece to
    the open item of the highest rank, ties to the one listed first."""
    points = []
    for k, (s, e, _, _) in enumerate(items):
        if e > s:
            points.append((s, 1, k))
            points.append((e, 0, k))
    points.sort()
    heap, ended, out = [], set(), []
    for j, (t, kind, k) in enumerate(points):
        if kind:
            heapq.heappush(heap, (tuple(-r for r in items[k][3]), k))
        else:
            ended.add(k)
        while heap and heap[0][1] in ended:
            heapq.heappop(heap)
        nxt = points[j + 1][0] if j + 1 < len(points) else t
        if heap and nxt > t:
            out.append((t, nxt, items[heap[0][1]][2]))
    return out


def _overlap(gaps, pieces, into: dict, other: str) -> None:
    """Add each gap's overlap with each labelled piece to ``into``; the
    rest of the gap to ``other``. Both lists sorted, pieces disjoint."""
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < ge:
            a, b = max(pieces[k][0], gs), min(pieces[k][1], ge)
            if b > a:
                into[pieces[k][2]] += (b - a) * 1e-9
                covered += b - a
            k += 1
        if ge - gs - covered > 0:
            into[other] += (ge - gs - covered) * 1e-9


def _entry(d: dict, name: str) -> dict:
    return d.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})


def span_stats(spans: list, lo: float, hi: float) -> dict:
    """Per span name: n, total_s, self_s, and the same per enclosing span
    name (``within``), over the spans that start in [lo, hi). Spans nest
    per host line (thread): a span's self time is its duration less its
    children's."""
    out = {}
    by_line = defaultdict(list)
    for sp in spans:
        by_line[sp[3]].append(sp)
    for line in by_line.values():
        line.sort(key=lambda sp: (sp[1], -sp[2]))
        stack = []                  # open [span, its children's seconds]

        def close():
            sp, child_s = stack.pop()
            if not lo <= sp[1] < hi:
                return
            dur = (sp[2] - sp[1]) * 1e-9
            own = _entry(out, sp[0])
            within = own.setdefault("within", {})
            for t in [own] + [_entry(within, name) for name in
                              dict.fromkeys(a[0][0] for a in stack)]:
                t["n"] += 1
                t["total_s"] += dur
                t["self_s"] += dur - child_s

        for sp in line:
            while stack and stack[-1][0][2] <= sp[1]:
                close()
            if stack:
                stack[-1][1] += (min(sp[2], stack[-1][0][2]) - sp[1]) * 1e-9
            stack.append([sp, 0.0])
        while stack:
            close()
    return out


def stalls(events: dict, lo: float, hi: float, top: int = 5) -> list:
    """The ``top`` longest turns of the load generator's loop in [lo, hi):
    from the start of one of its spans (``bench.wait_due`` sleeps at most
    2 ms, ``bench.submit`` sends what is due) to the start of the next,
    so a turn far longer than 2 ms is a stretch in which the generator did
    not run. Each with the ``lsh.*`` spans open then on any thread:
    [[start_s from lo, seconds, {span: seconds of overlap}]]."""
    starts = sorted(s for n, s, _ in events["host"]
                    if n in GENERATOR and lo <= s < hi)
    turns = sorted(zip(starts, starts[1:]), key=lambda t: t[0] - t[1])
    out = []
    for a, b in turns[:top]:
        open_ = defaultdict(float)
        for name, s, e, *_ in events.get("spans", []):
            c = min(e, b) - max(s, a)
            if c > 0:
                open_[name] += c * 1e-9
        out.append([(a - lo) * 1e-9, (b - a) * 1e-9,
                    dict(sorted(open_.items(), key=lambda kv: -kv[1]))])
    return out


def reduce(events: dict) -> dict:
    """``spans``, ``scope_s``, ``idle`` and ``stalls`` (module docstring).
    Device figures are averaged over the devices that ran anything."""
    lo, hi = _window(events)
    spans = events.get("spans", [])
    labels = [(s, e, n, (1, s)) for n, s, e, *_ in spans]
    labels += [(s, e, n, (0, s)) for n, s, e in events["host"]
               if n != trace.WINDOW_SPAN]
    pieces = _innermost(labels)
    idle, scope, n_dev = defaultdict(float), {}, 0
    for d in events["devices"].values():
        intervals = [(s, e) for _, s, e in d["ops"] + d["modules"]]
        if not intervals:
            continue
        n_dev += 1
        _overlap(stats.gaps(intervals, lo, hi), pieces, idle, "other")
        # executions counted as trace.reduce counts them: inside the window
        runs = sorted((s, e) for _, s, e in d["modules"]
                      if s >= lo and e <= hi)
        tags = d.get("op_stage") or [["", UNSCOPED]] * len(d["ops"])
        per_op = _innermost([(s, e, tuple(tag), (s,))
                             for (_, s, e), tag in zip(d["ops"], tags)])
        j = 0
        for rs, re_ in runs:
            while j < len(per_op) and per_op[j][1] <= rs:
                j += 1
            k = j
            while k < len(per_op) and per_op[k][0] < re_:
                a, b, (program, stage) = per_op[k]
                c = min(b, re_) - max(a, rs)
                if c > 0 and program:
                    prog = scope.setdefault(program, defaultdict(float))
                    prog[stage] += c * 1e-9
                k += 1
    if not n_dev:
        raise ValueError("the trace holds no device plane")
    return {
        "window_s": (hi - lo) * 1e-9,
        "spans": span_stats(spans, lo, hi),
        "scope_s": {p: {k: v / n_dev for k, v in st.items()}
                    for p, st in scope.items()},
        "idle": {k: v / n_dev for k, v in
                 sorted(idle.items(), key=lambda kv: -kv[1])},
        "stalls": stalls(events, lo, hi),
    }


# ---------------------------------------------------------------------------
# What a per-layer reader calls
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> dict:
    return reduce(extract(path))


def of_run(ctx) -> dict | None:
    """The reduction of the trace this run wrote, or None: no trace, a
    trace whose window is not the run's, or nothing to reduce."""
    t = ctx.get("trace")
    found = glob.glob(str(OUT_DIR / "*" / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    if not t or not found:
        return None
    path = max(found, key=os.path.getmtime)
    try:
        s = _reduced(path, os.stat(path).st_mtime_ns)
    except ValueError:
        return None
    return s if abs(s["window_s"] - t["window_s"]) < 1e-6 else None


def stage_ms(ctx, stage: str, program: str = "segmented_query"):
    """Device ms of one query stage per execution of the program, or None
    where no op of the program carries a stage (a program without
    scopes)."""
    s, t = of_run(ctx), ctx.get("trace")
    per = (s or {}).get("scope_s", {}).get(program, {})
    n = (t or {}).get("program_n", {}).get(program)
    if not n or not any(k in per for k in STAGES):
        return None
    return 1e3 * per.get(stage, 0.0) / n


def span_ms(ctx, name: str, which: str = "total_s"):
    """Mean ms of a span (``total_s`` or ``self_s``), or None where the
    run has none."""
    sp = ((of_run(ctx) or {}).get("spans") or {}).get(name)
    if not sp or not sp["n"]:
        return None
    return 1e3 * sp[which] / sp["n"]


FOLD = "lsh.fold"


def fold_stage_s(ctx, stage: str):
    """Seconds per fold of one phase: the self time of its
    ``lsh.fold.<stage>`` spans inside ``lsh.fold`` spans, or for
    ``yield`` the ``lsh.yield`` spans there, over the window's folds;
    None where the window holds no fold."""
    sp = (of_run(ctx) or {}).get("spans") or {}
    folds = sp.get(FOLD, {}).get("n")
    if not folds:
        return None
    name, which = ((f"{PREFIX}yield", "total_s") if stage == "yield"
                   else (f"{FOLD}.{stage}", "self_s"))
    inside = sp.get(name, {}).get("within", {}).get(FOLD)
    return (inside[which] if inside else 0.0) / folds


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="an .xplane.pb file, or a directory "
                                 "searched for the newest one")
    ap.add_argument("--save", help="write the extracted events here "
                                   "(gzipped JSON)")
    args = ap.parse_args(argv)
    path = args.path
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            ap.error(f"no .xplane.pb under {path}")
        path = max(found, key=os.path.getmtime)
    events = extract(path)
    if args.save:
        trace.save(events, Path(args.save))
    out = reduce(events)
    out["trace"] = trace.reduce(events)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
