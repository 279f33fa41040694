"""The profiler trace of a run, reduced to what the per-layer metrics read.

``capture`` wraps the traced window in ``jax.profiler`` tracing; the
harness's own spans (``jax.profiler.TraceAnnotation``, named ``bench.*``)
land on the host lines of the same trace. ``extract`` keeps, from the
``.xplane.pb`` file, the device operations and program executions of each
device plane and the ``bench.*`` host spans; ``reduce`` turns those into:

* ``busy_s``: the union of the intervals in which a program or an
  operation ran, inside the window, averaged over the devices that ran
  any;
* ``program_s`` / ``program_n``: device seconds and executions of each
  jitted program (``XLA Modules`` events, named without ``jit_`` and any
  suffix);
* ``device_ops``: the ten operations that took most device time;
* ``idle_gaps``: idle device time inside the window by what the host was
  doing then (each stretch of a gap goes to the innermost ``bench.*``
  span covering it, else to ``other``), top ten.
"""

from __future__ import annotations

import contextlib
import glob
import gzip
import json
import os
import re
from collections import defaultdict
from pathlib import Path

from bench import stats

WINDOW_SPAN = "bench.window"
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"


@contextlib.contextmanager
def capture(log_dir: Path):
    """Profile the enclosed block into ``log_dir``; yields a dict that
    holds the ``.xplane.pb`` path once the block has ended."""
    import jax
    out = {}
    log_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(log_dir))
    try:
        yield out
    finally:
        jax.profiler.stop_trace()
        found = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        out["path"] = found[-1] if found else None


def program_name(name: str) -> str:
    name = re.sub(r"^jit_", "", name)
    return re.split(r"[(\s.]", name, maxsplit=1)[0] or name


def op_name(text: str) -> str:
    """An HLO instruction's text -> its name and result shape, e.g.
    ``fusion.3 f32[4194304,128]`` (the layout and operands dropped)."""
    if " = " not in text:
        return text
    name, rest = text.split(" = ", 1)
    shape = ("(tuple)" if rest.startswith("(")
             else re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]))
    return f"{name.lstrip('%')} {shape}"[:96]


def extract(xplane_path: str) -> dict:
    """{"devices": {plane: {"ops": [[name, start_ns, end_ns]], "modules":
    [...]}}, "host": [[span, start_ns, end_ns]]} from an xplane file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, modules = [], []
            for line in plane.lines:
                dest = (ops if line.name == _OPS_LINE else
                        modules if line.name == _MODULES_LINE else None)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append([op_name(e.name), float(e.start_ns),
                                 float(e.start_ns + e.duration_ns)])
            if ops or modules:   # planes of other devices stay empty
                devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append([e.name, float(e.start_ns),
                                     float(e.start_ns + e.duration_ns)])
    return {"devices": devices, "host": host}


def save(events: dict, path: Path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(events, f)


def load(path: Path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _window(events: dict) -> tuple[float, float]:
    spans = [(s, e) for n, s, e in events["host"] if n == WINDOW_SPAN]
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    ends = [(s, e) for d in events["devices"].values()
            for _, s, e in d["ops"] + d["modules"]]
    if not ends:
        raise ValueError("the trace holds no window span and no device "
                         "event")
    return min(s for s, _ in ends), max(e for _, e in ends)


def _label(host: list, t: float) -> str:
    best, start = "other", -float("inf")
    for name, s, e in host:
        if s <= t < e and s > start and name != WINDOW_SPAN:
            best, start = name, s
    return best


def reduce(events: dict, top: int = 10) -> dict:
    lo, hi = _window(events)
    busy, program_s, program_n = [], defaultdict(float), defaultdict(int)
    op_s, idle = defaultdict(float), defaultdict(float)
    for d in events["devices"].values():
        intervals = [(s, e) for _, s, e in d["ops"] + d["modules"]]
        if not intervals:
            continue
        busy.append(stats.union_length(intervals, lo, hi))
        for name, s, e in d["modules"]:
            if s >= lo and e <= hi:
                program_s[program_name(name)] += (e - s) * 1e-9
                program_n[program_name(name)] += 1
        for name, s, e in d["ops"]:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                op_s[name] += c * 1e-9
        for s, e in stats.gaps(intervals, lo, hi):
            cuts = sorted({s, e} | {t for _, a, b in events["host"]
                                    for t in (a, b) if s < t < e})
            for a, b in zip(cuts, cuts[1:]):
                idle[_label(events["host"], (a + b) / 2)] += (b - a) * 1e-9
    if not busy:
        raise ValueError("the trace holds no device plane")
    n_dev = len(busy)

    def ranked(d, scale=1.0):
        return [[k, v * scale] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": sum(busy) / n_dev * 1e-9,
            "devices": n_dev,
            "program_s": {k: v / n_dev for k, v in program_s.items()},
            "program_n": {k: v / n_dev for k, v in program_n.items()},
            "device_ops": ranked(op_s, 1.0 / n_dev),
            "idle_gaps": ranked(idle, 1.0 / n_dev)}
