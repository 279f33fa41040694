"""Find what ``BENCHMARK.json`` names: cells, configurations, traffic
mixes, traffic drivers, per-layer metric readers, limits and peaks.

Everything is looked up by name, so a new cell, configuration, traffic
mix or metric is a new file plus a ``BENCHMARK.json`` entry:

* ``configs/<config>.json``   the deployment (the entry's ``file``);
* ``traffic/<traffic>.json``  a traffic mix: its ``kind`` and parameters;
* ``drivers/<kind>.py``       the general generator for a traffic kind;
* ``metrics/<metric>.py``     the reader of one per-layer metric (or of
                              the metric's name less its last part);
* ``limits/<cell>.json``      the limits ``correct`` is judged by;
* ``peaks.json``              the chip's published peaks by device kind.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
REPO_DIR = BENCH_DIR.parent


class SpecError(ValueError):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_benchmark(root: Path = REPO_DIR) -> dict:
    return _json(Path(root) / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json")


def config(bench: dict, name: str, root: Path = REPO_DIR) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(Path(root) / c["file"])
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(Path(bench_dir) / "traffic" / f"{name}.json")


def limits(cell_name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(Path(bench_dir) / "limits" / f"{cell_name}.json")


def peaks(device_kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = _json(Path(bench_dir) / "peaks.json")
    if device_kind not in table["devices"]:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json; "
                        f"known: {sorted(table['devices'])}")
    return table["devices"][device_kind]


def _load(path: Path, what: str):
    """A module loaded by its path (metric names hold dots, so readers
    cannot be imported as package members)."""
    if not path.exists():
        raise SpecError(f"no {what} {path}")
    name = "bench_" + "".join(c if c.isalnum() else "_"
                              for c in str(path.relative_to(path.parents[1])))
    found = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(found)
    found.loader.exec_module(mod)
    return mod


def driver(kind: str, bench_dir: Path = BENCH_DIR):
    """The module ``drivers/<kind>.py``; it defines ``run(ctx)``."""
    return _load(Path(bench_dir) / "drivers" / f"{kind}.py", "traffic driver")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """``read(ctx)`` of the metric's reader: the metric's value, or None
    where the run holds nothing for it to read. The reader of ``a.b.c``
    is ``metrics/a.b.c.py`` or, where there is none, that of ``a.b``: a
    quantity split by the end-to-end metric it moves (``.churn``,
    ``.batch``) keeps one reader."""
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = Path(bench_dir) / "metrics" / (".".join(parts[:k]) + ".py")
        if path.exists():
            return _load(path, "metric reader").read
    raise SpecError(f"no metric reader for {name!r} in "
                    f"{Path(bench_dir) / 'metrics'}")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def end_to_end(bench: dict, cell_name: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> list[dict]:
    """Per-layer metrics of a cell: those listing it, and those without a
    ``workloads`` key whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"]
            if (cell_name in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]
