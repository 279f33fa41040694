"""BENCHMARK.json keeps to its contract: names, units, keys, and every
name resolves to its file."""

import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys_and_size(bench):
    assert set(bench) == TOP
    assert (spec.REPO_DIR / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_command_and_paths(bench):
    cmd, paths = bench["command"], bench["paths"]
    assert 1 <= len(cmd) <= 32 and all(LINE.match(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (spec.REPO_DIR / p).is_dir()
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in paths)


def test_names_units_and_keys(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"])
        assert LINE.match(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        names.append(c["name"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        for k in ("name", "config", "traffic"):
            assert NAME.match(w[k])
        assert w["chips"] in (1, 4) and LINE.match(w["why"])
        names.append(w["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        names.append(m["name"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert LINE.match(m["layer"])
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert len(names) == len(set(names))
    assert len({(w["config"], w["traffic"])
                for w in bench["workloads"]}) == len(bench["workloads"])


def test_setup_metric_and_every_cell_reports(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        reported = {m["name"] for m in spec.end_to_end(bench, cell)}
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.per_layer(bench, cell)
        assert layer
        assert all(m["moves"] in reported for m in layer)


def test_every_name_resolves_to_its_file(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        cfg = spec.config(bench, c["name"])
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        tr = spec.traffic(w["traffic"])
        assert spec.driver(tr["kind"]).run
        lim = spec.limits(w["name"])
        assert lim["numbers"]["window_compiles"] == 0
        assert lim["numbers"]["unanswered"] == 0
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_full_check_fits_its_time_with_24_cells(bench):
    runs = 2 + 14 * 24
    total = runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_four_chip_cells_at_most_half(bench):
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(bench["workloads"]) // 2, 1)


def test_files_under_paths_have_name_characters():
    for p in spec.BENCH_DIR.rglob("*"):
        rel = p.relative_to(spec.REPO_DIR).as_posix()
        if "__pycache__" in rel or "/.out" in rel:
            continue
        assert PATH.match(rel), rel
    json.loads((spec.BENCH_DIR / "peaks.json").read_text())
