"""A configuration, a traffic kind with its mix, and a per-layer metric
added as new files plus BENCHMARK.json entries, with no edit to a file
the benchmark already has."""

import hashlib
import json

from bench import harness, spec
from bench.tests import tiny


def _digest(bench_dir):
    return {p.relative_to(bench_dir): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(bench_dir.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_from_new_files_only(tmp_path):
    root = tiny.make_root(tmp_path)
    bdir = root / "bench"
    before = _digest(bdir)

    new_cfg = dict(tiny.DENSE, name="tiny-new",
                   data=dict(tiny.DENSE["data"], n=2500))
    (bdir / "configs" / "tiny-new.json").write_text(json.dumps(new_cfg))
    (bdir / "drivers" / "tiny_kind.py").write_text(
        '"""A new traffic kind: closed batches under another name."""\n'
        "from bench.drivers.closed_batch import run  # noqa: F401\n")
    (bdir / "traffic" / "tiny-new-mix.json").write_text(json.dumps(
        {"kind": "tiny_kind", "batch": 32, "probes": 2, "topk": 5,
         "pool": 256}))
    (bdir / "metrics" / "new.rows_per_batch.py").write_text(
        '"""A new reader."""\n\n\ndef read(ctx):\n'
        '    return float(ctx["batch"])\n')
    (bdir / "limits" / "tiny-new.cell.json").write_text(
        json.dumps(tiny.LIMITS))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-new", "source": "test",
                             "file": "bench/configs/tiny-new.json",
                             "reduced": [], "why": "CPU test"})
    bench["workloads"].append({"name": "tiny-new.cell", "config": "tiny-new",
                               "traffic": "tiny-new-mix", "chips": 1,
                               "why": "CPU test"})
    for m in bench["end_to_end"]:
        if m["name"] == "queries_per_s":
            m["workloads"].append("tiny-new.cell")
    bench["per_layer"].append({
        "name": "new.rows_per_batch", "unit": "queries/batch",
        "better": "higher", "source": "program_counter", "layer": "device",
        "moves": "queries_per_s", "workloads": ["tiny-new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _digest(bdir)
    assert all(after[p] == d for p, d in before.items())   # nothing edited
    assert spec.config(bench, "tiny-new", root)["data"]["n"] == 2500
    assert spec.traffic("tiny-new-mix", bdir)["kind"] == "tiny_kind"

    line = harness.run_cell("tiny-new.cell", 5, 0.5, False, root=root,
                            require_tpu=False)
    assert line["correct"] and "queries_per_s" in line["metrics"]
    line = harness.run_cell("tiny-new.cell", 5, 0.5, True, root=root,
                            require_tpu=False)
    assert line["metrics"]["new.rows_per_batch"]["value"] == 32.0


def test_split_metric_reads_with_its_quantity_reader(tmp_path):
    """``device.idle_pct.churn`` has no file of its own: the reader of
    ``device.idle_pct`` reads it; a name with no reader at all is an
    error."""
    root = tiny.make_root(tmp_path)
    bdir = root / "bench"
    read = spec.metric_reader("device.idle_pct.churn", bdir)
    assert read is not None
    assert read({"trace": {"busy_s": 3.0, "window_s": 4.0}}) == 25.0
    (bdir / "metrics" / "device.idle_pct.churn.py").write_text(
        '"""A reader of its own wins."""\n\n\ndef read(ctx):\n'
        '    return 1.0\n')
    assert spec.metric_reader("device.idle_pct.churn", bdir)({}) == 1.0
    try:
        spec.metric_reader("nothing.here", bdir)
    except spec.SpecError:
        pass
    else:
        raise AssertionError("a metric with no reader was found")
