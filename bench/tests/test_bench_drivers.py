"""Each traffic driver end to end at a few thousand items on the CPU,
through the harness's test entry (the command itself refuses a CPU)."""

import subprocess
import sys

import pytest

from bench import harness, spec

SEED = 2**31 + 11     # the driver's seeds exceed 32 signed bits


def run(root, cell, seconds=1.0, traced=False):
    return harness.run_cell(cell, SEED, seconds, traced, root=root,
                            require_tpu=False)


@pytest.mark.parametrize("cell,metric", [
    ("tiny-dense.batch-t8", "queries_per_s"),
    ("tiny-cp.batch-t1", "queries_per_s"),
    ("tiny-dense.served", "p99_ms"),
    ("tiny-dense.churn", "mutations_per_s"),
])
def test_driver_end_to_end(tiny_root, cell, metric):
    line = run(tiny_root, cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"][metric]["value"] > 0
    assert line["metrics"]["setup_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["window_compiles"]["value"] == 0


def test_traced_run_reports_per_layer_metrics_only(tiny_root):
    line = run(tiny_root, "tiny-dense.churn", traced=True)
    assert line["correct"]
    names = set(line["metrics"])
    assert "mutations_per_s" not in names and "setup_s" not in names
    assert "wal.commit_ms" in names


def test_traced_run_measures_trace_seconds(tmp_path):
    """A traced run's window is the mix's ``trace_seconds``, so the
    profiler records the whole of it and stops after the generator."""
    import json
    from bench.tests import tiny
    root = tiny.make_root(tmp_path)
    mix = dict(tiny.TRAFFIC["tiny-served"], trace_seconds=0.3)
    (root / "bench" / "traffic" / "tiny-served.json").write_text(
        json.dumps(mix))
    line = run(root, "tiny-dense.served", seconds=1.0, traced=True)
    assert line["correct"]
    assert line["attempted"] == round(mix["rate_per_s"] * 0.3)


def test_same_seed_same_inputs(tiny_root):
    from bench import deploy
    cfg = spec.config(spec.load_benchmark(tiny_root), "tiny-dense",
                      tiny_root)
    a, b = deploy.make(cfg, SEED), deploy.make(cfg, SEED)
    assert (a.items_host() == b.items_host()).all()
    assert (deploy.make(cfg, SEED + 1).items_host() != a.items_host()).any()


def test_command_refuses_a_cpu():
    out = subprocess.run(
        [sys.executable, str(spec.BENCH_DIR / "run.py"), "--workload",
         "sift1m.batch-t8", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})
    assert out.returncode == harness.NO_CHIP_EXIT
    assert out.stdout == ""
