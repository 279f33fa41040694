"""A few-thousand-item copy of the benchmark for CPU tests: the real
harness, drivers, readers and reference, with tiny configurations,
mixes and limits written into a temporary checkout."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import spec

# On the CPU an f32 matrix product is exact to f32, so the tiny configs
# state float32 hash operands (the chip's default rounds them to bf16).
DENSE = {
    "name": "tiny-dense", "source": "https://arxiv.org/abs/1807.05614",
    "data": {"kind": "clustered_dense", "n": 3000, "dims": [8, 16],
             "cluster_size": 1000, "cluster_spread": 0.5,
             "query_noise": 0.2},
    "family": {"kind": "cp-e2lsh", "num_codes": 8, "num_tables": 8,
               "rank": 4, "bucket_width": 12.0},
    "metric": "euclidean",
    "index": {"bucket_cap": 64, "max_deltas": 2, "build_batch": 1024,
              "mults_seed": 0},
    "durability": {"snapshot_every": 512},
    "precision": {"data": "float32", "scores": "float32",
                  "hash_operands": "float32"},
    "reduced": ["data.n"], "assumed": []}
CP = dict(DENSE, name="tiny-cp",
          data={"kind": "cp_random", "n": 2000, "dims": [6, 6, 6],
                "rank": 4, "query_noise": 0.01},
          family=dict(DENSE["family"], bucket_width=1.0))
TRAFFIC = {
    "tiny-batch-t8": {"kind": "closed_batch", "batch": 64, "probes": 8,
                      "topk": 10, "pool": 512},
    "tiny-batch-t1": {"kind": "closed_batch", "batch": 64, "probes": 1,
                      "topk": 10, "pool": 512},
    "tiny-served": {"kind": "open_loop", "rate_per_s": 200, "probes": 1,
                    "topk": 10, "pool": 512, "max_batch": 16,
                    "deadline_ms": 5, "trace_seconds": None},
    "tiny-churn": {"kind": "churn", "insert_batch": 64, "delete_batch": 64,
                   "query_rate_per_s": 100, "probes": 1, "topk": 10,
                   "pool": 512, "max_batch": 2, "deadline_ms": 5,
                   "trace_seconds": None, "check_inserts": 16,
                   "check_deletes": 16}}
CELLS = {"tiny-dense.batch-t8": ("tiny-dense", "tiny-batch-t8"),
         "tiny-cp.batch-t1": ("tiny-cp", "tiny-batch-t1"),
         "tiny-dense.served": ("tiny-dense", "tiny-served"),
         "tiny-dense.churn": ("tiny-dense", "tiny-churn")}
LIMITS = {"numbers": {"score_gap": 1e-5, "topk_mismatch": 0.05,
                      "ncand_mismatch": 0.05, "unanswered": 0,
                      "window_compiles": 0},
          "compare": {"sample": 32, "tie_rtol": 1e-3}}
BATCH = ["tiny-dense.batch-t8", "tiny-cp.batch-t1"]
E2E = [("queries_per_s", "queries/s", "higher", BATCH),
       ("p99_ms", "ms", "lower", ["tiny-dense.served"]),
       ("mutations_per_s", "items/s", "higher", ["tiny-dense.churn"]),
       ("setup_s", "s", "lower", None)]
# the real readers, each on the tiny cells of its kind
PER_LAYER = [
    ("query.device_ms", "ms", "queries_per_s", BATCH),
    ("segmented_query_roofline", "%", "queries_per_s", BATCH),
    ("device.idle_pct.batch", "%", "queries_per_s", BATCH),
    ("device.idle_pct.served", "%", "p99_ms", ["tiny-dense.served"]),
    ("device.idle_pct.churn", "%", "mutations_per_s", ["tiny-dense.churn"]),
    ("wal.commit_ms", "ms", "mutations_per_s", ["tiny-dense.churn"]),
    ("compact.fold_s", "s", "mutations_per_s", ["tiny-dense.churn"])]
CHURN_LIMITS = {"numbers": dict(LIMITS["numbers"], window_score_gap=1e-5,
                                window_topk_mismatch=0.05,
                                window_ncand_mismatch=0.05,
                                inserts_lost=0, deletes_found=0,
                                mutations_failed=0),
                "compare": dict(LIMITS["compare"], self_match_dist=0.5)}


def _dump(path: Path, obj) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path) -> Path:
    """A checkout in ``tmp``: the bench directory, tiny files, and a
    BENCHMARK.json whose metrics the real readers read."""
    shutil.copytree(spec.BENCH_DIR, tmp / "bench", ignore=shutil.ignore_patterns(
        ".out", "tests", "__pycache__"))
    real = spec.load_benchmark()
    for cfg in (DENSE, CP):
        _dump(tmp / "bench" / "configs" / f"{cfg['name']}.json", cfg)
    for name, tr in TRAFFIC.items():
        _dump(tmp / "bench" / "traffic" / f"{name}.json", tr)
    workloads = []
    for cell, (cfg, tr) in CELLS.items():
        _dump(tmp / "bench" / "limits" / f"{cell}.json",
              CHURN_LIMITS if "churn" in cell else LIMITS)
        workloads.append({"name": cell, "config": cfg, "traffic": tr,
                          "chips": 1, "why": "CPU test"})
    e2e, per_layer = [], []
    for name, unit, better, cells in E2E:
        e2e.append({"name": name, "unit": unit, "better": better,
                    "bound": 0.25, "source": "host_clock",
                    **({"workloads": cells} if cells else {})})
    for name, unit, moves, cells in PER_LAYER:
        per_layer.append({"name": name, "unit": unit, "better": "lower",
                          "source": "program_counter", "layer": "test",
                          "moves": moves, "workloads": cells})
    bench = dict(real, configs=[
        {"name": c["name"], "source": c["source"],
         "file": f"bench/configs/{c['name']}.json", "reduced": c["reduced"],
         "why": "CPU test"} for c in (DENSE, CP)],
        workloads=workloads, end_to_end=e2e, per_layer=per_layer)
    _dump(tmp / "BENCHMARK.json", bench)
    return tmp
