"""The control of a cell's comparison: the plain reference put in the
program's place, one step below the precision the configuration states
(hash operands bfloat16 -> float8_e4m3fn, float32 scores -> bfloat16), at
the cell's own size. Its numbers have to fail the cell's limits; the
benchmark's own runs never run it.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it prints one JSON line: the numbers the cell's comparison
reads for the control, each with its limit, and whether any failed.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

_BENCH = Path(__file__).resolve().parent
for _p in (_BENCH.parent, _BENCH.parent / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import check, deploy, reference, spec  # noqa: E402

LOWER = {"float64": "float32", "float32": "bfloat16",
         "bfloat16": "float8_e4m3fn"}


def control_numbers(name: str, seed: int, root: Path = spec.REPO_DIR,
                    bench: dict | None = None) -> dict:
    """The control's numbers for one seed, over the planted queries the
    cell's comparison samples."""
    bench = spec.load_benchmark(root) if bench is None else bench
    cell = spec.cell(bench, name)
    bench_dir = Path(root) / "bench"
    cfg = spec.config(bench, cell["config"], root)
    tr = spec.traffic(cell["traffic"], bench_dir)
    limits = spec.limits(name, bench_dir)
    dep = deploy.make(cfg, seed)
    _, pool = deploy.query_pool(dep, seed, tr["pool"])
    pick = deploy.host_rng(seed, 4).choice(
        tr["pool"], size=limits["compare"]["sample"], replace=False)
    queries = check.ref_layout(deploy.rows(pool, pick))
    items = dep.items_host()
    dep.corpus = None
    probes, topk = tr["probes"], tr["topk"]
    prec = cfg["precision"]
    low = reference.Index(dep.host_family, items,
                          LOWER[prec["hash_operands"]])
    ctrl = reference.answer(low, cfg["metric"], queries, probes,
                            cfg["index"]["bucket_cap"], topk,
                            score_precision=LOWER[prec["scores"]])
    numbers = check.against_reference(
        cfg, dep.host_family, items, queries, ctrl, probes, topk,
        limits["compare"]["tie_rtol"])
    for name in ("score_gap", "topk_mismatch", "ncand_mismatch"):
        # churn compares window answers by the same numbers
        if "window_" + name in limits["numbers"]:
            numbers["window_" + name] = numbers[name]
    return {k: v for k, v in numbers.items() if k in limits["numbers"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    limits = spec.limits(args.workload)["numbers"]
    for seed in args.seeds:
        numbers = control_numbers(args.workload, seed)
        ok, table = check.judge(numbers, {k: limits[k] for k in numbers})
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails": not ok, "numbers": table}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
