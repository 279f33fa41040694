"""Benchmark harness: one module per paper table + validation benches.
Prints ``name,us_per_call,derived`` CSV rows (stdout)."""

from __future__ import annotations

import sys


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (collision, durability, fused_probe,
                            hash_throughput, index_ingest,
                            index_multiprobe, index_mutation, index_qps,
                            index_sharded, kernels, recall, serving_slo,
                            table1_e2lsh, table2_srp)
    print("name,us_per_call,derived")
    rows = []
    rows += table1_e2lsh.run()
    rows += table2_srp.run()
    rows += collision.run()
    rows += recall.run()
    rows += index_qps.run()
    rows += index_multiprobe.run()
    rows += fused_probe.run()
    rows += index_sharded.run()
    rows += index_mutation.run()
    rows += index_ingest.run()
    rows += serving_slo.run()
    rows += durability.run()
    rows += hash_throughput.run()
    rows += kernels.run()
    print(f"# {len(rows)} benchmark rows", file=sys.stderr)


if __name__ == "__main__":
    main()
