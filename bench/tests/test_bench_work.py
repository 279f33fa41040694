"""The implementation-independent work count, at the cells' shapes."""

import pytest

from bench import spec, work

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def cfg(name):
    return spec.config(spec.load_benchmark(), name)


def test_sift1m_batch_t8_is_memory_bound():
    c = cfg("sift1m-cp-e2lsh")
    assert work.item_bytes(c) == 512                 # 128 float32
    assert work.hash_flops(c) == 64 * 2 * 128        # K*L dense dots
    assert work.score_flops(c) == 2 * 128
    n_cand = 1024 * 1144                             # T=8, PR 12's mean
    w = work.query_work(c, 1024, 8, n_cand)
    assert w["bytes"] == n_cand * 516 + 1024 * 512 + 1024 * 8 * 8 * 4
    assert w["flops"] == 1024 * 16384 + n_cand * 256
    t, bound = work.least_time(w, PEAKS)
    assert bound == "memory"
    assert t == pytest.approx(605_257_728 / 819e9)   # ~0.74 ms


# 1M CP tensors of 12 x 12 x 12 at rank 4, hashed and re-ranked in
# format: a deferred cell's shape
CP1M = {"data": {"kind": "cp_random", "n": 1000000, "dims": [12, 12, 12],
                 "rank": 4},
        "family": {"kind": "cp-e2lsh", "num_codes": 8, "num_tables": 8,
                   "rank": 4}}


def test_cp1m_batch_t1_counts_in_format_rows():
    c = CP1M
    assert work.item_bytes(c) == 4 * 3 * 12 * 4      # 576 B per CP item
    inner = 3 * 2 * 12 * 4 * 4 + 3 * 4 * 4           # Grams + products
    assert work.hash_flops(c) == 64 * inner
    assert work.score_flops(c) == inner
    w = work.query_work(c, 1024, 1, 5018)
    assert w["bytes"] == 5018 * 580 + 1024 * 576 + 1024 * 8 * 1 * 4
    assert w["flops"] == 1024 * 64 * inner + 5018 * inner
    assert work.least_time(w, PEAKS)[1] == "memory"


def test_more_candidates_more_bytes():
    c = cfg("sift1m-cp-e2lsh")
    a = work.query_work(c, 64, 1, 1000)["bytes"]
    b = work.query_work(c, 64, 1, 2000)["bytes"]
    assert b - a == 1000 * 516
