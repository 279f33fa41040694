"""Operations and bytes a query batch needs, counted from the method and
the configuration's shapes, not from how the program computes them.

Bytes: every distinct candidate's row read once, with its id (4 bytes),
the query batch itself, and one 4-byte key per probed bucket. FLOPs: the
batch's K*L hash projections and one cross inner product per candidate
(the norms of the corpus are a per-store cost, not a per-query one).
The least time of a batch is the larger of bytes over the chip's memory
bandwidth and FLOPs over its peak; ``least_time`` says which bound won.
"""

from __future__ import annotations

import math


def item_bytes(config: dict) -> int:
    data = config["data"]
    if data["kind"] == "cp_random":
        return 4 * sum(d * data["rank"] for d in data["dims"])
    return 4 * math.prod(data["dims"])


def _inner_flops(config: dict, rank_x: int) -> int:
    """FLOPs of one <Y, X> with X of CP rank ``rank_x``."""
    data = config["data"]
    if data["kind"] == "cp_random":
        r = data["rank"]
        n = len(data["dims"])
        return sum(2 * d * r * rank_x for d in data["dims"]) + n * r * rank_x
    return 2 * math.prod(data["dims"])


def hash_flops(config: dict) -> int:
    """Per query: K*L projections onto rank-R CP tensors."""
    fam = config["family"]
    return (fam["num_codes"] * fam["num_tables"]
            * _inner_flops(config, fam["rank"]))


def score_flops(config: dict) -> int:
    """Per candidate: the cross inner product with the query."""
    data = config["data"]
    return _inner_flops(config, data.get("rank", 1))


def query_work(config: dict, batch: int, probes: int,
               n_cand: int) -> dict:
    """Bytes and FLOPs of one query batch with ``n_cand`` candidates in
    all (the sum of each query's distinct candidates)."""
    row = item_bytes(config)
    tables = config["family"]["num_tables"]
    return {"bytes": n_cand * (row + 4) + batch * row
            + batch * tables * probes * 4,
            "flops": batch * hash_flops(config) + n_cand * score_flops(config)}


def least_time(w: dict, peaks: dict) -> tuple[float, str]:
    t_mem = w["bytes"] / peaks["hbm_bytes_per_s"]
    t_flop = w["flops"] / peaks["bf16_flops_per_s"]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
