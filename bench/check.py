"""The comparison that decides ``correct``: what the timed path answered,
against the plain reference, each number beside its limit.

Numbers over a sample of answered queries (limits in ``limits/<cell>.json``):

* ``score_gap``: the widest gap between a score the program returned and
  the exact (float64) score of the item it named, on the scale the
  arithmetic works at: for a distance d the gap of d^2 over
  ||q||^2 + ||y||^2 (the expanded form's own error scale, so a self-match
  at distance 0 reads its rounding and not a ratio to 0), for a cosine
  the plain difference;
* ``topk_mismatch``: the share of queries whose top-k is not the
  reference's: at some rank the exact score of the program's item differs
  from the reference's by more than ``tie_rtol`` (so equal-score swaps
  pass), a rank is filled on one side only, or an id repeats;
* ``ncand_mismatch``: the share of queries whose candidate count differs
  from the reference's distinct probe-window members;
* counts with the limit 0: ``unanswered`` (a request that never came
  back), and in churn ``inserts_lost`` (an acknowledged insert the
  reference finds in its own bucket window but the index did not return
  first) and ``deletes_found`` (an acknowledged delete that came back).

Churn reads the same three numbers twice: over its self-queries, on the
store it left with deltas and tombstones outstanding, and, named
``window_*``, over the sampled window answers, each against the store
state that fits it best (``drivers/churn.py``).

The candidate count is the only trace of the candidate set that the
timed path returns, so the sets themselves are not compared.
"""

from __future__ import annotations

import numpy as np

from bench import reference

# A score gap that could not be measured (an id out of range) reads as
# this, far above any limit; JSON has no infinity.
UNMEASURABLE = 1e30


def ref_layout(queries):
    """Program-format host queries (dense (Q, *dims) or a CPTensor) ->
    the reference's layout (flat rows, or a tuple of factor arrays)."""
    if hasattr(queries, "factors"):
        return tuple(np.asarray(f) for f in queries.factors)
    q = np.asarray(queries)
    return q.reshape(q.shape[0], -1)


def take(items, idx):
    if isinstance(items, tuple):
        return tuple(f[idx] for f in items)
    return items[idx]


def count(items) -> int:
    return (items[0] if isinstance(items, tuple) else items).shape[0]


def exact_of(metric: str, items, queries, ids):
    """(exact score, error scale) of every (query, id) the program named:
    NaN where the id is -1 fill, +inf where it is out of range. The scale
    is ||q||^2 + ||y||^2 for a distance and 1 for a cosine."""
    n = count(items)
    out, scale = np.full(ids.shape, np.nan), np.ones(ids.shape)
    for q in range(ids.shape[0]):
        row = ids[q]
        ok = (row >= 0) & (row < n)
        if ok.any():
            query = take(queries, q)
            out[q, ok] = reference.scores(metric, query, items, row[ok])
            if metric == "euclidean":
                scale[q, ok] = (reference.sq_norms(take(items, row[ok]))
                                + reference.sq_norms(take(queries, [q]))[0])
        out[q, row >= n] = np.inf
    return out, scale


def score_gap(metric: str, prog_scores, exact) -> float:
    exact, scale = exact
    valid = ~np.isnan(exact)
    if not valid.any():
        return 0.0
    e, p = exact[valid], np.asarray(prog_scores, np.float64)[valid]
    if not np.all(np.isfinite(e)) or not np.all(np.isfinite(p)):
        return UNMEASURABLE
    if metric == "euclidean":
        return float(np.max(np.abs(p * p - e * e) / scale[valid]))
    return float(np.max(np.abs(p - e)))


def topk_mismatch(prog_ids, exact, ref_ids, ref_scores,
                  tie_rtol: float) -> float:
    exact = exact[0]
    bad = 0
    for q in range(prog_ids.shape[0]):
        pv, rv = prog_ids[q] >= 0, ref_ids[q] >= 0
        ids = prog_ids[q][pv]
        if (not np.array_equal(pv, rv) or len(set(ids.tolist())) != ids.size
                or not np.all(np.isfinite(exact[q][pv]))):
            bad += 1
            continue
        r = ref_scores[q][rv]
        if np.any(np.abs(exact[q][pv] - r)
                  > tie_rtol * np.maximum(np.abs(r), 1e-12)):
            bad += 1
    return bad / max(prog_ids.shape[0], 1)


def compare(metric: str, items, queries, prog, ref,
            tie_rtol: float) -> dict:
    """Numbers of one sample: ``prog`` and ``ref`` are each (ids, scores,
    n_cand) for the same queries (reference layout)."""
    p_ids, p_scores, p_n = (np.asarray(a) for a in prog)
    r_ids, r_scores, r_n = (np.asarray(a) for a in ref)
    exact = exact_of(metric, items, queries, p_ids)
    return {"score_gap": score_gap(metric, p_scores, exact),
            "topk_mismatch": topk_mismatch(p_ids, exact, r_ids, r_scores,
                                           tie_rtol),
            "ncand_mismatch": float(np.mean(p_n != r_n)) if p_n.size else 0.0}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}): every number at or under
    its limit, and every limit read."""
    table = {}
    ok = True
    for name, limit in limits.items():
        if name not in numbers:
            ok = False
            table[name] = {"value": None, "limit": limit}
            continue
        v = float(numbers[name])
        table[name] = {"value": v, "limit": limit}
        ok &= bool(v <= limit)
    return ok, table


def against_reference(config: dict, host_family, items, queries, prog,
                      probes: int, topk: int, tie_rtol: float) -> dict:
    """Numbers of a sample of answers from a store of one segment with
    every item live: the reference indexes ``items`` (arrival order) at
    the configuration's hash precision and answers ``queries`` exactly."""
    index = reference.Index(host_family, items,
                            config["precision"]["hash_operands"])
    ref = reference.answer(index, config["metric"], queries, probes,
                           config["index"]["bucket_cap"], topk)
    return compare(config["metric"], items, queries, prog, ref, tie_rtol)
