"""Closed-loop query batches: one client sends a batch of ``batch``
queries, waits for the answer, and sends the next.

Traffic parameters: ``batch``, ``probes`` (T), ``topk``, ``pool`` (size
of the seeded pool of planted queries the client holds on the host; each
batch draws ``batch`` distinct rows of it). The entry the window drives
is ``LSHService.query_arrays``. End-to-end: ``queries_per_s``, all the
queries answered over all the time of the window.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, deploy, stats


def run(ctx):
    from bench.harness import Result

    tr, cfg = ctx.traffic, ctx.config
    b, probes, topk = tr["batch"], tr["probes"], tr["topk"]
    dep = deploy.make(cfg, ctx.seed)
    ctx.part("data_and_family")
    svc = deploy.serve(dep)
    ctx.part("build")
    _, pool = deploy.query_pool(dep, ctx.seed, tr["pool"])
    rng = deploy.host_rng(ctx.seed, 2)
    draw = lambda: rng.choice(tr["pool"], size=b, replace=False)  # noqa: E731
    ctx.part("query_pool")
    for _ in range(2):
        svc.query_arrays(deploy.rows(pool, draw()), topk, probes=probes)
    ctx.part("warm_up")

    batches = []
    with ctx.window() as win:
        while time.perf_counter() - win.t0 < ctx.seconds:
            with ctx.span("bench.generate"):
                r = draw()
                q = deploy.rows(pool, r)
            with ctx.span("bench.query_call"):
                ids, scores, n_cand = svc.query_arrays(q, topk, probes=probes)
            batches.append((r, ids, scores, n_cand, time.perf_counter()))
        elapsed = batches[-1][-1] - win.t0
    ctx.read_memory()
    traced = [(b, int(x[3].sum())) for x in batches]

    # the sample, drawn from the seed, of every query answered
    cmp = ctx.limits["compare"]
    pick = deploy.host_rng(ctx.seed, 4).choice(
        len(batches) * b, size=min(cmp["sample"], len(batches) * b),
        replace=False)
    rows = [(i // b, i % b) for i in np.sort(pick)]
    queries = check.ref_layout(deploy.rows(
        pool, np.array([batches[j][0][i] for j, i in rows])))
    prog = tuple(np.stack([batches[j][k][i] for j, i in rows])
                 for k in (1, 2, 3))
    items = dep.items_host()
    dep.service = dep.corpus = svc = None
    t0 = time.perf_counter()
    numbers = check.against_reference(
        cfg, dep.host_family, items, queries, prog, probes, topk,
        cmp["tie_rtol"])
    numbers["unanswered"] = 0
    return Result(
        attempted=len(batches) * b, failed=0,
        e2e={"queries_per_s": stats.rate(len(batches) * b, elapsed)},
        numbers=numbers,
        layer={"traced_batches": traced, "batch": b, "probes": probes},
        notes={"window": f"{len(batches)} batches of {b} in {elapsed:.3f} s",
               "mean_candidates": float(np.mean(
                   np.concatenate([x[3] for x in batches]))),
               "reference_s": f"{time.perf_counter() - t0:.3f}"})
