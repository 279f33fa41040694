"""Open-loop single-row queries: independent users send one query each on
a Poisson schedule, whether or not earlier ones have been answered.

Traffic parameters: ``rate_per_s`` (fixed in the mix), ``probes``,
``topk``, ``pool``, the scheduler's ``max_batch`` and ``deadline_ms``,
and ``trace_seconds`` (the window of a traced run, which the profiler
records whole). The entry the window drives is ``ServingScheduler.query``.
End-to-end: ``p99_ms``, the 99th percentile over every request of the
window, each timed from when it was due; a request that never comes back
counts as answered when the run stopped waiting for it.

Every seed gets the same number of requests, ``rate_per_s * seconds``,
with the gaps drawn from the seed and scaled so the last one is due as
the window closes.
"""

from __future__ import annotations

import functools
import threading
import time

import numpy as np

from bench import check, deploy, stats

DRAIN_S = 60.0   # how long past the window's close an answer may come


def arrivals(rng, rate: float, seconds: float) -> np.ndarray:
    """Due times in (0, seconds]: ``round(rate * seconds)`` requests with
    exponential gaps, scaled to end at ``seconds``."""
    n = max(int(round(rate * seconds)), 1)
    due = np.cumsum(rng.exponential(1.0, n))
    return due * (seconds / due[-1])


class Requests:
    """One open-loop stream: its schedule and, per request, submit and
    answer times in arrays. Only the answers of a sample drawn from the
    seed before the window are kept for the check, so the stream holds
    no object per request and adds nothing to the interpreter's
    collections while the window runs."""

    def __init__(self, ctx, seed_salt: int, rate: float, pool_size: int,
                 sample: int = 0):
        rng = deploy.host_rng(ctx.seed, seed_salt)
        self.due = arrivals(rng, rate, ctx.seconds)
        self.row = rng.integers(0, pool_size, size=self.due.size)
        n = self.due.size
        self.submit = np.zeros(n)
        self.done = np.full(n, np.nan)
        self.ok = np.zeros(n, bool)
        self.sample = np.sort(deploy.host_rng(ctx.seed, seed_salt, 1).choice(
            n, size=min(sample, n), replace=False))
        self._sampled = set(self.sample.tolist())
        self.answers = {}
        self._pending = 0
        self._idle = threading.Condition()

    def _on_done(self, i: int, future) -> None:
        self.done[i] = time.perf_counter()
        if future.exception() is None:
            self.ok[i] = True
            if i in self._sampled:
                self.answers[i] = future.result()
        with self._idle:
            self._pending -= 1
            self._idle.notify_all()

    def drive(self, win, submit, span) -> None:
        """Submit every request at its due time (``submit(i)`` returns the
        future)."""
        t0, n, i = win.t0, self.due.size, 0
        while i < n:
            now = time.perf_counter()
            if now < t0 + self.due[i]:
                with span("bench.wait_due"):
                    time.sleep(min(t0 + self.due[i] - now, 0.002))
                continue
            with span("bench.submit"):
                while i < n and t0 + self.due[i] <= now:
                    with self._idle:
                        self._pending += 1
                    self.submit[i] = time.perf_counter()
                    try:
                        f = submit(i)
                    except Exception:     # refused at submission: missing
                        with self._idle:
                            self._pending -= 1
                    else:
                        f.add_done_callback(
                            functools.partial(self._on_done, i))
                    i += 1

    def wait(self) -> float:
        """Wait for every answer, up to ``DRAIN_S`` past the last due
        time; returns when the waiting stopped."""
        with self._idle:
            self._idle.wait_for(lambda: self._pending == 0, timeout=DRAIN_S)
        return time.perf_counter()

    def answered(self) -> np.ndarray:
        return self.ok

    def latencies_ms(self, t0: float, stopped: float) -> np.ndarray:
        done = np.where(self.ok, self.done, np.nan)
        return 1e3 * stats.latencies_from_due(t0 + self.due, done, stopped)

    def p99_ms(self, t0: float, stopped: float) -> float:
        return stats.percentile(self.latencies_ms(t0, stopped), 99)

    def timeline(self, t0: float, stopped: float, bins: int = 10) -> str:
        """p99 latency (ms) of the requests due in each tenth of the
        window: a stall shows where it happened."""
        parts = np.array_split(self.latencies_ms(t0, stopped), bins)
        return " ".join(f"{stats.percentile(p, 99):.1f}" for p in parts
                        if p.size)

    def lateness(self, t0: float) -> str:
        late = 1e3 * (self.submit - (t0 + self.due))
        return (f"p50 {np.percentile(late, 50):.3f} ms, p99 "
                f"{np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms")


def counters(sched, svc) -> dict:
    return {"sched_requests": sched.stats.requests,
            "sched_batches": sched.stats.batches,
            "wal_ms": svc.stats.wal_ms, "wal_appends": svc.stats.wal_appends,
            "fold_ms": svc.stats.auto_compact_ms,
            "folds": svc.stats.auto_compactions}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def padded_sizes(max_batch: int) -> list[int]:
    """The batch sizes the scheduler dispatches: powers of two up to the
    one that holds ``max_batch``."""
    return [1 << i for i in range((max_batch - 1).bit_length() + 1)]


def warm_queries(svc, pool, max_batch: int, topk: int, probes: int) -> None:
    for b in padded_sizes(max_batch):
        svc.query_arrays(deploy.rows(pool, np.arange(b)), topk,
                         probes=probes)


def sample_answers(req: Requests, pool):
    """The seeded sample's answered requests: (request ids, queries in the
    reference layout, program answers (ids, scores, n_cand))."""
    pick = np.array([i for i in req.sample if req.ok[i]], np.int64)
    queries = check.ref_layout(deploy.rows(pool, req.row[pick]))
    res = [req.answers[i] for i in pick]
    prog = (np.stack([r[0] for r in res]), np.stack([r[1] for r in res]),
            np.array([r[2] for r in res]))
    return pick, queries, prog


def run(ctx):
    from bench.harness import Result
    from repro.serving.scheduler import ServingScheduler

    tr, cfg = ctx.traffic, ctx.config
    probes, topk = tr["probes"], tr["topk"]
    dep = deploy.make(cfg, ctx.seed)
    ctx.part("data_and_family")
    svc = deploy.serve(dep)
    ctx.part("build")
    _, pool = deploy.query_pool(dep, ctx.seed, tr["pool"])
    req = Requests(ctx, 3, tr["rate_per_s"], tr["pool"],
                   ctx.limits["compare"]["sample"])
    ctx.part("query_pool")
    sched = ServingScheduler(svc, max_batch=tr["max_batch"],
                             deadline_ms=tr["deadline_ms"])
    try:
        warm_queries(svc, pool, tr["max_batch"], topk, probes)
        burst = [sched.query(deploy.rows(pool, i), topk=topk, probes=probes)
                 for i in range(4 * tr["max_batch"])]
        for f in burst:
            f.result(timeout=600)
        ctx.part("warm_up")

        before = counters(sched, svc)
        with ctx.window() as win:
            req.drive(win, lambda i: sched.query(
                deploy.rows(pool, req.row[i]), topk=topk, probes=probes),
                ctx.span)
            stopped = req.wait()
            layer = delta(counters(sched, svc), before)
        ctx.read_memory()
    finally:
        sched.close()
    answered = req.answered()
    p99 = req.p99_ms(win.t0, stopped)
    _, queries, prog = sample_answers(req, pool)
    items = dep.items_host()
    dep.service = dep.corpus = svc = None
    t0 = time.perf_counter()
    numbers = check.against_reference(
        cfg, dep.host_family, items, queries, prog, probes, topk,
        ctx.limits["compare"]["tie_rtol"])
    numbers["unanswered"] = int((~answered).sum())
    return Result(
        attempted=int(answered.size), failed=int((~answered).sum()),
        e2e={"p99_ms": p99}, numbers=numbers, layer=layer,
        notes={"requests": f"{answered.size} at {tr['rate_per_s']}/s, "
                           f"mean batch {layer['sched_requests'] / max(layer['sched_batches'], 1):.2f}",
               "generator_lateness": req.lateness(win.t0),
               "p99_ms_by_tenth": req.timeline(win.t0, stopped),
               "reference_s": f"{time.perf_counter() - t0:.3f}"})
