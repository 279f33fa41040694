"""Steady streaming churn over the durable index, behind the scheduler,
in the manner of the big-ann-benchmarks streaming runbooks: one
closed-loop writer deletes the ``delete_batch`` oldest live items and
then inserts ``insert_batch`` new ones, so the live count and every
program shape stay put; the service's own ``max_deltas`` trigger folds
the deltas; every mutation is WAL-committed (fsync) before it is
acknowledged. Single-row queries arrive open loop meanwhile.

Traffic parameters: ``insert_batch``, ``delete_batch``,
``query_rate_per_s``, ``probes``, ``topk``, ``pool``, ``max_batch``,
``deadline_ms``, ``trace_seconds``, ``check_inserts``, ``check_deletes``.
The window drives ``ServingScheduler.insert``, ``delete`` and ``query``
over ``DurableLSHService``. End-to-end: ``mutations_per_s`` (items
inserted plus items deleted, acknowledged inside the window, over the
window), and ``p99_ms.churn``, the queries' 99th percentile as
``open_loop`` takes it, which the run reports where ``BENCHMARK.json``
lists it and prints on an earlier line always.

Set-up runs whole cycles until the first fold, warming the query program
at every delta count and padded batch size, so the window starts right
after a fold and compiles nothing.

The check follows the store through every state the writer published
(the log gives each mutation's start and acknowledgement, and whether an
insert folded): a sampled window query is compared with the reference of
each state it could have seen, and the best fit counts. After the window,
with deltas and tombstones outstanding, self-queries of acknowledged
inserts (half of them still in deltas) and of acknowledged deletes (half
of them tombstones the fold has not removed) check the guarantee: an
acknowledged insert is found, an acknowledged delete never comes back.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np

from bench import check, deploy, reference, stats
from bench.drivers import open_loop


class Writer:
    """The closed-loop writer; its log gives each mutation's kind, submit
    and acknowledgement times, items, and whether it folded the deltas."""

    def __init__(self, ctx, dep, sched):
        tr, data = ctx.traffic, ctx.config["data"]
        self.ctx, self.dep, self.sched = ctx, dep, sched
        self.ib, self.db = tr["insert_batch"], tr["delete_batch"]
        if self.ib != self.db:
            raise ValueError("churn keeps the live count: insert_batch "
                             "must equal delete_batch")
        self.key = deploy.jax_key(ctx.seed, 5)
        self.dims = tuple(data["dims"])
        self.spread = float(data["cluster_spread"])
        self.inserted = []           # host batches, in arrival order
        self.log = []                # (kind, t_submit, t_ack, items, folded)
        self.errors = []

    def fresh(self, key):
        return np.asarray(deploy.fresh_items(
            key, self.dep.centers, n=self.ib, spread=self.spread,
            dims=self.dims))

    @property
    def folds(self) -> int:
        return self.dep.service.stats.auto_compactions

    def cycle(self) -> bool:
        """Delete the oldest live items, then insert new ones; False (and
        the error recorded) when the service refused either."""
        try:
            with self.ctx.span("bench.ingest"):
                t = time.perf_counter()
                self.sched.delete(np.arange(self.db)).result()
                self.log.append(("delete", t, time.perf_counter(), self.db,
                                 False))
            with self.ctx.span("bench.generate"):
                batch = self.fresh(jax.random.fold_in(self.key,
                                                      len(self.inserted)))
            with self.ctx.span("bench.ingest"):
                folds, t = self.folds, time.perf_counter()
                self.sched.insert(batch).result()
                self.log.append(("insert", t, time.perf_counter(), self.ib,
                                 self.folds != folds))
        except Exception as e:   # counted against the run, never swallowed
            self.errors.append(repr(e))
            return False
        self.inserted.append(batch)
        return True

    def until_fold(self, after_insert=None) -> bool:
        """Run cycles until an insert folds the deltas; gives up (False)
        after twice the cycles a fold takes."""
        folds = self.folds
        for _ in range(2 * (self.dep.service.index.max_deltas + 1)):
            if not self.cycle():
                return False
            if after_insert:
                after_insert()
            if self.folds != folds:
                return True
        return False

    def loop_until(self, t_end: float) -> None:
        while time.perf_counter() < t_end and self.cycle():
            pass


def warm_fold_shapes(dep, seed: int, pool, tr) -> None:
    """An insert publishes its delta before the fold it triggers, so the
    queries that run during a fold see ``max_deltas + 1`` deltas. Warm
    those programs through the public service path: a second, plain
    service over the same corpus that folds one delta later, given that
    many deltas and queried at every padded batch size, then dropped."""
    from repro.serving.lsh_service import LSHService

    cfg, data = dep.config, dep.config["data"]
    deltas = cfg["index"]["max_deltas"] + 1
    svc = LSHService(dep.family, metric=cfg["metric"],
                     bucket_cap=cfg["index"]["bucket_cap"],
                     max_deltas=deltas)
    svc.build(dep.corpus, batch_size=cfg["index"]["build_batch"])
    for j in range(deltas):
        # host rows, as the writer sends them: a delta keeps what it got
        svc.insert(np.asarray(deploy.fresh_items(
            deploy.jax_key(seed, 7, j), dep.centers, n=tr["insert_batch"],
            spread=float(data["cluster_spread"]), dims=tuple(data["dims"]))))
    open_loop.warm_queries(svc, pool, tr["max_batch"], tr["topk"],
                           tr["probes"])


def store_states(log, n: int, db: int, ib: int) -> list:
    """Every state the writer's log published, in order: (reference
    ``Store``, index of the mutation that published it; -1 for the
    build). An insert that folds publishes twice: its delta, then the
    fold (a base of every live item, no deltas)."""
    base, deltas, d, i = (0, n), [], 0, 0

    def state():
        return reference.Store((base, *deltas), d * db, n + i * ib)

    out = [(state(), -1)]
    for k, (kind, _, _, _, folded) in enumerate(log):
        if kind == "delete":
            d += 1
        else:
            deltas.append((n + i * ib, n + (i + 1) * ib))
            i += 1
            if folded:
                out.append((state(), k))
                base, deltas = (d * db, n + i * ib), []
        out.append((state(), k))
    return out


def _live(items, store):
    return check.take(items, slice(store.live_lo, store.live_hi))


def _one(prog, j):
    return tuple(a[j:j + 1] for a in prog)


def _pick(rng, lo: int, hi: int, k: int, exclude=()) -> np.ndarray:
    pool = np.setdiff1d(np.arange(lo, hi), exclude)
    return rng.choice(pool, size=min(k, pool.size), replace=False)


def window_numbers(cfg, index, every, states, log, req, pick, wq, wprog,
                   probes, topk, tie_rtol) -> tuple[dict, float]:
    """The sampled window answers against the reference: each query
    against every state it could have seen, from the one left by the
    mutations acknowledged before it was sent to the last one begun
    before its answer came back; the best fit counts. Returns the numbers
    and the mean count of states per query."""
    cap, metric = cfg["index"]["bucket_cap"], cfg["metric"]
    mut = np.array([k for _, k in states])
    acks = np.array([e[2] for e in log])
    starts = np.array([e[1] for e in log])
    keys = index.query_keys(wq, probes)
    gap, topk_bad, ncand_bad, tried = 0.0, 0.0, 0.0, 0
    for j, i in enumerate(pick):
        done = int(np.sum(acks <= req.submit[i]))
        begun = int(np.sum(starts <= req.done[i]))
        first = int(np.searchsorted(mut, done - 1, side="right")) - 1
        last = int(np.searchsorted(mut, begun - 1, side="right")) - 1
        q = check.take(wq, [j])
        best = None
        for s in range(first, last + 1):
            store = states[s][0]
            ref = reference.answer(index, metric, q, probes, cap, topk,
                                   store=store, keys=keys[j:j + 1])
            nums = check.compare(metric, _live(every, store), q,
                                 _one(wprog, j), ref, tie_rtol)
            fit = (nums["topk_mismatch"] + nums["ncand_mismatch"],
                   nums["score_gap"])
            if best is None or fit < best[0]:
                best = (fit, nums)
        tried += last - first + 1
        gap = max(gap, best[1]["score_gap"])
        topk_bad += best[1]["topk_mismatch"]
        ncand_bad += best[1]["ncand_mismatch"]
    m = max(len(pick), 1)
    return ({"window_score_gap": gap, "window_topk_mismatch": topk_bad / m,
             "window_ncand_mismatch": ncand_bad / m}, tried / m)


def _failed_run(ctx, req, writer, t0, stopped, acked):
    """A run whose writer was refused: the store the checks need was
    never reached, so only the failure is reported."""
    from bench.harness import Result
    answered = req.answered()
    return Result(
        attempted=int(answered.size) + len(writer.log),
        failed=int((~answered).sum()) + len(writer.errors),
        e2e={"mutations_per_s": stats.rate(acked, ctx.seconds),
             "p99_ms.churn": req.p99_ms(t0, stopped)},
        numbers={"mutations_failed": len(writer.errors)},
        notes={"writer_errors": writer.errors})


def run(ctx):
    from bench.harness import Result
    from repro.serving.scheduler import ServingScheduler

    tr, cfg = ctx.traffic, ctx.config
    probes, topk = tr["probes"], tr["topk"]
    dep = deploy.make(cfg, ctx.seed)
    ctx.part("data_and_family")
    svc = deploy.serve(dep, ctx.out_dir / "durable")
    ctx.part("build_and_snapshot")
    _, pool = deploy.query_pool(dep, ctx.seed, tr["pool"])
    req = open_loop.Requests(ctx, 3, tr["query_rate_per_s"], tr["pool"],
                             ctx.limits["compare"]["sample"])
    ctx.part("query_pool")
    sched = ServingScheduler(svc, max_batch=tr["max_batch"],
                             deadline_ms=tr["deadline_ms"])
    writer = Writer(ctx, dep, sched)

    def warm():
        open_loop.warm_queries(svc, pool, tr["max_batch"], topk, probes)

    try:
        warm_fold_shapes(dep, ctx.seed, pool, tr)
        ctx.part("warm_fold_shapes")
        warm()
        writer.until_fold(after_insert=warm)
        ctx.part("warm_up_cycle")
        before = open_loop.counters(sched, svc)
        n_log = len(writer.log)
        with ctx.window() as win:
            th = threading.Thread(target=writer.loop_until,
                                  args=(win.t0 + ctx.seconds,),
                                  name="bench-writer")
            th.start()
            req.drive(win, lambda i: sched.query(
                deploy.rows(pool, req.row[i]), topk=topk, probes=probes),
                ctx.span)
            th.join()
            stopped = req.wait()
            layer = open_loop.delta(open_loop.counters(sched, svc), before)
        ctx.read_memory()
        t_close = win.t0 + ctx.seconds
        acked = sum(e[3] for e in writer.log[n_log:] if e[2] <= t_close)
        if writer.log and writer.log[-1][4]:
            writer.cycle()     # leave deltas and tombstones outstanding
        if writer.errors:
            return _failed_run(ctx, req, writer, win.t0, stopped, acked)

        # self-queries of acknowledged inserts and deletes, half of each
        # still outstanding (in a delta, or a tombstone in the base)
        n, ib = dep.n, writer.ib
        states = store_states(writer.log, n, writer.db, ib)
        final = states[-1][0]
        rng = deploy.host_rng(ctx.seed, 6)
        ki, kd = tr["check_inserts"], tr["check_deletes"]
        ins = _pick(rng, final.segments[1][0], final.live_hi, ki // 2)
        ins = np.concatenate([ins, _pick(rng, max(n, final.live_lo),
                                         final.live_hi, ki - ins.size, ins)])
        dels = _pick(rng, final.segments[0][0], final.live_lo, kd // 2)
        dels = np.concatenate([dels, _pick(rng, 0, final.live_lo,
                                           kd - dels.size, dels)])
        every = np.concatenate([dep.items_host()] + [
            b.reshape(ib, -1) for b in writer.inserted])
        self_ids = np.concatenate([ins, dels]).astype(np.int64)
        qrows = every[self_ids]
        futs = [sched.query(dep.program_items(qrows[i:i + 1])[0], topk=topk,
                            probes=probes) for i in range(self_ids.size)]
        self_res = [f.result(timeout=600) for f in futs]
    finally:
        sched.close()
        svc.close()
    answered = req.answered()
    p99 = req.p99_ms(win.t0, stopped)
    dep.service = dep.corpus = svc = None

    cmp, cap = ctx.limits["compare"], cfg["index"]["bucket_cap"]
    t0 = time.perf_counter()
    index = reference.Index(dep.host_family, every,
                            cfg["precision"]["hash_operands"])
    prog = tuple(np.stack([r[k] for r in self_res]) for k in range(3))
    keys = index.query_keys(qrows, probes)
    ref = reference.answer(index, cfg["metric"], qrows, probes, cap, topk,
                           store=final, keys=keys)
    numbers = check.compare(cfg["metric"], _live(every, final), qrows, prog,
                            ref, cmp["tie_rtol"])
    k = ins.size
    cands = index.window_ids(keys[:k], cap, final)
    findable = np.array([a in set(c.tolist()) for a, c in zip(ins, cands)])
    numbers["inserts_lost"] = int(np.sum(
        findable & (prog[0][:k, 0] != ins - final.live_lo)))
    numbers["deletes_found"] = int(np.sum(prog[1][k:, 0]
                                          < cmp["self_match_dist"]))

    pick, wq, wprog = open_loop.sample_answers(req, pool)
    window, states_per_query = window_numbers(
        cfg, index, every, states, writer.log, req, pick, wq, wprog, probes,
        topk, cmp["tie_rtol"])
    numbers.update(window)
    numbers["unanswered"] = int((~answered).sum())
    numbers["mutations_failed"] = len(writer.errors)
    failed = int((~answered).sum()) + len(writer.errors)
    return Result(
        attempted=int(answered.size) + len(writer.log[n_log:]),
        failed=failed,
        e2e={"mutations_per_s": stats.rate(acked, ctx.seconds),
             "p99_ms.churn": p99},
        numbers=numbers, layer=layer,
        notes={"mutations": f"{acked} items acknowledged in the window, "
                            f"{layer['folds']} folds, "
                            f"{final.live_lo // writer.db} deletes in all",
               "self_queries": f"{k} inserts ({ki // 2} in deltas), "
                               f"{dels.size} deletes ({kd // 2} tombstones)"
                               f", {len(final.segments) - 1} deltas",
               "states_per_window_query": f"{states_per_query:.3f}",
               "writer_errors": writer.errors,
               "requests": f"{answered.size} at {tr['query_rate_per_s']}/s,"
                           f" p99 {p99:.3f} ms",
               "generator_lateness": req.lateness(win.t0),
               "p99_ms_by_tenth": req.timeline(win.t0, stopped),
               "slowest_mutations_s": " ".join(
                   f"{e[0]}:{e[2] - e[1]:.3f}" for e in sorted(
                       writer.log[n_log:], key=lambda e: e[1] - e[2])[:5]),
               "reference_s": f"{time.perf_counter() - t0:.3f}"})
