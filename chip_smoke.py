"""Drive the served LSH index once on a TPU, at a deployment size.

    python chip_smoke.py [--seed 0]     # one chip: dense, mutate, in-format,
                                        # serve and durable phases
    python chip_smoke.py --chips 4      # only the S=4 sharded index against
                                        # the S=1 index on the same host

Everything goes through the entry points a user calls: ``build_service``
and ``LSHService`` for the index, ``ServingScheduler`` for single-row
traffic, ``DurableLSHService`` for the write-ahead log and recovery, and
``ShardedLSHIndex`` over a device mesh. The data is generated from
``--seed``. Every check compares against a plain NumPy float64 reference
or a planted answer; a failed check or any error ends the run with a
non-zero exit code, and only a run in which every check passed prints the
last line, ``{"ok": true, "device": {...}}``.

The script refuses to run without a TPU: its sizes are for a 16 GB chip,
and a CPU number is not a chip number. ``tests/test_chip_smoke.py``
rehearses the same phase functions on the CPU at a few thousand items.

Sizes (one v5e chip, 16 GB):

* dense: 1,000,000 x 128 f32 clustered vectors (the SIFT1M shape of
  ANN-Benchmarks, arXiv:1807.05614), each viewed as 8 x 16 for cp-e2lsh,
  euclidean, K=8, L=8, bucket_cap=64, query batches of 1024 at T=1 and
  T=8 probes.
* in-format: 1,000,000 CP tensors (12, 12, 12) of rank 4 under cp-e2lsh
  and 1,000,000 TT tensors of TT-rank 4 under tt-srp (cosine), re-ranked
  in format.

No size is cut.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import cp_random_data, tt_random_data  # noqa: E402
from repro.core.index import DeviceLSHIndex, ShardedLSHIndex  # noqa: E402
from repro.core.lsh import make_family  # noqa: E402
from repro.serving.durability import DurableLSHService  # noqa: E402
from repro.serving.lsh_service import build_service  # noqa: E402
from repro.serving.scheduler import ServingScheduler  # noqa: E402

# Dense deployment: SIFT1M's shape, viewed as an order-2 tensor for the
# tensorized families. Clusters of ~1000 items keep the bucket occupancy
# independent of n, so a CPU rehearsal at a few thousand items predicts
# the recall at 1M.
DENSE_MODES = (8, 16)
CLUSTER_SIZE = 1000
CLUSTER_SPREAD = 0.5   # within-cluster std per coordinate (centers: std 1)
QUERY_NOISE = 0.2      # planted query = corpus member + this std of noise
DENSE_WIDTH = 12.0     # E2LSH bucket width for the dense family
# The paper's in-format regime (benchmarks/table1_e2lsh.py's dims), with
# the planted-query construction of examples/ann_search.py.
TENSOR_DIMS = (12, 12, 12)
TENSOR_RANK = 4
TENSOR_NOISE = 0.01
CP_WIDTH = 1.0
TT_SRP_BITS = 16       # one SRP bucket per ~15 items at 1M

TOPK = 10
CAP = 64
# Score tolerances against the float64 reference. Dense: f32 rounding of
# the expanded ||q||^2 + ||y||^2 - 2<q, y> at the planted distance stays
# below 2e-5 here. In format, the CP/TT chains add rounding over every
# mode and rank term (up to ~1e-4 seen on CPU). Operands rounded to
# bfloat16 (the TPU's default f32 matmul precision) miss both by ~100x.
SCORE_RTOL = 1e-4
IN_FORMAT_RTOL = 1e-3


class CheckFailed(Exception):
    """A smoke check did not hold."""


class Log:
    """Prints one line per event, each prefixed with the phase and the
    device the run is on."""

    def __init__(self):
        d = jax.devices()
        self.device = f"{d[0].platform}:{d[0].device_kind} x{len(d)}"
        self.phase = "-"

    def __call__(self, msg: str) -> None:
        print(f"[{self.phase}] {self.device} | {msg}", flush=True)

    def check(self, name: str, ok: bool, detail: str = "") -> None:
        self(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip())
        if not ok:
            raise CheckFailed(f"[{self.phase}] {name} {detail}")


def _timed(fn, *args, **kwargs):
    """(result, wall seconds), waiting for the device work it started:
    for an index or service, every array of its store."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    index = getattr(out, "index", out)
    store = getattr(index, "store", None)
    jax.block_until_ready(store.view.all_arrays if store is not None
                          else out)
    return out, time.perf_counter() - t0


def _backends(svc) -> str:
    index = svc.index
    path = (index.query_path if isinstance(index, ShardedLSHIndex)
            else "single-device")
    return (f"hash_backend={index.family.resolved_backend()} "
            f"probe_backend={index.probe_path} query_path={path}")


def _check_xla(log: Log, index) -> None:
    """The smoke route runs the xla backends: 'auto' must not resolve to a
    kernel the chip's compiler refuses."""
    got = (index.family.resolved_backend(), index.probe_path)
    log.check("backends_are_xla", got == ("xla", "xla"), f"got {got}")


# ---------------------------------------------------------------------------
# Data (generated on the device from the seed)
# ---------------------------------------------------------------------------


def clustered(key, n: int) -> jax.Array:
    """(n, 8, 16) f32: Gaussian clusters of ~CLUSTER_SIZE items."""
    d = int(np.prod(DENSE_MODES))
    kc, ka, kx = jax.random.split(key, 3)
    c = max(n // CLUSTER_SIZE, 1)
    centers = jax.random.normal(kc, (c, d))
    assign = jax.random.randint(ka, (n,), 0, c)
    x = centers[assign] + CLUSTER_SPREAD * jax.random.normal(kx, (n, d))
    return x.reshape((n,) + DENSE_MODES)


def perturb(key, tree, noise: float):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        a + noise * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])


def take(tree, ids):
    ids = jnp.asarray(ids)
    return jax.tree.map(lambda a: a[ids], tree)


# ---------------------------------------------------------------------------
# Plain float64 references
# ---------------------------------------------------------------------------


def dense64(tree) -> np.ndarray:
    """(m, ...) dense, CP or TT batch -> (m, D) float64 dense rows."""
    if hasattr(tree, "factors"):
        fs = [np.asarray(f, np.float64) for f in tree.factors]
        out = np.einsum("nir,njr,nkr->nijk", *fs) * tree.scale
    elif hasattr(tree, "cores"):
        cs = [np.asarray(c, np.float64) for c in tree.cores]
        out = np.einsum("naib,nbjc,nckd->nijk", *cs) * tree.scale
    else:
        out = np.asarray(tree, np.float64)
    return out.reshape(out.shape[0], -1)


def reference_scores(metric: str, queries, corpus, ids: np.ndarray
                     ) -> np.ndarray:
    """float64 score of each (row, id) pair; ids (B, k) with -1 fill."""
    b, k = ids.shape
    q = dense64(queries)
    y = dense64(take(corpus, np.maximum(ids, 0).reshape(-1))).reshape(
        b, k, -1)
    if metric == "euclidean":
        return np.linalg.norm(q[:, None, :] - y, axis=-1)
    qn = q / np.linalg.norm(q, axis=-1, keepdims=True)
    yn = y / np.linalg.norm(y, axis=-1, keepdims=True)
    return np.einsum("bd,bkd->bk", qn, yn)


def check_results(log: Log, tag: str, metric: str, ids, scores, n_live: int,
                  queries=None, corpus=None, rtol: float = SCORE_RTOL
                  ) -> None:
    """Ids distinct and in range, scores in rank order, and (with the
    corpus) each score equal to its float64 recomputation."""
    ids, scores = np.asarray(ids), np.asarray(scores)
    valid = ids >= 0
    # the -1 fill is a suffix: once a row runs out of candidates it stays out
    in_range = bool(np.all(ids < n_live)) and bool(
        np.all(valid[:, 1:] <= valid[:, :-1]))
    distinct = all(len(set(r[r >= 0].tolist())) == int((r >= 0).sum())
                   for r in ids)
    log.check(f"{tag}/ids_distinct_in_range", in_range and distinct,
              f"valid={int(valid.sum())}/{ids.size} n_live={n_live}")
    step = np.diff(np.where(valid, scores, 0.0), axis=1)[valid[:, 1:]]
    ordered = bool(np.all(step >= 0) if metric == "euclidean"
                   else np.all(step <= 0))
    log.check(f"{tag}/scores_sorted", ordered)
    if corpus is None:
        return
    ref = reference_scores(metric, queries, corpus, ids)
    err = np.abs(scores.astype(np.float64) - ref) / np.maximum(np.abs(ref),
                                                               1e-12)
    worst = float(err[valid].max()) if valid.any() else 0.0
    log.check(f"{tag}/scores_match_float64", worst <= rtol,
              f"max_rel_err={worst:.3e} tol={rtol:g}")


def planted_rate(ids, planted, k: int) -> float:
    """Share of rows whose planted id is among the first k results."""
    ids = np.asarray(ids)[:, :k]
    return float(np.mean((ids == np.asarray(planted)[:, None]).any(axis=1)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_dense(log: Log, key, *, n: int, batch: int,
                recall_floor: float = 0.9):
    """Build the dense service and query a planted batch at T=1 and T=8.

    -> state dict shared with the later phases."""
    log.phase = "dense"
    k_data, k_fam, k_q, k_pick = jax.random.split(key, 4)
    t0 = time.perf_counter()
    corpus = jax.block_until_ready(clustered(k_data, n))
    t_data = time.perf_counter() - t0
    svc, t_build = _timed(
        build_service, k_fam, "cp-e2lsh", DENSE_MODES, corpus,
        metric="euclidean", num_codes=8, num_tables=8,
        bucket_width=DENSE_WIDTH, bucket_cap=CAP)
    _check_xla(log, svc.index)
    planted = np.random.default_rng(int(jax.random.randint(
        k_pick, (), 0, 2**31 - 1))).choice(n, size=batch, replace=False)
    queries = perturb(k_q, take(corpus, planted), QUERY_NOISE)
    log(f"n={n} d={np.prod(DENSE_MODES)} (viewed {DENSE_MODES}) "
        f"kind=cp-e2lsh K=8 L=8 cap={svc.index.cap} batch={batch} "
        f"{_backends(svc)} setup_s: data={t_data:.3f} build={t_build:.3f}")
    for probes in (1, 8):
        (ids, scores, n_cand), t_first = _timed(
            svc.query_arrays, queries, TOPK, probes=probes)
        _, t_query = _timed(svc.query_arrays, queries, TOPK, probes=probes)
        top1 = planted_rate(ids, planted, 1)
        top10 = planted_rate(ids, planted, TOPK)
        log(f"T={probes} setup_s(compile+first)={t_first:.3f} "
            f"query_s={t_query:.4f} ({batch / t_query:.0f} queries/s) "
            f"planted@1={top1:.4f} planted@10={top10:.4f} "
            f"mean_candidates={float(np.mean(n_cand)):.1f}")
        check_results(log, f"T{probes}", "euclidean", ids, scores, n,
                      queries, corpus)
        if probes == 8:
            log.check("T8/planted_in_top10", top10 >= recall_floor,
                      f"{top10:.4f} >= {recall_floor}")
    return dict(svc=svc, corpus=corpus, queries=queries, planted=planted,
                family=svc.index.family, key=key)


def phase_mutate(log: Log, state, *, inserts: int, batch: int) -> None:
    """Insert, delete the planted ids, compact; check each step."""
    log.phase = "mutate"
    svc, corpus, queries, planted = (state["svc"], state["corpus"],
                                     state["queries"], state["planted"])
    n = jax.tree.leaves(corpus)[0].shape[0]
    k_new = jax.random.fold_in(state["key"], 1)
    new = clustered(k_new, inserts)
    _, t_ins = _timed(svc.insert, new)
    # orig[e] = the original id (arrival order) behind effective id e
    orig = np.arange(n + inserts)
    log(f"n={n} inserts={inserts} deletes={len(planted)} batch={batch} "
        f"{_backends(svc)} setup_s: insert={t_ins:.3f}")

    def self_queries(tag):
        """Every inserted item, queried as itself, comes back at rank 1."""
        want = np.searchsorted(orig, n + np.arange(inserts))
        got, t = [], 0.0
        for s in range(0, inserts, batch):
            (ids, scores, _), dt = _timed(
                svc.query_arrays,
                take(new, np.arange(s, min(s + batch, inserts))), TOPK)
            got.append(np.asarray(ids)[:, 0])
            t += dt
            check_results(log, f"{tag}/self", "euclidean", ids, scores,
                          len(orig))
        got = np.concatenate(got)
        log(f"{tag}: self-queries wall_s={t:.3f} (incl. compile)")
        log.check(f"{tag}/inserted_rank1", bool(np.all(got == want)),
                  f"{int(np.sum(got == want))}/{inserts}")

    def planted_gone(tag):
        for probes in (1, 8):
            ids, scores, _ = svc.query_arrays(queries, TOPK, probes=probes)
            check_results(log, f"{tag}/T{probes}", "euclidean", ids, scores,
                          len(orig))
            ids = np.asarray(ids)
            back = orig[np.where(ids >= 0, ids, 0)][ids >= 0]
            hits = int(np.isin(back, planted).sum())
            log.check(f"{tag}/T{probes}/deleted_never_surface", hits == 0,
                      f"{hits} deleted ids returned")

    self_queries("after_insert")
    _, t_del = _timed(svc.delete, planted)
    orig = np.delete(orig, planted)
    log(f"setup_s: delete={t_del:.3f} n_live={svc.index.size}")
    log.check("live_count", svc.index.size == len(orig),
              f"{svc.index.size} == {len(orig)}")
    planted_gone("after_delete")
    self_queries("after_delete")
    _, t_cmp = _timed(svc.compact)
    log(f"setup_s: compact={t_cmp:.3f} deltas={len(svc.index.store.deltas)}")
    planted_gone("after_compact")
    self_queries("after_compact")


def phase_in_format(log: Log, key, *, n: int, batch: int,
                    recall_floor: float = 0.9) -> None:
    """n CP tensors under cp-e2lsh and n TT tensors under tt-srp, built
    and re-ranked in format; planted recall@1 against a float64 reference.
    """
    log.phase = "in-format"
    cases = (("cp-e2lsh", cp_random_data, "euclidean",
              dict(num_codes=8, bucket_width=CP_WIDTH)),
             ("tt-srp", tt_random_data, "cosine",
              dict(num_codes=TT_SRP_BITS)))
    for i, (kind, gen, metric, fam_kw) in enumerate(cases):
        k_data, k_fam, k_q, k_pick = jax.random.split(
            jax.random.fold_in(key, i), 4)
        t0 = time.perf_counter()
        corpus = jax.block_until_ready(jax.vmap(
            lambda k: gen(k, TENSOR_DIMS, TENSOR_RANK))(
                jax.random.split(k_data, n)))
        t_data = time.perf_counter() - t0
        svc, t_build = _timed(
            build_service, k_fam, kind, TENSOR_DIMS, corpus, metric=metric,
            num_tables=8, rank=TENSOR_RANK, bucket_cap=CAP, **fam_kw)
        _check_xla(log, svc.index)
        planted = np.random.default_rng(int(jax.random.randint(
            k_pick, (), 0, 2**31 - 1))).choice(n, size=batch,
                                                  replace=False)
        queries = perturb(k_q, take(corpus, planted), TENSOR_NOISE)
        (ids, scores, n_cand), t_first = _timed(svc.query_arrays, queries,
                                                TOPK)
        _, t_query = _timed(svc.query_arrays, queries, TOPK)
        at1 = planted_rate(ids, planted, 1)
        log(f"{kind} n={n} dims={TENSOR_DIMS} rank={TENSOR_RANK} "
            f"metric={metric} K={fam_kw['num_codes']} L=8 cap={svc.index.cap}"
            f" batch={batch} {_backends(svc)} setup_s: data={t_data:.3f} "
            f"build={t_build:.3f} compile+first={t_first:.3f} "
            f"query_s={t_query:.4f} planted@1={at1:.4f} "
            f"mean_candidates={float(np.mean(n_cand)):.1f}")
        check_results(log, kind, metric, ids, scores, n, queries, corpus,
                      rtol=IN_FORMAT_RTOL)
        log.check(f"{kind}/planted_recall@1", at1 >= recall_floor,
                  f"{at1:.4f} >= {recall_floor}")
        del svc, corpus


def phase_serve(log: Log, state, *, requests: int) -> None:
    """Single-row traffic through ServingScheduler matches the batched
    call; an insert and a delete on the ingest lane are seen by the
    queries after them."""
    log.phase = "serve"
    svc, queries = state["svc"], state["queries"]
    rows = take(queries, np.arange(requests))
    with ServingScheduler(svc, max_batch=64, deadline_ms=5.0) as sched:
        t0 = time.perf_counter()
        futs = [sched.query(take(rows, i), topk=TOPK)
                for i in range(requests)]
        single = np.stack([np.asarray(f.result(timeout=600)[0])
                           for f in futs])
        t_single = time.perf_counter() - t0
        batched = np.asarray(svc.query_arrays(rows, TOPK)[0])
        log(f"requests={requests} {_backends(svc)} "
            f"wall_s(incl. compile)={t_single:.3f} "
            f"batches={sched.stats.batches}")
        log.check("single_rows_equal_batched",
                  bool(np.array_equal(single, batched)),
                  f"{int((single == batched).all(axis=1).sum())}/{requests}"
                  " rows equal")
        item = clustered(jax.random.fold_in(state["key"], 2), 1)
        sched.insert(item).result(timeout=600)
        new_id = svc.index.size - 1
        ids = np.asarray(sched.query(item[0], topk=TOPK).result(
            timeout=600)[0])
        log.check("insert_visible", ids[0] == new_id,
                  f"rank-1 id {ids[0]} == {new_id}")
        sched.delete(np.array([new_id])).result(timeout=600)
        ids = np.asarray(sched.query(item[0], topk=TOPK).result(
            timeout=600)[0])
        log.check("delete_visible",
                  new_id not in ids.tolist() and bool(
                      np.all(ids < svc.index.size)),
                  f"id {new_id} absent, n_live={svc.index.size}")


def phase_durable(log: Log, state, *, n: int, mutations: int,
                  batch: int) -> None:
    """WAL + snapshot + recovery in one process: acknowledged mutations,
    a snapshot, more mutations, then recover() on a fresh instance; the
    recovered service answers with the same ids."""
    log.phase = "durable"
    family, corpus = state["family"], take(state["corpus"], np.arange(n))
    queries = take(state["queries"], np.arange(batch))
    kw = dict(metric="euclidean", bucket_cap=CAP)
    with tempfile.TemporaryDirectory(prefix="lsh-durable-") as tmp:
        svc = DurableLSHService(family, tmp, **kw)
        _, t_build = _timed(svc.build, corpus)
        rng = np.random.default_rng(int(jax.random.randint(
            jax.random.fold_in(state["key"], 3), (), 0, 2**31 - 1)))
        t_mut = 0.0
        for r in range(2):
            new = clustered(jax.random.fold_in(state["key"], 10 + r),
                            mutations)
            t0 = time.perf_counter()
            svc.insert(new)
            svc.delete(rng.choice(svc.index.size, size=mutations // 4,
                                  replace=False))
            if r == 0:
                svc.snapshot()
            t_mut += time.perf_counter() - t0
        before = svc.query_arrays(queries, TOPK)
        svc.close()
        fresh = DurableLSHService(family, tmp, **kw)
        _, t_rec = _timed(fresh.recover)
        after = fresh.query_arrays(queries, TOPK)
        log(f"n={n} mutations=2x({mutations} inserts, {mutations // 4} "
            f"deletes) {_backends(fresh)} setup_s: build+snapshot="
            f"{t_build:.3f} mutate+snapshot={t_mut:.3f} recover={t_rec:.3f}"
            f" wal_appends={svc.stats.wal_appends} "
            f"health={fresh.health}")
        log.check("recovered_size", fresh.index.size == svc.index.size,
                  f"{fresh.index.size} == {svc.index.size}")
        log.check("recovered_ids_equal",
                  bool(np.array_equal(np.asarray(before[0]),
                                      np.asarray(after[0]))))
        fresh.close()


def phase_sharded(log: Log, key, *, n: int, shards: int, inserts: int,
                  batch: int) -> None:
    """ShardedLSHIndex(S) over a mesh against DeviceLSHIndex on device 0:
    the same top-k ids after build, insert, delete, compact, rebalance.

    Both indexes keep the exact default cap (the largest bucket): an
    explicit ``bucket_cap`` truncates each shard's slice of a bucket, so a
    sharded index may then see up to S*L*cap candidates where one device
    sees L*cap, and its ids may rightly differ. With exact caps the
    largest dense bucket sets the gather size, so the T=8 comparison runs
    on an eighth of the batch. Compaction runs the indexes' default
    chunked fold, the one a served index takes."""
    log.phase = f"sharded-S{shards}"
    k_data, k_fam, k_q, k_new = jax.random.split(key, 4)
    corpus = jax.block_until_ready(clustered(k_data, n))
    family = make_family(k_fam, "cp-e2lsh", DENSE_MODES, num_codes=8,
                         num_tables=8, bucket_width=DENSE_WIDTH)
    hash_batch = 1 << 16
    sharded, t_sh = _timed(lambda: ShardedLSHIndex(
        family, metric="euclidean", shards=shards).build(
            corpus, batch_size=hash_batch))
    single, t_one = _timed(lambda: DeviceLSHIndex(
        family, metric="euclidean").build(corpus, batch_size=hash_batch))
    log(f"n={n} shards={shards} batch={batch} cap: sharded={sharded.cap} "
        f"single={single.cap} hash_backend={family.resolved_backend()} "
        f"probe_backend={sharded.probe_path} query_path={sharded.query_path}"
        f" setup_s: build_sharded={t_sh:.3f} build_single={t_one:.3f}")
    log.check("query_path", sharded.query_path == "shard_map",
              sharded.query_path)

    def on_distinct_devices(tag):
        """Every array of the base segment spans all ``shards`` devices."""
        base = sharded.store.base
        devs = {len(a.sharding.device_set)
                for a in jax.tree.leaves((base.sorted_keys, base.perm,
                                          base.corpus))}
        log.check(f"{tag}_on_distinct_devices", devs == {shards},
                  f"devices per array {sorted(devs)}")

    on_distinct_devices("base")
    planted = np.arange(0, n, max(n // batch, 1))[:batch]
    queries = perturb(k_q, take(corpus, planted), QUERY_NOISE)

    def same_ids(tag, probes_list=(1,)):
        for probes in probes_list:
            rows = batch if probes == 1 else max(batch // 8, 1)
            q = take(queries, np.arange(rows))
            (a, sa, _), t_a = _timed(sharded.query_batch, q, TOPK,
                                     probes=probes)
            (b, sb, _), t_b = _timed(single.query_batch, q, TOPK,
                                     probes=probes)
            a, b = np.asarray(a), np.asarray(b)
            both = (a >= 0) & (b >= 0)
            gap = np.abs(np.asarray(sa)[both] - np.asarray(sb)[both])
            diff = int((a != b).any(axis=1).sum())
            log(f"{tag} T={probes} rows={rows} wall_s(incl. compile): "
                f"sharded={t_a:.3f} single={t_b:.3f} "
                f"max|score diff|={float(gap.max(initial=0.0)):.3e}")
            log.check(f"{tag}/T{probes}/ids_equal_S1", diff == 0,
                      f"{diff}/{rows} rows differ")

    def mutate(tag, op, indexes=(sharded, single)):
        walls = [f"{name}={_timed(op, index)[1]:.3f}"
                 for name, index in zip(("sharded", "single"), indexes)]
        log(f"{tag} setup_s: {' '.join(walls)}")

    same_ids("build", (1, 8))
    new = clustered(k_new, inserts)
    mutate("insert", lambda index: index.insert(new))
    same_ids("insert")
    mutate("delete", lambda index: index.delete(planted))
    same_ids("delete")
    mutate("compact", lambda index: index.compact())
    log.check("compacted", not sharded.store.deltas and not single.store.deltas)
    on_distinct_devices("compacted")
    same_ids("compact")
    mutate("rebalance", lambda index: index.rebalance(), (sharded,))
    on_distinct_devices("rebalanced")
    same_ids("rebalance", (1, 8))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded index against S=1")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {dev.platform}); refusing to "
              "run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 2
    enable_compile_cache()    # before the first compile
    log = Log()
    log("sizes: 1,000,000 items per corpus, query batches of 1024; "
        "no cuts")
    key = jax.random.PRNGKey(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(log, key, n=1_000_000, shards=4, inserts=4096,
                      batch=1024)
    else:
        state = phase_dense(log, key, n=1_000_000, batch=1024)
        phase_mutate(log, state, inserts=4096, batch=1024)
        phase_in_format(log, jax.random.fold_in(key, 7), n=1_000_000,
                        batch=1024)
        phase_serve(log, state, requests=64)
        phase_durable(log, state, n=1_000_000, mutations=1024, batch=1024)
    log.phase = "done"
    log(f"all checks passed, wall_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
