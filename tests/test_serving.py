"""The serving plane must never hand back a torn read.

Three guarantees pinned here, per store layout (device + sharded
S in {1, 2, 4}):

* **Swap atomicity** — queries racing a background ``prepare_compact`` /
  ``prepare_rebalance`` are bit-identical to the pure pre-swap snapshot
  while the shadow builds, and every query racing the publish itself
  matches either the pre- or the post-swap snapshot exactly (the flip is
  one pointer assignment; no query observes a mixture).
* **Chunked-fold parity** — the incremental shadow build
  (``swap_chunk_rows`` small) produces a store bit-identical to the
  monolithic one-program fold (``swap_chunk_rows=None``): same segment
  arrays, same query answers.
* **Scheduler contract** — micro-batch coalescing returns exactly the
  rows a direct ``query_arrays`` batch would; sampling requests never
  coalesce and replay by seed; errors resolve futures instead of wedging
  the lane; tenant quotas reject with ``QuotaExceeded`` and count it.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import grids
from repro.core import segments
from repro.serving.lsh_service import LSHService
from repro.serving.scheduler import (QuotaExceeded, ServingScheduler,
                                     TenantQuota)

TOPK = 5
N_CORPUS = 67          # coprime to every shard count: padded last shard
N_QUERIES = 6
N_INS = 13

LAYOUTS = (None,) + grids.SHARD_COUNTS    # device + sharded S in {1,2,4}


def _service(shards, fmt="dense", **kw):
    make = (grids.cp_corpus_and_queries if fmt == "cp"
            else grids.corpus_and_queries)
    corpus, queries = make(N_CORPUS, N_QUERIES)
    kw.setdefault("bucket_cap", 16)
    kw.setdefault("max_deltas", 64)       # no auto-compact under the races
    svc = LSHService(grids.grid_family("cp-e2lsh"), metric="euclidean",
                     shards=shards, **kw).build(corpus)
    return svc, corpus, queries


def _mutate(svc, corpus):
    """One delta slab + tombstones in both base and delta, so the fold
    has real compaction work (not a no-op flip)."""
    svc.insert(jax.tree.map(lambda a: np.asarray(a[:N_INS]) + 0.5, corpus))
    svc.delete([3, 10, 25, N_CORPUS + 2])


def _answers(svc, queries):
    return svc.query_arrays(queries, topk=TOPK)


def _matches(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def _assert_same(a, b):
    for name, x, y in zip(("ids", "scores", "n_cand"), a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)


class TestSwapInterleaving:
    """Satellite: seeded interleaving — queries racing a background swap
    build/publish are bit-identical to a pure pre- or post-swap answer."""

    @pytest.mark.parametrize("shards", LAYOUTS)
    def test_queries_racing_compact_swap_are_never_torn(self, shards):
        svc, corpus, queries = _service(shards)
        _mutate(svc, corpus)
        pre = _answers(svc, queries)

        results = []
        done = threading.Event()

        def serve():
            while not done.is_set():
                results.append(_answers(svc, queries))

        thread = threading.Thread(target=serve)
        thread.start()
        try:
            pending = svc.prepare_compact()
            assert pending is not None      # the mutations gave it work
            # every query that raced the build saw the untouched live store
            mid = list(results)
            svc.apply_swap(pending)
        finally:
            done.set()
            thread.join()
        for r in mid:
            _assert_same(r, pre)
        post = _answers(svc, queries)
        # queries that raced the publish saw exactly one of the two stores
        for r in results:
            assert _matches(r, pre) or _matches(r, post), \
                "a query racing the swap returned a torn mixture"
        assert not svc.index.store.mutated

    @pytest.mark.parametrize("shards", grids.SHARD_COUNTS)
    def test_queries_racing_rebalance_swap_are_never_torn(self, shards):
        svc, corpus, queries = _service(shards)
        _mutate(svc, corpus)
        pre = _answers(svc, queries)

        results = []
        done = threading.Event()

        def serve():
            while not done.is_set():
                results.append(_answers(svc, queries))

        thread = threading.Thread(target=serve)
        thread.start()
        try:
            pending = svc.prepare_rebalance()
            mid = list(results)
            svc.apply_swap(pending)
        finally:
            done.set()
            thread.join()
        for r in mid:
            _assert_same(r, pre)
        post = _answers(svc, queries)
        for r in results:
            assert _matches(r, pre) or _matches(r, post)

    @pytest.mark.parametrize("shards", (None, 2))
    def test_stale_swap_rejected_after_interleaved_mutation(self, shards):
        """A mutation between prepare and apply invalidates the shadow —
        publishing it would silently drop the mutation."""
        svc, corpus, queries = _service(shards)
        _mutate(svc, corpus)
        pending = svc.prepare_compact()
        svc.insert(np.asarray(corpus[:2]) + 1.0)
        with pytest.raises(RuntimeError, match="mutated"):
            svc.apply_swap(pending)
        # the live store still serves; a fresh prepare/apply succeeds
        svc.apply_swap(svc.prepare_compact())
        assert not svc.index.store.mutated
        _answers(svc, queries)


class TestChunkedFoldParity:
    """The incremental (chunked, throttled) shadow build is an
    implementation detail: its store must be bit-identical to the
    monolithic fold's, down to every segment array."""

    @pytest.mark.parametrize("shards,fmt", [
        *(pytest.param(s, "dense", id=str(s)) for s in LAYOUTS),
        *(pytest.param(s, "cp", id=f"cp-{s}") for s in LAYOUTS)])
    def test_chunked_store_bit_identical_to_monolithic(self, shards, fmt):
        svc_mono, corpus, queries = _service(shards, fmt)
        svc_chunk, _, _ = _service(shards, fmt)
        svc_mono.index.swap_chunk_rows = None
        svc_chunk.index.swap_chunk_rows = 16   # many chunks over 67 items
        for svc in (svc_mono, svc_chunk):
            _mutate(svc, corpus)
            svc.apply_swap(svc.prepare_compact())
        a, b = svc_mono.index.store.base, svc_chunk.index.store.base
        assert a.cap == b.cap
        np.testing.assert_array_equal(np.asarray(a.keys), np.asarray(b.keys))
        np.testing.assert_array_equal(np.asarray(a.sorted_keys),
                                      np.asarray(b.sorted_keys))
        np.testing.assert_array_equal(np.asarray(a.perm), np.asarray(b.perm))
        for la, lb in zip(jax.tree.leaves(a.corpus), jax.tree.leaves(b.corpus)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        _assert_same(_answers(svc_mono, queries), _answers(svc_chunk, queries))


class TestGatherRowsChunked:
    """``gather_rows_chunked`` against a plain NumPy gather, over leaves of
    ndim 1 to 4: ids, keys, dense (m, 8, 16) rows, CP factors and TT
    cores, which the chunks write row-flat and re-shape at the end."""

    LEAVES = {"ids": ((), np.int32), "keys": ((3,), np.uint32),
              "dense": ((8, 16), np.float32), "cp": ((4, 2), np.float32),
              "tt": ((1, 4, 2), np.float32)}
    OUT_ROWS = 40

    @pytest.mark.parametrize("chunk", [4, 7, 64])
    def test_matches_numpy_gather(self, chunk):
        rng = np.random.default_rng(chunk)
        srcs = [{k: (rng.standard_normal((m,) + shape) * 100).astype(dt)
                 for k, (shape, dt) in self.LEAVES.items()}
                for m in (23, 9)]
        # 25 of 40 output rows are written, from both sources; the rest
        # must stay zero
        dst = rng.permutation(self.OUT_ROWS)[:25]
        dst_idxs = [dst[:17], dst[17:]]
        src_idxs = [rng.choice(23, 17, replace=False),
                    rng.choice(9, 8, replace=False)]
        out = segments.gather_rows_chunked(
            jax.tree.map(jnp.asarray, srcs[0]),
            [jax.tree.map(jnp.asarray, src) for src in srcs],
            src_idxs, dst_idxs, self.OUT_ROWS, chunk=chunk)
        for k, (shape, dt) in self.LEAVES.items():
            want = np.zeros((self.OUT_ROWS,) + shape, dt)
            for src, s_idx, d_idx in zip(srcs, src_idxs, dst_idxs):
                want[d_idx] = src[k][s_idx]
            got = np.asarray(out[k])
            assert got.dtype == want.dtype and got.shape == want.shape, k
            np.testing.assert_array_equal(got, want, err_msg=k)
            unwritten = np.setdiff1d(np.arange(self.OUT_ROWS), dst)
            assert not got[unwritten].any(), k


class TestSchedulerCoalescing:
    def test_coalesced_rows_match_direct_batch(self):
        svc, _, queries = _service(None)
        direct = svc.query_arrays(queries, topk=TOPK)
        with ServingScheduler(svc, max_batch=4, deadline_ms=100.0) as sched:
            futs = [sched.query(q, topk=TOPK) for q in queries]
            got = [f.result(timeout=30) for f in futs]
        for i, (ids, scores, n_cand) in enumerate(got):
            np.testing.assert_array_equal(ids, direct[0][i])
            np.testing.assert_array_equal(scores, direct[1][i])
            assert n_cand == int(direct[2][i])
        assert sched.stats.requests == N_QUERIES
        assert sched.stats.batches >= 1
        # pad rows never inflate the tenant's counters
        assert svc.stats.queries == 2 * N_QUERIES   # direct + scheduled

    def test_sampling_requests_replay_by_seed_and_never_coalesce(self):
        svc, _, queries = _service(None)
        with ServingScheduler(svc, max_batch=8, deadline_ms=50.0) as sched:
            futs = [sched.query(queries[0], topk=TOPK, mode="uniform", seed=9)
                    for _ in range(4)]
            got = [f.result(timeout=30) for f in futs]
            sched.flush(timeout=30)
            # one program batch per sampling request: the draw is a
            # per-request seeded event, never amortized across requests
            assert sched.stats.batches == 4
        for r in got[1:]:
            _assert_same(r, got[0])
        direct = svc.query_arrays(queries[:1], topk=TOPK, mode="uniform",
                                  seed=9)
        _assert_same(got[0], (direct[0][0], direct[1][0], int(direct[2][0])))

    def test_errors_resolve_futures_without_wedging_the_lane(self):
        svc, _, queries = _service(None)
        with ServingScheduler(svc, max_batch=4, deadline_ms=5.0) as sched:
            with pytest.raises(ValueError, match="probes must be >= 1"):
                sched.query(queries[0], probes=0).result(timeout=30)
            with pytest.raises(ValueError, match="seed"):
                sched.query(queries[0], mode="uniform").result(timeout=30)
            ids, _, _ = sched.query(queries[0], topk=TOPK).result(timeout=30)
            assert ids.shape == (TOPK,)

    def test_ingest_lane_orders_mutations_and_swaps(self):
        svc, corpus, queries = _service(2)
        direct = LSHService(grids.grid_family("cp-e2lsh"), metric="euclidean",
                            shards=2, bucket_cap=16, max_deltas=64,
                            ).build(corpus)
        _mutate(direct, corpus)
        direct.apply_swap(direct.prepare_compact())
        with ServingScheduler(svc, max_batch=4, deadline_ms=5.0) as sched:
            sched.insert(np.asarray(corpus[:N_INS]) + 0.5)
            sched.delete([3, 10, 25, N_CORPUS + 2])
            assert sched.compact().result(timeout=60) is svc
            fut = sched.query(queries[0], topk=TOPK)
            _assert_same(fut.result(timeout=30),
                         tuple(r[0] if getattr(r, "ndim", 0) else r
                               for r in _answers(direct, queries[:1])))
        assert not svc.index.store.mutated
        assert svc.stats.compactions == 1

    def test_flush_and_close_contract(self):
        svc, _, queries = _service(None)
        sched = ServingScheduler(svc, max_batch=4, deadline_ms=5.0)
        futs = [sched.query(q, topk=TOPK) for q in queries]
        sched.flush(timeout=30)
        assert all(f.done() for f in futs)
        sched.close()
        sched.close()                      # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            sched.query(queries[0])


class TestNamespaces:
    def _two_tenant(self):
        svc_a, corpus, queries = _service(None)
        svc_b, _, _ = _service(2)
        sched = ServingScheduler(
            {"a": svc_a, "b": svc_b},
            max_batch=4, deadline_ms=5.0,
            quotas={"a": TenantQuota(max_items=N_CORPUS + 4)})
        return sched, svc_a, svc_b, corpus, queries

    def test_tenants_route_to_their_own_index(self):
        sched, svc_a, svc_b, corpus, queries = self._two_tenant()
        with sched:
            assert sorted(sched.namespaces()) == ["a", "b"]
            assert sched.service("a") is svc_a
            ra = sched.query(queries[0], tenant="a", topk=TOPK).result(30)
            rb = sched.query(queries[0], tenant="b", topk=TOPK).result(30)
            da = svc_a.query_arrays(queries[:1], topk=TOPK)
            db = svc_b.query_arrays(queries[:1], topk=TOPK)
            np.testing.assert_array_equal(ra[0], da[0][0])
            np.testing.assert_array_equal(rb[0], db[0][0])
            # per-tenant counters stay per-tenant (1 scheduled + 1 direct)
            assert sched.tenant_stats("a").queries == 2
            assert sched.tenant_stats("b").queries == 2
            with pytest.raises(KeyError, match="unknown namespace"):
                sched.query(queries[0], tenant="nope")
            with pytest.raises(ValueError, match="already registered"):
                sched.add_namespace("a", svc_a)

    def test_max_items_quota_rejects_oversize_insert(self):
        sched, svc_a, _, corpus, _ = self._two_tenant()
        with sched:
            sched.insert(np.asarray(corpus[:4]), tenant="a").result(30)
            with pytest.raises(QuotaExceeded, match="max_items"):
                sched.insert(np.asarray(corpus[:1]), tenant="a")
            assert svc_a.stats.rejected == 1
            # tenant "b" has no quota: same insert admits fine
            sched.insert(np.asarray(corpus[:1]), tenant="b").result(30)

    def test_max_pending_quota_sheds_load(self):
        svc, _, queries = _service(None)
        sched = ServingScheduler(
            svc, max_batch=4, deadline_ms=5.0,
            quotas={"default": TenantQuota(max_pending=0)})
        with sched:
            with pytest.raises(QuotaExceeded, match="max_pending"):
                sched.query(queries[0])
            assert svc.stats.rejected == 1
