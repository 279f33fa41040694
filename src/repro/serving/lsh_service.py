"""Batched LSH similarity-search service — the paper's workload as a
deployable serving component.

A corpus of tensors (dense / CP / TT format) is hashed once at build time
with one of the paper's families; queries arrive in batches and run through
the segment-store indexes of ``repro.core.index`` as one jit-compiled
program — batch-native fused hashing (projection -> discretize -> bucket
keys in one program; ``build_service(..., hash_backend=...)`` picks the
XLA einsum path or the Pallas kernels, 'auto' = xla), vmapped
``searchsorted`` bucket probes over every segment's sorted key tables,
tombstone filtering, and exact in-format re-rank — never leaving the
accelerator until the final top-k.

The corpus is mutable in place: ``insert(batch)`` appends a sorted delta
segment (served immediately, no rebuild), ``delete(ids)`` tombstones items
by their current effective ids, and ``compact()`` folds deltas and
tombstones back into one base segment (also triggered automatically past
the index's ``max_deltas``). ``ServiceStats`` tracks the mutation traffic
next to the query traffic, with automatic compaction time split out of
``insert_ms`` (``auto_compact_ms``/``auto_compactions``) so ingest
throughput numbers never silently absorb fold cost.

Mutations never stall serving: ``prepare_compact()``/``prepare_rebalance()``
build the full replacement store off the query path (every array
materialized and placed — the second buffer of a double-buffered swap) and
``apply_swap()`` publishes it as a single pointer flip. Queries dispatched
before the flip finish on the store they pinned, bit-identical to its
answers; the synchronous ``compact()``/``rebalance()`` endpoints are the
same prepare+flip pair run back-to-back. ``repro.serving.scheduler`` runs
the prepare step on its ingest lane so the query lane never waits.

``LSHService(..., shards=S)`` serves through the mesh-sharded
``ShardedLSHIndex``, whose mutation plane is shard-native: the base
segment is partitioned into S per-shard sorted tables (placed over a mesh
axis when one is available, see ``repro.distributed.index_sharding``),
``insert`` routes each batch to the least-loaded shards as one sharded
delta slab (no replication), ``compact()`` is shard-local, and the
explicit ``rebalance()`` endpoint re-partitions the live corpus when
occupancy skews (``ServiceStats.shard_occupancy`` / ``rebalances`` track
it). Queries fan out to every shard, probe base + delta slabs per shard,
and merge globally. Effective-id bookkeeping is automatic — callers
always see ids into the current live corpus regardless of shard or
segment count.

``LSHService(..., device=False)`` serves through ``HostLSHIndex`` (the
dict-of-buckets build kept as the membership reference); queries run
through the same shared planner, mutations are rebuild-only.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Sequence

import jax
import numpy as np

from repro import tracing
from repro.core.index import (DeviceLSHIndex, HostLSHIndex, ShardedLSHIndex,
                              _SegmentedIndex)
from repro.core.lsh import LSHFamily, make_family
from repro.core.probing import QUERY_MODES


@dataclasses.dataclass
class ServiceStats:
    queries: int = 0
    batches: int = 0
    total_ms: float = 0.0
    total_candidates: int = 0
    build_s: float = 0.0
    # per-mode query counters (topk + uniform + weighted == queries)
    topk_queries: int = 0
    uniform_queries: int = 0
    weighted_queries: int = 0
    # mutation counters
    inserted: int = 0          # items appended via insert()
    insert_batches: int = 0
    insert_ms: float = 0.0     # insert wall time, auto-compaction excluded
    deleted: int = 0           # items tombstoned via delete()
    delete_batches: int = 0
    compactions: int = 0       # explicit compact()/apply_swap publications
    compact_ms: float = 0.0    # explicit compact build wall time only
    auto_compactions: int = 0  # max_deltas-triggered folds inside insert()
    auto_compact_ms: float = 0.0
    rebalances: int = 0        # explicit cross-shard re-partitions
    rebalance_ms: float = 0.0
    rejected: int = 0          # requests refused by a tenant quota
                               # (set by the serving scheduler)
    shard_occupancy: tuple[int, ...] = ()  # live items per shard (sharded
                                           # index only; updated per mutation)
    # robustness / durability counters
    errors: int = 0            # failed ingest-lane mutations (scheduler)
    last_error: str = ""       # "<Type>: <message>" of the newest failure
    retries: int = 0           # ingest retries after transient IO failures
    timeouts: int = 0          # requests expired past the scheduler deadline
    unavailable: int = 0       # requests shed while degraded/recovering
    recoveries: int = 0        # successful snapshot+replay recoveries
    recovery_ms: float = 0.0   # restore + replay wall time
    wal_appends: int = 0       # committed WAL records
    wal_ms: float = 0.0        # fsync-inclusive WAL append wall time
    snapshots: int = 0         # atomic snapshots written
    snapshot_ms: float = 0.0

    @property
    def occupancy_skew(self) -> float:
        """max/mean live items per shard (1.0 = perfectly balanced)."""
        occ = self.shard_occupancy
        if not occ or not sum(occ):
            return 1.0
        return max(occ) * len(occ) / sum(occ)

    @property
    def mean_latency_ms(self):
        return self.total_ms / max(self.queries, 1)

    @property
    def mean_candidates(self):
        return self.total_candidates / max(self.queries, 1)

    @property
    def qps(self):
        return self.queries / max(self.total_ms / 1e3, 1e-9)

    @property
    def insert_items_per_s(self):
        return self.inserted / max(self.insert_ms / 1e3, 1e-9)

    def reset(self):
        """Zero the query counters (e.g. after jit warmup); keeps build_s
        and the mutation counters."""
        self.queries = self.batches = 0
        self.topk_queries = self.uniform_queries = self.weighted_queries = 0
        self.total_ms = 0.0
        self.total_candidates = 0

    def reset_mutations(self):
        """Zero the mutation counters — ``build()`` calls this on every
        (re)build so the stats always describe the live index, never a
        previous corpus's mutation history."""
        self.inserted = self.insert_batches = 0
        self.deleted = self.delete_batches = 0
        self.compactions = self.auto_compactions = self.rebalances = 0
        self.insert_ms = self.compact_ms = 0.0
        self.auto_compact_ms = self.rebalance_ms = 0.0
        self.rejected = 0
        self.shard_occupancy = ()
        self.errors = self.retries = self.timeouts = self.unavailable = 0
        self.last_error = ""
        self.recoveries = self.wal_appends = self.snapshots = 0
        self.recovery_ms = self.wal_ms = self.snapshot_ms = 0.0


class LSHService:
    """build() once, then serve query batches and streaming mutations."""

    def __init__(self, family: LSHFamily, metric: str = "euclidean",
                 device: bool = True, bucket_cap: int | None = None,
                 shards: int | None = None, max_deltas: int = 8,
                 probes: int = 1, query_mode: str = "topk",
                 probe_backend: str = "auto"):
        if int(probes) < 1:
            raise ValueError(f"probes must be >= 1, got {probes}")
        if query_mode not in QUERY_MODES:
            raise ValueError(f"unknown query_mode {query_mode!r}; expected "
                             f"one of {QUERY_MODES}")
        self.probes = int(probes)
        self.query_mode = query_mode
        if shards is not None:
            if not device:
                raise ValueError(
                    "shards requires the device index (pass device=True); "
                    "the host-dict path has no sharded layout")
            self.index = ShardedLSHIndex(family, metric=metric, shards=shards,
                                         bucket_cap=bucket_cap,
                                         max_deltas=max_deltas,
                                         probe_backend=probe_backend)
        elif device:
            self.index = DeviceLSHIndex(family, metric=metric,
                                        bucket_cap=bucket_cap,
                                        max_deltas=max_deltas,
                                        probe_backend=probe_backend)
        else:
            if bucket_cap is not None:
                raise ValueError(
                    "bucket_cap applies to the device index only; the host "
                    "index always probes full buckets (pass device=True)")
            self.index = HostLSHIndex(family, metric=metric,
                                      probe_backend=probe_backend)
        self.stats = ServiceStats()
        self.health = "serving"  # namespace health; the durable subclass
                                 # moves through cold/recovering/degraded

    @property
    def probe_path(self) -> str:
        """The resolved probe backend ('xla' | 'pallas') the underlying
        index serves queries through (see ``core.index.*.probe_path``)."""
        return self.index.probe_path

    def build(self, corpus, batch_size: int = 2048) -> "LSHService":
        t0 = time.perf_counter()
        self.index.build(corpus, batch_size=batch_size)
        self.stats.build_s = time.perf_counter() - t0
        self.stats.reset_mutations()   # stats describe the live index only
        self._track_shards()
        return self

    # -- queries ------------------------------------------------------------

    def query_arrays(self, queries, topk: int = 10, *,
                     probes: int | None = None, mode: str | None = None,
                     seed: int | None = None, stat_rows: int | None = None):
        """Batched raw results: (ids (B, topk), scores (B, topk), n_cand (B,)).

        ids are effective (live-corpus) ids, -1-filled where a row has fewer
        than topk candidates. One jit-compiled call through the shared
        segment planner for every index deployment.

        ``probes``/``mode`` override the service defaults per request —
        validated here with the constructor's contract, so a bad override
        raises the same ``ValueError`` instead of flowing into the jit
        program. The sampling modes (``"uniform"``/``"weighted"``) draw
        ``topk`` distinct members from the probed bucket union and require
        an explicit per-request ``seed`` (the PRNG key is derived from it
        and nothing else — the same seed on the same index state replays
        the exact draw; the service keeps no hidden sampling state).

        ``stat_rows`` caps the row count attributed to the query counters —
        the micro-batch scheduler pads coalesced batches to stable program
        shapes and passes the real request count so pad rows never inflate
        per-tenant stats.

        The call is one ``lsh.query.call`` span (dispatch, wait, copy of
        the answers to the host; ``total_ms`` is its duration) with a
        ``lsh.query.wait`` child around the wait for the device.
        """
        probes = self.probes if probes is None else int(probes)
        if probes < 1:
            raise ValueError(f"probes must be >= 1, got {probes}")
        if int(topk) < 1:
            raise ValueError(f"topk must be >= 1, got {topk}")
        mode = self.query_mode if mode is None else mode
        if mode not in QUERY_MODES:
            raise ValueError(f"unknown query mode {mode!r}; expected one "
                             f"of {QUERY_MODES}")
        rng = None
        if mode in ("uniform", "weighted"):
            if seed is None:
                raise ValueError(
                    f"mode={mode!r} needs an explicit per-request seed "
                    "(sampling draws are seeded, never implicit)")
            rng = jax.random.PRNGKey(int(seed))
        elif seed is not None:
            raise ValueError("seed applies to the sampling modes only; "
                             "mode='topk' is deterministic")
        n = jax.tree.leaves(queries)[0].shape[0]
        if stat_rows is not None:
            n = min(n, int(stat_rows))
        with tracing.span("lsh.query.call", n=n) as call:
            out = self.index.query_batch(queries, topk=topk, probes=probes,
                                         mode=mode, rng=rng)
            with tracing.span("lsh.query.wait"):
                jax.block_until_ready(out)
            ids, scores, n_cand = (np.asarray(a) for a in out)
        self.stats.queries += n
        setattr(self.stats, f"{mode}_queries",
                getattr(self.stats, f"{mode}_queries") + n)
        self.stats.batches += 1
        self.stats.total_ms += call.seconds * 1e3
        self.stats.total_candidates += int(n_cand.sum())
        return ids, scores, n_cand

    def query_batch(self, queries, topk: int = 10, *,
                    probes: int | None = None, mode: str | None = None,
                    seed: int | None = None) -> list[dict[str, Any]]:
        """Per-query result dicts (ids/scores trimmed of -1 fill)."""
        ids, scores, n_cand = self.query_arrays(queries, topk=topk,
                                                probes=probes, mode=mode,
                                                seed=seed)
        out = []
        for row_ids, row_scores, nc in zip(ids, scores, n_cand):
            mask = row_ids >= 0
            out.append({"ids": row_ids[mask], "scores": row_scores[mask],
                        "candidates": int(nc)})
        return out

    # -- mutations ----------------------------------------------------------

    def _mutable_index(self) -> _SegmentedIndex:
        if not isinstance(self.index, _SegmentedIndex):
            raise TypeError(
                "the host index is rebuild-only; streaming mutations need "
                "the device or sharded index (device=True)")
        return self.index

    def _track_shards(self) -> None:
        if isinstance(self.index, ShardedLSHIndex):
            self.stats.shard_occupancy = tuple(
                int(c) for c in self.index.occupancy())

    def _sync_mutation_stats(self) -> None:
        """Mirror the index's mutation counters into the stats, splitting
        max_deltas-triggered automatic folds from explicit publications."""
        index = self.index
        self.stats.auto_compactions = index.auto_compactions
        self.stats.auto_compact_ms = index.auto_compact_s * 1e3
        self.stats.compactions = index.compactions - index.auto_compactions
        self.stats.rebalances = getattr(index, "rebalances", 0)

    def insert(self, batch, batch_size: int = 2048) -> "LSHService":
        """Append a batch of items (one delta segment — a routed sharded
        slab on the sharded index — served immediately). A max_deltas
        auto-compaction triggered here is timed into ``auto_compact_ms``,
        never ``insert_ms`` — ``insert_items_per_s`` measures ingest, not
        fold cost. The apply is one ``lsh.index.insert`` span (its
        duration less the fold's feeds ``insert_ms``)."""
        index = self._mutable_index()
        n = jax.tree.leaves(batch)[0].shape[0]
        auto_s0 = index.auto_compact_s
        with tracing.span("lsh.index.insert", n=n) as apply:
            index.insert(batch, batch_size=batch_size)
            jax.block_until_ready(
                [seg.sorted_keys for seg in
                 [index.store.base] + index.store.deltas])
        self.stats.insert_ms += (apply.seconds
                                 - (index.auto_compact_s - auto_s0)) * 1e3
        self.stats.inserted += n
        self.stats.insert_batches += 1
        self._sync_mutation_stats()
        self._track_shards()
        return self

    def delete(self, ids) -> int:
        """Tombstone items by their current effective ids; returns count."""
        n = self._mutable_index().delete(ids)
        self.stats.deleted += n
        self.stats.delete_batches += 1
        self._track_shards()
        return n

    def prepare_compact(self):
        """Build the compacted replacement store OFF the query path and
        return the pending swap (None when there is nothing to fold).
        Queries keep serving the live store while this runs; publish the
        result with ``apply_swap``. The build wall time lands in
        ``compact_ms``."""
        index = self._mutable_index()
        t0 = time.perf_counter()
        pending = index.prepare_compact()
        self.stats.compact_ms += (time.perf_counter() - t0) * 1e3
        return pending

    def prepare_rebalance(self):
        """Build the globally re-partitioned replacement store off the
        query path (sharded index only); publish with ``apply_swap``. The
        build wall time lands in ``rebalance_ms``."""
        index = self._mutable_index()
        if not isinstance(index, ShardedLSHIndex):
            raise TypeError("rebalance applies to the sharded index only "
                            "(pass shards=S)")
        t0 = time.perf_counter()
        pending = index.prepare_rebalance()
        self.stats.rebalance_ms += (time.perf_counter() - t0) * 1e3
        return pending

    def apply_swap(self, pending) -> "LSHService":
        """Publish a prepared store: one pointer flip, no device work.
        Raises RuntimeError if the index mutated since the prepare (the
        shadow would drop those mutations) — serialize mutations with the
        prepare/apply pair, as the scheduler's ingest lane does."""
        self._mutable_index().apply_swap(pending)
        self._sync_mutation_stats()
        self._track_shards()
        return self

    def compact(self) -> "LSHService":
        """Fold deltas + tombstones back into the base (shard-local on the
        sharded index — shards keep their item mix, see ``rebalance``).
        Synchronous prepare + flip; single-threaded callers see exactly
        the old behavior."""
        return self.apply_swap(self.prepare_compact())

    def rebalance(self) -> "LSHService":
        """Re-partition the live corpus into contiguous, evenly-sized
        shards (the explicit cross-shard move; sharded index only)."""
        return self.apply_swap(self.prepare_rebalance())


def build_service(key, kind: str, dims: Sequence[int], corpus, *,
                  metric: str | None = None, num_codes: int = 8,
                  num_tables: int = 8, rank: int = 4,
                  bucket_width: float = 4.0, device: bool = True,
                  bucket_cap: int | None = None,
                  shards: int | None = None,
                  max_deltas: int = 8,
                  hash_backend: str = "auto",
                  probe_backend: str = "auto",
                  probes: int = 1,
                  query_mode: str = "topk") -> LSHService:
    metric = metric or ("cosine" if kind.endswith("srp") else "euclidean")
    fam = make_family(key, kind, dims, num_codes=num_codes,
                      num_tables=num_tables, rank=rank,
                      bucket_width=bucket_width, hash_backend=hash_backend)
    return LSHService(fam, metric=metric, device=device,
                      bucket_cap=bucket_cap, shards=shards,
                      max_deltas=max_deltas, probes=probes,
                      query_mode=query_mode,
                      probe_backend=probe_backend).build(corpus)
