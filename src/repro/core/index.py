"""Multi-table LSH indexes for approximate nearest-neighbour search.

The classic (K, L) construction on top of the paper's hash families, built
on the segment core in ``repro.core.segments``: every index is a
``SegmentStore`` — one immutable base segment (per-table sorted uint32
bucket keys + permutation + corpus slice) plus bounded delta segments
(streaming inserts) and a tombstone mask (streaming deletes) — queried by a
single shared planner: hash the batch once, probe every segment with the
vmapped ``searchsorted``/gather path, filter tombstones inside the probe,
re-rank exactly in format, and merge the per-segment top-k with the stable
validity-aware two-key sort (the PR 2 shard merge, reused verbatim for
segments). Three deployments share that planner:

``DeviceLSHIndex`` (the default, exported as ``LSHIndex``) keeps the store
on one device and runs one jit program per query batch.

``ShardedLSHIndex`` is shard-native end-to-end: the base segment lays over
a mesh axis in S contiguous shards (the shard_map placement lives in
``repro.distributed.index_sharding``) and every mutation stays on the
shards. ``insert`` routes each batch to shards least-loaded-first in
contiguous slabs (one ``ShardedSegment`` delta per batch, placed with the
same NamedSharding rules as the base — nothing is replicated), and
``compact()`` folds each shard's base slice + delta slabs + tombstones
into a new base shard locally, with no cross-shard traffic; only an
explicit ``rebalance()`` re-partitions the live corpus contiguously when
occupancy skews. Results are identical to ``DeviceLSHIndex`` for any
shard count.

``HostLSHIndex`` keeps the FAISS-style dict-of-buckets build as the
bucket-membership semantics reference (``candidates()`` probes the dicts),
but serves ``query``/``query_batch`` through the same shared planner over a
single-segment store.

Mutation API (device + sharded): ``insert(batch)`` hashes the batch and
appends a small sorted delta segment (one jit sort program; queries start
probing it immediately), ``delete(ids)`` tombstones items by their current
effective ids (no recompilation — only mask bits flip), and ``compact()``
merges the surviving keys + corpus rows back into one base segment without
re-hashing. With the default exact bucket cap, query results match a fresh
build over the effective corpus bit-identically: ids and candidate counts
always; scores to float-reassociation ulps while deltas are outstanding or
while a shard-locally compacted base partitions shards differently from a
contiguous fresh build, and exactly whenever the stored arrays coincide
with a fresh build's (a flat ``compact()``, or a sharded ``rebalance()``).
Indexes built with an explicit ``bucket_cap`` keep live-window lookups, so
a truncated probe window gathers the first ``cap`` *live* members of each
bucket — tombstones no longer consume window space — but delta segments
still carry their own caps, so the fresh-rebuild parity guarantee applies
to the default cap only. Inserts past ``max_deltas`` outstanding deltas
trigger an automatic compaction.

Bucket keys are a universal multiply-add hash of the K integer hashcodes in
uint32 arithmetic (natural mod-2^32 wraparound) so the numpy host path and
the jnp device path produce bit-identical keys without requiring x64 mode.
Build, insert, and query hashing all run through the family's batch-native
``hash_keys`` program (``segments.bucket_keys`` / ``query_keys``):
projection, discretization, and the key combine are one fused program per
batch, on the XLA or Pallas backend the family's ``hash_backend`` selects.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import probing, segments
from repro.core.lsh import LSHFamily
from repro.core.probing import QUERY_MODES
from repro.core.segments import (SegmentStore, bucket_keys, build_segment,
                                 build_sharded_segment, make_mults,
                                 tree_index)

# Back-compat aliases: the pre-segment module exposed these underscored
# helpers; the tier-1 tests import them from here.
_combine_codes = segments._combine_codes
_make_mults = make_mults
_max_run_length = segments._max_run_length


def _check_metric(metric: str) -> None:
    if metric not in ("euclidean", "cosine"):
        raise ValueError(metric)


def _check_mode(mode: str, rng) -> None:
    """Shared query-mode validation: sampling modes need an explicit PRNG
    key per request (no hidden state — reusing a key replays the draw),
    and the deterministic top-k mode must not be handed one silently."""
    if mode not in QUERY_MODES:
        raise ValueError(
            f"unknown query mode {mode!r}; expected one of {QUERY_MODES}")
    if mode == "topk" and rng is not None:
        raise ValueError("rng applies to the sampling modes only; "
                         "mode='topk' is deterministic")
    if mode != "topk" and rng is None:
        raise ValueError(
            f"mode={mode!r} samples from the probed bucket union and needs "
            "an explicit PRNG key (pass rng=jax.random.PRNGKey(seed))")


def _score_fn(metric: str):
    return segments._score_fn(metric)


@jax.jit
def _hash_one(family, x):
    return family.hash(x)


# ---------------------------------------------------------------------------
# Shared single-query wrappers (one mixin, not three copies)
# ---------------------------------------------------------------------------


class _LSHIndexBase:
    """Query API shared by every index deployment.

    Subclasses provide ``query_batch`` / ``candidates_batch`` (and the
    ``family`` / ``metric`` / ``corpus`` attributes); the single-query
    wrappers below are the one shared implementation of the
    ``(ids, scores, n_candidates)`` numpy contract.
    """

    def candidates(self, x, probes: int = 1) -> np.ndarray:
        """Union of live bucket members over all tables/segments (sorted);
        ``probes`` = T > 1 widens each table to its T ranked buckets."""
        cand, valid = self.candidates_batch(tree_index(x, None),
                                            probes=probes)
        cand = np.asarray(cand[0])
        return np.sort(cand[np.asarray(valid[0])]).astype(np.int64)

    def query(self, x, topk: int = 10, *, probes: int = 1,
              mode: str = "topk", rng=None
              ) -> tuple[np.ndarray, np.ndarray, int]:
        """-> (ids, scores, n_candidates). Exact re-rank of the candidates.

        scores are distances (ascending) for 'euclidean', similarities
        (descending) for 'cosine'; rows with fewer than ``topk`` candidates
        are trimmed of the -1 fill. ``probes``/``mode``/``rng`` follow the
        ``query_batch`` contract (multi-probe expansion + sampling modes).
        """
        ids, scores, n_cand = self.query_batch(tree_index(x, None), topk,
                                               probes=probes, mode=mode,
                                               rng=rng)
        ids = np.asarray(ids[0])
        mask = ids >= 0
        return (ids[mask].astype(np.int64), np.asarray(scores[0])[mask],
                int(n_cand[0]))

    def effective_corpus(self):
        """The corpus the returned ids index into (rebuild-only paths)."""
        return self.corpus


@dataclasses.dataclass(frozen=True)
class PendingSwap:
    """A fully-built shadow store awaiting publication (the second buffer
    of the double-buffered swap).

    ``prepare_compact()`` / ``prepare_rebalance()`` build the replacement
    store off the query path — every device array materialized and placed —
    and hand back one of these; ``apply_swap()`` publishes it as a pointer
    flip. ``source``/``generation`` pin the store state the shadow was
    derived from, so a swap can never silently discard mutations that
    landed while the shadow was building."""

    store: SegmentStore
    kind: str                 # "compact" | "rebalance"
    source: SegmentStore
    generation: int
    corpus_cache: Any = None  # sharded: the ``_corpus`` value post-flip


class _SegmentedIndex(_LSHIndexBase):
    """Store-backed mutation + introspection API shared by the device and
    sharded deployments. Subclasses implement ``_new_store`` and
    ``_build_compact_store``."""

    store: SegmentStore | None

    @property
    def size(self) -> int:
        """Number of live (queryable) items."""
        return self.store.n_live if self.store is not None else 0

    @property
    def sorted_keys(self):
        return self.store.base.sorted_keys

    @property
    def perm(self):
        return self.store.base.perm

    @property
    def cap(self) -> int:
        return self.store.base.cap

    def effective_corpus(self):
        return self.store.effective_corpus()

    # -- mutations ----------------------------------------------------------

    def insert(self, batch, batch_size: int = 1024):
        """Append a batch of items as one small sorted delta segment.

        The batch is hashed once and sorted in one jit program; queries
        probe the new segment immediately. New items take the next
        effective ids (after every currently-live item). More than
        ``max_deltas`` outstanding deltas trigger an automatic
        ``compact()``.
        """
        if jax.tree.leaves(batch)[0].shape[0] == 0:
            return self
        keys = bucket_keys(self.family, self._mults, batch, batch_size)
        self.store.append_delta(
            build_segment(keys, batch, bucket_cap=self.bucket_cap))
        self._maybe_auto_compact()
        return self

    def delete(self, ids) -> int:
        """Tombstone items by their current effective ids (the numbering
        ``query``/``query_batch`` return). Later items shift down, exactly
        as in a fresh rebuild without them. Returns the number deleted."""
        return self.store.delete_effective(np.asarray(ids))

    def _maybe_auto_compact(self) -> None:
        """Compact when the delta count exceeds ``max_deltas``, accounting
        the fold's wall time separately (``auto_compact_s`` /
        ``auto_compactions``, fed from the ``lsh.fold`` span) so callers
        timing an ``insert`` can split the mutation cost from the
        compaction cost it occasionally triggers."""
        if len(self.store.deltas) <= self.max_deltas:
            return
        with tracing.span("lsh.fold", deltas=len(self.store.deltas)) as fold:
            self.compact()
            jax.block_until_ready(self.store.base.sorted_keys)
        self.auto_compact_s += fold.seconds
        self.auto_compactions += 1

    def _reset_mutation_state(self) -> None:
        """Rebuilding (``build()`` on a live index) starts a fresh mutation
        history — stale compaction/rebalance counters would otherwise
        describe the previous corpus."""
        self.compactions = 0
        self.auto_compactions = 0
        self.auto_compact_s = 0.0

    # -- double-buffered swap -----------------------------------------------

    def prepare_compact(self) -> PendingSwap | None:
        """Build the compacted replacement store OFF the query path.

        Gathers the stored corpus-order keys of every surviving item (no
        re-hashing), rebuilds the sorted tables, places every array, and
        blocks until all of it has landed — the live store is untouched and
        queries keep serving it throughout. Returns the pending shadow
        store for ``apply_swap`` (None when the store is pristine and there
        is nothing to fold)."""
        store = self.store
        if not store.mutated:
            return None
        if store.n_live == 0:
            raise ValueError("cannot compact an index with no live items")
        shadow = self._build_compact_store(store)
        jax.block_until_ready(jax.tree.leaves(shadow.view.all_arrays))
        return PendingSwap(store=shadow, kind="compact", source=store,
                           generation=store.generation)

    def apply_swap(self, pending: PendingSwap | None):
        """Publish a prepared shadow store: one pointer flip, no device
        work. Queries in flight finish on whichever store they pinned at
        dispatch (results are bit-identical to that store's answers);
        queries dispatched after the flip serve the new store. Raises
        RuntimeError if the live store mutated after ``pending`` was
        prepared — the shadow would silently drop those mutations —
        so callers (the serving scheduler's ingest lane) must serialize
        mutations with the prepare/apply pair."""
        if pending is None:
            return self
        store = self.store
        if (store is not pending.source
                or store.generation != pending.generation):
            raise RuntimeError(
                "store mutated since this swap was prepared; the shadow "
                "store is stale — call prepare again (serialize mutations "
                "with the prepare/apply pair, e.g. on the serving "
                "scheduler's ingest lane)")
        self._pre_publish(pending)
        self.store = pending.store      # the flip
        if pending.kind == "compact":
            self.compactions += 1
        else:
            self.rebalances += 1
        return self

    def _pre_publish(self, pending: PendingSwap) -> None:
        """Subclass hook: index-side cache updates that must ride the flip."""

    def compact(self):
        """Merge base + deltas minus tombstones into one fresh base segment.

        Runs as a synchronous double-buffered swap: the replacement store
        is fully built first (``prepare_compact`` — stored keys only, no
        re-hash), then published as a pointer flip, so even a caller
        interleaving queries from another thread never observes a
        half-built store. Afterwards effective and physical ids coincide
        and query programs return to the single-base shape. With the
        default exact cap results are unchanged by construction; with an
        explicit ``bucket_cap`` compaction reclaims the probe-window slots
        tombstones were consuming, so truncated buckets can regain
        candidates.
        """
        return self.apply_swap(self.prepare_compact())


# ---------------------------------------------------------------------------
# Device index (single-device segment store)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DeviceLSHIndex(_SegmentedIndex):
    """Device-resident (K, L) index: a segment store of sorted bucket keys +
    permutations, fully batched jit-compiled queries, streaming mutations.

    corpus: any pytree whose leaves share a leading axis of size n. Query
    batches are pytrees with a leading batch axis B; `query_batch` returns
    (ids (B, topk) int32 with -1 fill, scores (B, topk), n_candidates (B,)).
    """

    family: LSHFamily
    metric: str = "euclidean"  # or "cosine"
    seed: int = 0
    bucket_cap: int | None = None  # None -> exact (largest build-time bucket)
    max_deltas: int = 8            # outstanding deltas before auto-compact
    swap_chunk_rows: int | None = 4096  # shadow-build copy chunk (None ->
                                        # one store-sized program per fold)
    probe_backend: str = "auto"    # 'auto' | 'xla' | 'pallas' — the fused
                                   # probe path (segments.resolved_probe_backend)

    store: SegmentStore | None = None
    compactions: int = 0
    auto_compactions: int = 0
    auto_compact_s: float = 0.0
    _mults: np.ndarray | None = None

    def __post_init__(self):
        _check_metric(self.metric)
        self._mults = make_mults(self.seed, self.family.num_codes)

    @property
    def probe_path(self) -> str:
        """The resolved probe program ``query_batch`` executes: ``"xla"``
        (the fused segment-major schedule) or ``"pallas"`` (the fused query
        kernel). Introspection hook for CI legs that must fail loudly if
        the requested backend silently falls back."""
        return segments.resolved_probe_backend(self.probe_backend)

    @property
    def corpus(self):
        """The effective (live) corpus the returned ids index into."""
        return self.store.effective_corpus() if self.store else None

    # -- build --------------------------------------------------------------

    def build(self, corpus, batch_size: int = 1024) -> "DeviceLSHIndex":
        keys = bucket_keys(self.family, self._mults, corpus, batch_size)
        self.store = self._new_store(keys, corpus)
        self._reset_mutation_state()
        return self

    def _new_store(self, keys, corpus) -> SegmentStore:
        return SegmentStore(
            build_segment(keys, corpus, bucket_cap=self.bucket_cap,
                          warn_layout=type(self).__name__),
            live_window=self.bucket_cap is not None)

    def _build_compact_store(self, store: SegmentStore) -> SegmentStore:
        # chunked assembly (the default) keeps every fold program bounded
        # so concurrently dispatched queries interleave with the build —
        # values are bit-identical to the one-program gather. Its phases
        # are spans: lsh.fold.order and .gather (effective_arrays_chunked),
        # .sort (the throttled sort), .tables (the new store's lookups)
        if self.swap_chunk_rows is None:
            keys, corpus = store.effective_arrays()
            return self._new_store(keys, corpus)
        keys, corpus = store.effective_arrays_chunked(
            int(self.swap_chunk_rows))
        seg = build_segment(keys, corpus, bucket_cap=self.bucket_cap,
                            warn_layout=type(self).__name__,
                            sort_throttled=True)
        with tracing.span("lsh.fold.tables"):
            return SegmentStore(seg, live_window=self.bucket_cap is not None)

    # -- query --------------------------------------------------------------

    def candidates_batch(self, queries, *, probes: int = 1
                         ) -> tuple[jax.Array, jax.Array]:
        """-> (cand (B, W) effective ids with -1 fill, valid (B, W) bool)."""
        view = self.store.view
        return segments.segmented_candidates(
            self.family, view.all_arrays, jnp.asarray(self._mults),
            queries, caps=view.all_caps, probes=int(probes))

    def query_batch(self, queries, topk: int = 10, *, probes: int = 1,
                    mode: str = "topk", rng=None):
        """-> (ids (B, topk), scores (B, topk), n_candidates (B,)) jax arrays.

        Rows with fewer than topk candidates are filled with id -1 and
        +inf distance / -inf similarity. One jit-compiled program end-to-end
        over every segment (base + outstanding deltas, tombstones filtered).

        ``probes`` = T > 1 turns on query-directed multi-probe: each table
        probes its T most promising buckets (``repro.core.probing``), so
        fewer tables reach the same recall; T=1 is bit-identical to the
        single-probe program. ``mode`` selects the result semantics:
        ``"topk"`` (default) is the exact re-ranked top-k; ``"uniform"`` /
        ``"weighted"`` instead *sample* ``topk`` distinct members from the
        probed bucket union (uniformly / proportional to bucket size) and
        need an explicit per-request PRNG key via ``rng``.
        """
        _check_mode(mode, rng)
        view = self.store.view
        args = (self.family, view.all_arrays,
                jnp.asarray(self._mults), queries)
        if mode != "topk":
            return segments.segmented_sample(
                *args, rng, metric=self.metric, topk=topk,
                caps=view.all_caps, probes=int(probes), mode=mode)
        return segments.segmented_query(
            *args, metric=self.metric, topk=topk, caps=view.all_caps,
            probes=int(probes), probe_backend=self.probe_backend)


LSHIndex = DeviceLSHIndex  # default deployment


# ---------------------------------------------------------------------------
# Mesh-sharded index (sharded base segment + replicated deltas)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardedLSHIndex(_SegmentedIndex):
    """Corpus-sharded (K, L) index over a named mesh axis with a global
    top-k merge — the multi-host layout of ``DeviceLSHIndex``.

    The *base* segment is partitioned into ``shards`` contiguous slices;
    each shard holds its own (L, n_s) sorted bucket keys + permutation
    (local ids, pad slots marked with the n_s sentinel) and its (n_s, ...)
    corpus slice, placed with ``NamedSharding``. Mutations are
    shard-native: ``insert`` routes each batch to shards with the
    deterministic least-loaded-first policy (``segments.route_balanced``)
    and appends one sharded delta slab per batch, placed exactly like the
    base; ``delete`` flips tombstone bits; ``compact()`` folds every
    shard's base slice + delta slabs + tombstones into a new base shard
    *locally* (no re-hash, no global gather — O(n/S) per shard), leaving
    each shard's item mix unchanged; ``rebalance()`` is the explicit
    global re-partition for when sustained skew (or compaction history)
    leaves occupancy uneven, and restores the contiguous fresh-build
    layout. A query batch runs as one jit program: replicated hashing,
    per-shard probe of the base block + every delta slab with an in-shard
    merge (via ``shard_map`` when a mesh carries the shard axis, ``vmap``
    otherwise — see ``query_path``), then the single global S-way merge.
    With the default exact cap the merged top-k is bit-identical to
    ``DeviceLSHIndex`` for any shard count and any routing.

    An explicit ``bucket_cap`` truncates each *shard's* slice of a bucket,
    so the union of candidates can exceed the single-device truncation (up
    to S*L*cap) — recall can only improve, throughput bounds are per shard.
    """

    family: LSHFamily
    metric: str = "euclidean"  # or "cosine"
    seed: int = 0
    shards: int = 1
    bucket_cap: int | None = None  # None -> exact (largest per-shard bucket)
    max_deltas: int = 8
    swap_chunk_rows: int | None = 4096  # shadow-build copy chunk (None ->
                                        # one store-sized program per fold)
    probe_backend: str = "auto"    # 'auto' | 'xla' | 'pallas' — the fused
                                   # probe path (segments.resolved_probe_backend)
    keep_corpus: bool = True   # False drops the unsharded build-time copy
                               # (at real multi-host scale it won't fit;
                               # effective_corpus() regathers from shards)

    _corpus: Any = None            # build-time pytree (keep_corpus=True)
    store: SegmentStore | None = None
    compactions: int = 0
    rebalances: int = 0
    auto_compactions: int = 0
    auto_compact_s: float = 0.0
    mesh: Any = None               # jax Mesh carrying the shard axis, or None
    mesh_axis: str | None = None
    _mults: np.ndarray | None = None

    def __post_init__(self):
        _check_metric(self.metric)
        if int(self.shards) < 1:
            raise ValueError(f"shards must be >= 1, got {self.shards}")
        self._mults = make_mults(self.seed, self.family.num_codes)

    @property
    def corpus(self):
        """The effective (live) corpus the returned ids index into — the
        build-time pytree while pristine (None under ``keep_corpus=False``),
        regathered from the segments once mutated, matching
        ``DeviceLSHIndex.corpus``. A shard-local ``compact()`` invalidates
        the build-time copy (shards no longer hold contiguous slices); the
        regathered corpus is cached here so repeated access costs one
        gather, not one per call."""
        if self.store is None:
            return self._corpus
        if self.store.mutated:
            return self.store.effective_corpus()
        if self._corpus is None and self.keep_corpus:
            self._corpus = self.store.effective_corpus()
        return self._corpus

    @property
    def corpus_sharded(self):
        return self.store.base.corpus if self.store else None

    @property
    def shard_size(self) -> int:
        return self.store.base.shard_size

    @property
    def query_path(self) -> str:
        """The program ``query_batch`` executes: ``"shard_map"`` when a
        mesh carries the shard axis, ``"vmap"`` on the single-program
        fallback. Introspection hook for CI legs that must fail loudly if
        multi-device coverage silently degrades to the vmap path. The
        pallas probe backend always serves through the single-program
        path (its mesh shard_map dispatch is the deferred TPU leg), so it
        reports ``"vmap"`` even when a mesh exists."""
        return ("shard_map"
                if self.mesh is not None and self.probe_path != "pallas"
                else "vmap")

    @property
    def probe_path(self) -> str:
        """The resolved probe program ``query_batch`` executes: ``"xla"``
        (the fused segment-major schedule, inside whichever distribution
        program ``query_path`` names) or ``"pallas"`` (the fused query
        kernel, run per shard as a single program — its mesh shard_map
        dispatch is the deferred TPU leg, see ROADMAP)."""
        return segments.resolved_probe_backend(self.probe_backend)

    def occupancy(self) -> np.ndarray:
        """(S,) live items per shard (base + delta slabs)."""
        return self.store.shard_live_counts

    # -- build --------------------------------------------------------------

    def build(self, corpus, batch_size: int = 1024) -> "ShardedLSHIndex":
        from repro.distributed import index_sharding  # deferred: core<->dist

        keys = bucket_keys(self.family, self._mults, corpus, batch_size)
        self.mesh, self.mesh_axis = index_sharding.resolve_mesh(
            int(self.shards))
        self.store = self._new_store(keys, corpus)
        self._corpus = corpus if self.keep_corpus else None
        self._reset_mutation_state()
        return self

    def _reset_mutation_state(self) -> None:
        super()._reset_mutation_state()
        self.rebalances = 0

    def _place(self, shadow: bool = False):
        if self.mesh is None:
            return lambda t: t
        from repro.distributed import index_sharding
        fn = (index_sharding.place_shadow if shadow
              else index_sharding.place_sharded)
        return functools.partial(fn, mesh=self.mesh, axis=self.mesh_axis)

    def _place_segment(self, seg, shadow: bool = False):
        place = self._place(shadow)
        return dataclasses.replace(
            seg, keys=place(seg.keys), sorted_keys=place(seg.sorted_keys),
            perm=place(seg.perm), corpus=place(seg.corpus))

    def _new_store(self, keys, corpus, shadow: bool = False) -> SegmentStore:
        seg = build_sharded_segment(
            keys, corpus, int(self.shards), bucket_cap=self.bucket_cap,
            warn_layout=type(self).__name__)
        live_window = self.bucket_cap is not None
        if self.mesh is None:
            return SegmentStore(seg, live_window=live_window)
        return SegmentStore(self._place_segment(seg, shadow),
                            place=self._place(), live_window=live_window)

    # -- mutations (shard-native) -------------------------------------------

    def insert(self, batch, batch_size: int = 1024):
        """Route a batch to shards (least-loaded-first, contiguous slabs)
        and append it as one sharded delta slab, hashed once and sorted
        per shard locally. New items take the next effective ids in batch
        order, exactly as on the device index; more than ``max_deltas``
        outstanding deltas trigger an automatic (shard-local) compaction.
        """
        if jax.tree.leaves(batch)[0].shape[0] == 0:
            return self
        n = jax.tree.leaves(batch)[0].shape[0]
        keys = bucket_keys(self.family, self._mults, batch, batch_size)
        alloc, offsets = segments.route_balanced(
            n, self.store.shard_live_counts)
        seg, positions = segments.build_sharded_delta(
            keys, batch, alloc, offsets, seq0=self.store.seq_len,
            bucket_cap=self.bucket_cap)
        if self.mesh is not None:
            seg = self._place_segment(seg)
        self.store.append_delta(seg, positions)
        self._maybe_auto_compact()
        return self

    def compact(self):
        """Fold each shard's base slice + delta slabs + tombstones into a
        new base shard, shard-locally: stored keys only (no re-hash), one
        per-shard gather + sort program with no cross-shard traffic, so
        steady-state compaction costs O(n/S) per shard. Shards keep the
        item mix routing gave them — their sequence ranges stay
        non-contiguous until an explicit ``rebalance()``; effective ids
        (and so query results) are unchanged by construction. Runs as a
        synchronous double-buffered swap (build shadow, flip pointer), the
        same machinery ``prepare_compact``/``apply_swap`` expose to the
        serving plane."""
        return self.apply_swap(self.prepare_compact())

    def _build_compact_store(self, store: SegmentStore) -> SegmentStore:
        """The shard-local fold, pure with respect to ``self``: builds and
        returns the replacement store; the live store (and every query
        pinned to its view) is untouched. The chunked fold's phases are
        the same ``lsh.fold.*`` spans as the device index's."""
        s = store.base.shards
        segs = store._segments()
        with tracing.span("lsh.fold.order"):
            live2d = np.concatenate(
                [store.live_host[off:off + g.slots].reshape(s, g.shard_size)
                 for off, g in zip(
                     np.cumsum([0] + [g.slots for g in segs[:-1]]), segs)],
                axis=1)
            pos2d = np.concatenate(
                [p.reshape(s, g.shard_size)
                 for p, g in zip(store.slot_pos, segs)], axis=1)
            counts = live2d.sum(axis=1).astype(np.int64)
            new_ns = max(int(counts.max()), 1)
            w = live2d.shape[1]
            idx = np.full((s, new_ns), w, np.int64)
            new_pos = np.full((s, new_ns), -1, np.int64)
            eff_seq = np.cumsum(store._live_seq) - 1
            for sh in range(s):
                sel = np.flatnonzero(live2d[sh])    # slot order = seq order
                idx[sh, :sel.size] = sel
                new_pos[sh, :sel.size] = eff_seq[pos2d[sh, sel]]
            keys_cat = jnp.concatenate([g.keys for g in segs], axis=1)
        if self.swap_chunk_rows is None:
            corpus_cat = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=1),
                *[g.corpus for g in segs])
            keys_n, sorted_keys, perm, corpus_n, max_runs = \
                segments._slab_gather_sort(
                    keys_cat, corpus_cat, jnp.asarray(idx, jnp.int32),
                    jnp.asarray(counts, jnp.int32), shard_size=new_ns)
        else:
            # chunked fold (the default): same values as the monolithic
            # fold, issued as bounded programs — a small keys gather, one
            # sort per table, and per-chunk corpus copies in flat
            # (shard * slot) row space — with blocking between them, so
            # concurrent queries interleave with the build instead of
            # queueing behind one store-sized program
            with tracing.span("lsh.fold.order"):
                keys_n = segments._slab_gather_keys(
                    keys_cat, jnp.asarray(idx, jnp.int32))
                jax.block_until_ready(keys_n)
                segments._yield_slot()
            counts_j = jnp.asarray(counts, jnp.int32)
            tables = []
            with tracing.span("lsh.fold.sort"):
                for table in range(keys_n.shape[-1]):
                    out = segments._sort_shard_table(
                        keys_n[:, :, table], counts_j, shard_size=new_ns)
                    jax.block_until_ready(out)
                    segments._yield_slot()
                    tables.append(out)
                perm = jnp.stack([t[0] for t in tables], axis=1)
                sorted_keys = jnp.stack([t[1] for t in tables], axis=1)
                max_runs = jnp.stack([t[2] for t in tables])
            valid = idx < w          # sentinel w marks pad rows
            sh_i, col_i = np.nonzero(valid)
            srcs, src_idxs, dst_idxs = [], [], []
            off = 0
            for g in segs:
                wg = g.shard_size
                m = (idx[sh_i, col_i] >= off) & (idx[sh_i, col_i] < off + wg)
                srcs.append(jax.tree.map(
                    lambda a: a.reshape((s * wg,) + a.shape[2:]), g.corpus))
                src_idxs.append(sh_i[m] * wg + (idx[sh_i[m], col_i[m]] - off))
                dst_idxs.append(sh_i[m] * new_ns + col_i[m])
                off += wg
            flat = segments.gather_rows_chunked(
                srcs[0], srcs, src_idxs, dst_idxs, s * new_ns,
                chunk=int(self.swap_chunk_rows))
            corpus_n = jax.tree.map(
                lambda a: a.reshape((s, new_ns) + a.shape[1:]), flat)
        if self.bucket_cap is None:
            cap = max(int(np.asarray(max_runs).max()), 1)
            segments._warn_coarse(type(self).__name__, cap,
                                  self.family.num_tables, int(counts.max()),
                                  shards=s)
        else:
            cap = min(int(self.bucket_cap), new_ns)
        seg = segments.ShardedSegment(
            keys=keys_n, sorted_keys=sorted_keys, perm=perm, corpus=corpus_n,
            cap=cap, counts=tuple(int(c) for c in counts))
        with tracing.span("lsh.fold.tables"):
            if self.mesh is not None:
                seg = self._place_segment(seg, shadow=True)
            return SegmentStore(
                seg, place=self._place(), base_pos=new_pos.reshape(-1),
                live_window=self.bucket_cap is not None)

    def _pre_publish(self, pending: PendingSwap) -> None:
        # The shard layout changes under the flip: a shard-local compact
        # invalidates the build-time corpus copy (non-contiguous sequence
        # ranges → corpus_cache=None), a rebalance restores the fresh-build
        # layout and installs the gathered corpus as the pristine fallback.
        self._corpus = pending.corpus_cache

    def prepare_rebalance(self) -> PendingSwap:
        """Build the globally re-partitioned replacement store OFF the
        query path (the one deliberately global program in the mutation
        plane: gather the live corpus in sequence order, re-partition into
        S contiguous shards, re-sort per shard). Blocks until the shadow
        has landed on its shards; the live store keeps serving throughout.
        """
        store = self.store
        if store.n_live == 0:
            raise ValueError("cannot rebalance an index with no live items")
        keys, corpus = store.effective_arrays()
        shadow = self._new_store(keys, corpus, shadow=True)
        jax.block_until_ready(jax.tree.leaves(shadow.view.all_arrays))
        return PendingSwap(store=shadow, kind="rebalance", source=store,
                           generation=store.generation,
                           corpus_cache=corpus if self.keep_corpus else None)

    def rebalance(self):
        """Gather the live corpus (sequence order) and re-partition it into
        S contiguous, evenly-sized shards — for when routing skew or
        shard-local compaction history leaves occupancy uneven. Restores
        the exact layout of a fresh build over the effective corpus (so
        post-rebalance queries are bit-identical to one, scores included).
        Runs as a synchronous double-buffered swap, like ``compact``.
        """
        return self.apply_swap(self.prepare_rebalance())

    # -- query --------------------------------------------------------------

    def candidates_batch(self, queries, *, probes: int = 1
                         ) -> tuple[jax.Array, jax.Array]:
        """-> (cand (B, W) effective ids with -1 fill, valid bool)."""
        view = self.store.view
        return segments.sharded_candidates(
            self.family, view.seg_arrays(0), view.delta_arrays,
            jnp.asarray(self._mults), queries, cap=view.base.cap,
            delta_caps=view.delta_caps, probes=int(probes))

    def query_batch(self, queries, topk: int = 10, *, probes: int = 1,
                    mode: str = "topk", rng=None):
        """Same contract as DeviceLSHIndex.query_batch (effective ids,
        multi-probe ``probes``, sampling ``mode``/``rng``). A sampling
        query is one global draw over the cross-shard union, so it always
        runs the single-program vmap path regardless of the mesh
        (``query_path`` describes the ``"topk"`` program)."""
        _check_mode(mode, rng)
        view = self.store.view
        args = (self.family, view.seg_arrays(0),
                view.delta_arrays, jnp.asarray(self._mults), queries)
        kwargs = dict(metric=self.metric, topk=topk, cap=view.base.cap,
                      delta_caps=view.delta_caps, probes=int(probes))
        if mode != "topk":
            return segments.sharded_sample_vmap(*args, rng, mode=mode,
                                                **kwargs)
        kwargs["probe_backend"] = self.probe_backend
        if (self.mesh is not None
                and segments.resolved_probe_backend(self.probe_backend)
                != "pallas"):
            from repro.distributed import index_sharding
            return index_sharding.shard_map_query(
                *args, mesh=self.mesh, axis=self.mesh_axis, **kwargs)
        return segments.sharded_query_vmap(*args, **kwargs)


# ---------------------------------------------------------------------------
# Host index (dict-of-buckets build kept as the membership reference)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HostLSHIndex(_LSHIndexBase):
    """Dict-of-buckets build: the bucket-membership semantics reference.

    corpus: any pytree whose leaves share a leading axis of size n —
    e.g. stacked CPTensor factors (n, d, R), stacked TT cores, or a dense
    (n, d_1, ..., d_N) array. ``candidates()`` probes the host-side Python
    dicts one query at a time (the independent reference the device tests
    pin against); ``query``/``query_batch`` serve through the same shared
    segment planner as every other deployment. Rebuild-only: streaming
    mutations live on the device/sharded indexes.
    """

    family: LSHFamily
    metric: str = "euclidean"  # or "cosine"
    seed: int = 0
    probe_backend: str = "auto"    # 'auto' | 'xla' | 'pallas'

    corpus: Any = None
    size: int = 0
    store: SegmentStore | None = None
    _tables: list[dict[int, list[int]]] | None = None
    _mults: np.ndarray | None = None

    def __post_init__(self):
        _check_metric(self.metric)
        self._mults = make_mults(self.seed, self.family.num_codes)

    @property
    def probe_path(self) -> str:
        """The resolved probe program (see DeviceLSHIndex.probe_path)."""
        return segments.resolved_probe_backend(self.probe_backend)

    # -- build --------------------------------------------------------------

    def build(self, corpus, batch_size: int = 1024) -> "HostLSHIndex":
        self.corpus = corpus
        n = jax.tree.leaves(corpus)[0].shape[0]
        self.size = n
        keys = bucket_keys(self.family, self._mults, corpus, batch_size)
        all_keys = np.asarray(keys)
        self._tables = [dict() for _ in range(self.family.num_tables)]
        for i in range(n):
            for t in range(self.family.num_tables):
                self._tables[t].setdefault(int(all_keys[i, t]), []).append(i)
        self.store = SegmentStore(build_segment(
            keys, corpus, warn_layout=type(self).__name__))
        return self

    # -- query --------------------------------------------------------------

    def candidates(self, x, probes: int = 1) -> np.ndarray:
        """Union of bucket members over the L tables, via the host dicts.

        ``probes`` = T > 1 looks up each table's T ranked candidate keys
        (``repro.core.probing``) in the same dicts — membership stays
        dict-defined, so this is the reference the device multi-probe dedup
        (distinct members across overlapping probed buckets) is pinned to.
        """
        if probes == 1:
            codes = np.asarray(_hash_one(self.family, x))[None]  # (1, L, K)
            keys = _combine_codes(codes, self._mults)[:, :, None]  # (1, L, 1)
        else:
            keys = np.asarray(probing.probe_keys(
                self.family, jnp.asarray(self._mults), tree_index(x, None),
                probes=int(probes)))                      # (1, L, T)
        cand: set[int] = set()
        for t in range(self.family.num_tables):
            for key in keys[0, t]:
                cand.update(self._tables[t].get(int(key), ()))
        return np.fromiter(cand, dtype=np.int64, count=len(cand))

    def query_batch(self, queries, topk: int = 10, *, probes: int = 1,
                    mode: str = "topk", rng=None):
        """Same contract as DeviceLSHIndex.query_batch."""
        _check_mode(mode, rng)
        view = self.store.view
        args = (self.family, view.all_arrays,
                jnp.asarray(self._mults), queries)
        if mode != "topk":
            return segments.segmented_sample(
                *args, rng, metric=self.metric, topk=topk,
                caps=view.all_caps, probes=int(probes), mode=mode)
        return segments.segmented_query(
            *args, metric=self.metric, topk=topk, caps=view.all_caps,
            probes=int(probes), probe_backend=self.probe_backend)


# ---------------------------------------------------------------------------
# References / evaluation (vectorized: one batched score matrix, one
# query_batch call — no per-query Python loop)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric",))
def _score_matrix(metric, queries, corpus):
    """(B, ...) queries x (n, ...) corpus -> (B, n) exact scores."""
    score = _score_fn(metric)
    return jax.vmap(
        lambda q: jax.vmap(lambda y: score(q, y))(corpus))(queries)


def _score_batch(metric: str, x, ys):
    return _score_matrix(metric, tree_index(x, None), ys)[0]


def brute_force_batch(metric: str, queries, corpus, topk: int = 10):
    """Exact top-k over the whole corpus for a query batch.

    -> (ids (B, topk) int64, scores (B, topk)); one batched score matrix
    instead of a per-query loop.
    """
    scores = np.asarray(_score_matrix(metric, queries, corpus))
    order = np.argsort(scores if metric == "euclidean" else -scores,
                       axis=1)[:, :topk]
    return order, np.take_along_axis(scores, order, axis=1)


def brute_force(metric: str, x, corpus, topk: int = 10):
    """Exact top-k over the whole corpus (single-query recall reference)."""
    ids, scores = brute_force_batch(metric, tree_index(x, None), corpus, topk)
    return ids[0], scores[0]


def recall_at_k(index, queries, topk: int = 10,
                probes: int = 1) -> dict[str, float]:
    """Mean recall@k of index.query_batch vs. brute force over a query batch.

    Works for every index deployment (anything with the batched
    ``query_batch`` contract plus ``metric`` / ``effective_corpus`` /
    ``size``); the ground truth is one batched score matrix over the
    effective (live) corpus. ``probes`` = T > 1 measures the multi-probe
    query path (the (L, T) trade-off ``benchmarks/index_multiprobe``
    sweeps).
    """
    corpus = index.effective_corpus()
    truth, _ = brute_force_batch(index.metric, queries, corpus, topk)
    ids, _, n_cand = index.query_batch(queries, topk=topk, probes=probes)
    ids = np.asarray(ids)
    n_q = truth.shape[0]
    hits = sum(len(set(t) & set(row[row >= 0].tolist()))
               for t, row in zip(truth.tolist(), ids))
    return {
        "recall": hits / max(n_q * topk, 1),
        "mean_candidates": float(np.asarray(n_cand).sum()) / max(n_q, 1),
        "corpus_size": index.size,
    }
