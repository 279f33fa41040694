"""Immutable sorted segments + LSM-style segment store for the LSH indexes.

This is the storage/query core every index class in ``repro.core.index``
builds on. The unit of storage is an immutable segment — per hash table the
bucket keys of its items sorted ascending, the matching permutation of local
item ids, and the corpus slice the ids point into (exactly the PR 1 device
layout, per segment instead of per index):

  ``TableSegment``   keys (m, L) uint32 in corpus order, sorted_keys (L, m),
                     perm (L, m) int32, corpus pytree with leading dim m.
  ``ShardedSegment`` the same arrays with a leading shard dim S and per-shard
                     local ids (pad slots carry the n_s sentinel), laid out
                     for a mesh axis — the PR 2 sharded base.

Mutability is layered on top, LSM-style, by ``SegmentStore``: one base
segment plus a bounded list of small delta segments (streaming inserts) and
a tombstone mask over every slot (streaming deletes). Delta segments are
``TableSegment``s on the single-device store and ``ShardedSegment`` slabs
on the sharded store — ``route_balanced`` assigns each insert batch to
shards least-loaded-first in contiguous slabs, so the mutation plane is
shard-native end-to-end and nothing is replicated. A query probes every
segment with the same searchsorted/gather path, filters tombstones inside
the probe (dead slots are masked exactly like bucket misses, so they never
reach ranking or the candidate count), re-ranks per segment, and merges the
per-segment top-k with the stable validity-aware sort from PR 2 (extended
with the effective id as a third sort key, which makes the merge
independent of how items are partitioned into segments and shards).

Ids returned by queries are *effective* ids: the rank of the item in the
live corpus in *sequence order* (the order items entered the store — base
items first, then deltas in insert order, tombstones skipped). Because
routed delta slabs interleave shards, each segment carries a host-side
``slot_pos`` map from slot to sequence position; effective ids derive from
it, so a mutated store's results stay directly comparable to a fresh
rebuild over the effective corpus, and it is the numbering ``delete()``
accepts. ``compact()`` folds the surviving keys and corpus rows (no
re-hash — keys are stored in corpus order precisely so compaction never
touches the hash families) into a new base; the sharded fold is
shard-local (``_slab_gather_sort``), so shards keep whatever mix of items
they held and only an explicit ``rebalance()`` moves items across shards.

Indexes built with an explicit ``bucket_cap`` keep per-segment live-window
lookups (``live_rank``/``live_pos``): a truncated probe window skips
tombstoned slots and gathers the first ``cap`` *live* members of each
bucket, so heavy deletes no longer silently shrink capped candidate sets
until compaction.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import contractions, probing
# The universal bucket hash lives with the families (lsh.hash_keys fuses it
# into the hashing program); re-exported here for the host/table builders.
from repro.core.lsh import _combine_codes, make_mults
# The probe epilogue (bucket windows, dedup, packed top-k selection) is
# shared with the fused Pallas query kernel — one implementation, so the
# xla and pallas probe backends are bit-identical by construction.
from repro.kernels import epilogues as _epi

_PAD_KEY = np.uint32(0xFFFFFFFF)  # bucket key of shard-padding slots
_NO_ID = np.int32(0x7FFFFFFF)     # effective-id sentinel of probe misses
                                  # (sorts after every real effective id)

PROBE_BACKENDS = ("auto", "xla", "pallas")


def resolved_probe_backend(probe_backend: str = "auto") -> str:
    """'xla' or 'pallas': the explicit knob, else the REPRO_PROBE_BACKEND
    env var (read at trace time), else xla on every platform — mirroring
    ``LSHFamily.resolved_backend`` for the hashing stage.

    'xla' is the restructured segment-major schedule (one fused scan over
    segments, hoisted-norm re-rank, packed top-k selection); 'pallas' the
    fused query kernel in ``repro.kernels.fused_query`` (interpret mode off
    TPU). Both are bit-identical to the reference planner
    (``segmented_query_reference``), pinned by tests/test_fused_probe.py.

    ``auto`` does not pick pallas on TPU: the v5e compiler refuses the
    fused query kernel (a uint32 reduction in the key combine, then
    ``searchsorted``/``argsort``/a two-operand ``lax.sort`` in the body),
    and its whole-segment VMEM blocks exceed a core's VMEM at deployment
    sizes. An explicit 'pallas' still runs the kernel and its compiler
    error reaches the caller.
    """
    b = (probe_backend or "auto").strip().lower()
    if b == "auto":
        b = os.environ.get("REPRO_PROBE_BACKEND", "").strip().lower() or "auto"
    if b == "auto":
        b = "xla"
    if b not in ("xla", "pallas"):
        raise ValueError(
            f"probe_backend must be one of {PROBE_BACKENDS}, got "
            f"{probe_backend!r}")
    return b


def tree_index(tree, idx):
    return jax.tree.map(lambda a: a[idx], tree)


def _score_fn(metric: str):
    return (contractions.distance if metric == "euclidean"
            else contractions.cosine_similarity)


def _bad_score(metric: str) -> float:
    return jnp.inf if metric == "euclidean" else -jnp.inf


@jax.jit
def _hash_keys(family, xs, mults):
    """One fused program: batched projection -> discretize -> combine."""
    return family.hash_keys(xs, mults)


def bucket_keys(family, mults, corpus, batch_size: int) -> jax.Array:
    """(n, L) uint32 bucket keys of a corpus pytree, hashed in batches.

    The single source of build-time keys for every segment kind — host dict
    tables are filled from np.asarray of this, keeping host/device keys
    bit-identical. Each batch runs as ONE fused jit program through
    ``family.hash_keys`` (projection, discretize, and the uint32 radix
    combine never round-trip through separate dispatches).
    """
    n = jax.tree.leaves(corpus)[0].shape[0]
    mults = jnp.asarray(mults)
    keys = []
    for start in range(0, n, batch_size):
        chunk = tree_index(corpus, slice(start, min(start + batch_size, n)))
        keys.append(_hash_keys(family, chunk, mults))
    return jnp.concatenate(keys, axis=0)


def query_keys(family, mults, queries, probes: int = 1) -> jax.Array:
    """Hash a query batch once -> (L, B) uint32 bucket keys (fused).

    With ``probes`` = T > 1 the multi-probe expansion of
    ``repro.core.probing`` widens each (query, table) cell to its T ranked
    candidate bucket keys -> (L, T, B); slot 0 along T is the base key,
    bit-identical to the single-probe tensor.
    """
    with jax.named_scope("hash"):
        if probes == 1:
            return family.hash_keys(queries, jnp.asarray(mults)).T
        keys = probing.probe_keys(family, mults, queries, probes=probes)
        return jnp.moveaxis(keys, 0, -1)                  # (B,L,T) -> (L,T,B)


def _max_run_length(sorted_keys: jax.Array) -> jax.Array:
    """Longest run of equal values along the last axis of sorted keys."""
    return _max_run_length_masked(sorted_keys,
                                  jnp.ones(sorted_keys.shape, bool))


def _max_run_length_masked(sorted_keys: jax.Array,
                           valid: jax.Array) -> jax.Array:
    """Longest run of equal values along the last axis, counting only
    ``valid`` positions (runs break at invalid slots). Pad slots sort to
    the tail of their key run (stable sort, pads carry the largest local
    ids), so masking them yields the true largest *stored* bucket."""
    flat = sorted_keys.reshape(-1, sorted_keys.shape[-1])
    v = valid.reshape(flat.shape)
    n = flat.shape[1]
    if n == 0:
        return jnp.int32(0)
    idx = jnp.arange(n, dtype=jnp.int32)
    new_run = jnp.concatenate(
        [jnp.ones(flat.shape[:1] + (1,), bool),
         (flat[:, 1:] != flat[:, :-1]) | ~v[:, :-1]], axis=1)
    run_start = jax.lax.cummax(jnp.where(new_run, idx, 0), axis=1)
    return jnp.max(jnp.where(v, idx - run_start + 1, 0))


# ---------------------------------------------------------------------------
# Immutable segments
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class TableSegment:
    """One immutable sorted run: per-table sorted keys + permutation + the
    corpus slice. ``keys`` keeps the corpus-order copy so compaction can
    rebuild sorted tables without re-hashing."""

    keys: jax.Array         # (m, L) uint32, corpus order
    sorted_keys: jax.Array  # (L, m) uint32, ascending per table
    perm: jax.Array         # (L, m) int32 local ids in sorted-key order
    corpus: Any             # pytree, leaves (m, ...)
    cap: int                # static probe width (largest bucket at build,
                            # or the explicit bucket_cap truncation)

    @property
    def slots(self) -> int:
        return self.keys.shape[0]

    @property
    def items(self) -> int:       # every slot holds a real item
        return self.keys.shape[0]


@dataclasses.dataclass(frozen=True)
class ShardedSegment:
    """Sharded arrays with a leading shard dim: the sharded *base* and the
    routed delta *slabs* share this layout.

    Each shard holds ``counts[s]`` real items in slots ``[0, counts[s])`` of
    its slab; the remaining slots are padding (pad keys = _PAD_KEY, pad perm
    entries = the ``shard_size`` sentinel, so a probe landing on one — even
    via a _PAD_KEY collision — is masked as a miss by the liveness lookup).
    A fresh contiguous build fills every shard but the last; slab deltas and
    shard-locally compacted bases carry arbitrary per-shard counts.
    """

    keys: jax.Array         # (S, n_s, L) uint32, corpus order, pads _PAD_KEY
    sorted_keys: jax.Array  # (S, L, n_s) uint32
    perm: jax.Array         # (S, L, n_s) int32, pad slots -> n_s sentinel
    corpus: Any             # pytree, leaves (S, n_s, ...), zero-padded
    cap: int                # static probe width (largest per-shard bucket)
    counts: tuple[int, ...]  # real item count per shard

    @property
    def items(self) -> int:   # real (unpadded) item count n
        return sum(self.counts)

    @property
    def shards(self) -> int:
        return self.keys.shape[0]

    @property
    def shard_size(self) -> int:
        return self.keys.shape[1]

    @property
    def slots(self) -> int:
        return self.keys.shape[0] * self.keys.shape[1]


@jax.jit
def _sort_tables(keys_t: jax.Array):
    """(..., L, m) keys -> (perm, sorted_keys, max_run) along the last axis."""
    perm = jnp.argsort(keys_t, axis=-1, stable=True).astype(jnp.int32)
    sorted_keys = jnp.take_along_axis(keys_t, perm, axis=-1)
    return perm, sorted_keys, _max_run_length(sorted_keys)


def _warn_coarse(layout: str, cap: int, num_tables: int, n: int,
                 shards: int = 1) -> None:
    """Shared coarse-family warning: the exact default cap would gather more
    candidates than the store — for sharded bases, one shard — holds.
    Emitted from the shared segment-build path so every layout (device,
    sharded, host) warns identically; ``n`` is the per-shard item count
    when ``shards`` > 1."""
    if not n or cap * num_tables <= n:
        return
    fix = ("The family is too coarse for this data; raise num_codes / "
           "shrink bucket_width, or pass an explicit bucket_cap to bound "
           "{} work at some recall cost.")
    if shards > 1:
        warnings.warn(
            f"{layout}: largest per-shard bucket has {cap} of {n} items, so "
            f"the exact default cap gathers up to S*L*cap="
            f"{shards * num_tables * cap} candidates per query (more than a "
            "shard holds). " + fix.format("per-shard"))
    else:
        warnings.warn(
            f"{layout}: largest bucket has {cap} of {n} items, so the exact "
            f"default cap gathers up to L*cap={cap * num_tables} candidates "
            "per query (more than the corpus). " + fix.format("per-query"))


def build_segment(keys: jax.Array, corpus, *, bucket_cap: int | None = None,
                  warn_layout: str | None = None,
                  sort_throttled: bool = False) -> TableSegment:
    """(m, L) corpus-order keys + corpus slice -> sorted TableSegment.

    One jit program sorts every table and measures the largest bucket; the
    coarse-family warning fires only for base builds (``warn_layout`` set) —
    small delta segments trip the threshold by construction.
    ``sort_throttled`` sorts table-by-table instead (identical values) so
    a shadow build's sort stays off a concurrent query's critical path.
    """
    m = keys.shape[0]
    sorter = _sort_tables_throttled if sort_throttled else _sort_tables
    perm, sorted_keys, max_run = sorter(keys.T)
    if bucket_cap is None:
        cap = int(max_run) if m else 0
        if warn_layout is not None:
            _warn_coarse(warn_layout, cap, keys.shape[1], m)
    else:
        cap = min(int(bucket_cap), m)
    return TableSegment(keys=keys, sorted_keys=sorted_keys, perm=perm,
                        corpus=corpus, cap=cap)


def build_sharded_segment(keys: jax.Array, corpus, shards: int, *,
                          bucket_cap: int | None = None,
                          warn_layout: str | None = None) -> ShardedSegment:
    """(n, L) corpus-order keys + corpus -> S-sharded segment (unplaced).

    The corpus is split into S contiguous slices; the last is zero-padded
    (pad keys = _PAD_KEY, pad perm entries = the n_s sentinel). Mesh
    placement is the caller's concern (``distributed.index_sharding``).
    """
    n, num_tables = keys.shape
    n_s = max(-(-n // shards), 1)
    pad = shards * n_s - n
    keys_sh = jnp.pad(keys, ((0, pad), (0, 0)), constant_values=_PAD_KEY)
    keys_sh = keys_sh.reshape(shards, n_s, num_tables)
    perm, sorted_keys, max_run = _sort_tables(keys_sh.transpose(0, 2, 1))
    # pad slots get the n_s sentinel: liveness lookup masks them as misses
    offsets = jnp.arange(shards, dtype=jnp.int32)[:, None, None] * n_s
    perm = jnp.where(offsets + perm >= n, n_s, perm)
    corpus_sh = jax.tree.map(
        lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        .reshape((shards, n_s) + a.shape[1:]), corpus)
    if bucket_cap is None:
        cap = int(max_run) if n else 0
        if warn_layout is not None:
            _warn_coarse(warn_layout, cap, num_tables, n_s, shards)
    else:
        cap = min(int(bucket_cap), n_s)
    counts = tuple(int(np.clip(n - s * n_s, 0, n_s)) for s in range(shards))
    return ShardedSegment(keys=keys_sh, sorted_keys=sorted_keys, perm=perm,
                          corpus=corpus_sh, cap=cap, counts=counts)


# ---------------------------------------------------------------------------
# Routed delta slabs + shard-local fold (the shard-native mutation plane)
# ---------------------------------------------------------------------------


def route_balanced(batch_n: int, loads) -> tuple[np.ndarray, np.ndarray]:
    """Deterministic balance policy: fill the least-loaded shard first.

    -> (alloc (S,), offsets (S,)) int64, shard-id order: shard ``s`` takes
    the contiguous batch slab ``[offsets[s], offsets[s] + alloc[s])``.
    Water-fill over ascending (load, shard id): the lowest shards are
    raised toward a common level, leftovers go one item each to the
    least-loaded shards — so steady-state ingest keeps shard occupancy
    within one item of even without ever moving stored rows.
    """
    loads = np.asarray(loads, np.int64)
    s = loads.size
    order = np.lexsort((np.arange(s), loads))
    lv = loads[order]
    alloc_sorted = np.zeros(s, np.int64)
    b = int(batch_n)
    if b > 0:
        for k in range(1, s + 1):
            room = int((lv[k] - lv[:k]).sum()) if k < s else b
            if room >= b:
                level, extra = divmod(int(lv[:k].sum()) + b, k)
                tgt = np.full(k, level, np.int64)
                tgt[:extra] += 1
                alloc_sorted[:k] = tgt - lv[:k]
                break
    alloc = np.zeros(s, np.int64)
    alloc[order] = alloc_sorted
    offsets = np.zeros(s, np.int64)
    offsets[order] = np.concatenate(([0], np.cumsum(alloc_sorted)[:-1]))
    return alloc, offsets


@functools.partial(jax.jit, static_argnames=("shards", "shard_size"))
def _slab_scatter_sort(keys, corpus, idx, counts, *, shards, shard_size):
    """Scatter a routed batch into per-shard slabs and sort each locally.

    ``keys`` (B, L) corpus-order bucket keys; ``idx`` (S * shard_size,)
    int32 rows into the batch (row B = pad); ``counts`` (S,) int32 real
    rows per shard. One program: pad-row gather -> per-shard stable sort
    -> pad sentinel -> masked max bucket run. The device half of
    ``build_sharded_delta`` — also the ``insert_program`` the dry run
    AOT-profiles.
    """
    b, num_tables = keys.shape
    keys_pad = jnp.concatenate(
        [keys, jnp.full((1, num_tables), _PAD_KEY, jnp.uint32)])
    keys_sh = keys_pad[idx].reshape(shards, shard_size, num_tables)
    corpus_sh = jax.tree.map(
        lambda a: jnp.concatenate([a, jnp.zeros_like(a[:1])])[idx]
        .reshape((shards, shard_size) + a.shape[1:]), corpus)
    perm, sorted_keys, _ = _sort_tables(keys_sh.transpose(0, 2, 1))
    pad = perm >= counts[:, None, None]
    perm = jnp.where(pad, shard_size, perm)
    # per-shard max runs (host takes the max): keeps the program free of
    # even the scalar cross-shard reduce a global max would schedule
    max_runs = jax.vmap(_max_run_length_masked)(sorted_keys, ~pad)
    return keys_sh, sorted_keys, perm, corpus_sh, max_runs


def build_sharded_delta(keys, corpus, alloc, offsets, *, seq0: int,
                        bucket_cap: int | None = None
                        ) -> tuple[ShardedSegment, np.ndarray]:
    """(B, L) batch keys + batch corpus + a ``route_balanced`` plan ->
    (slab ShardedSegment, positions).

    ``positions`` is the (S * slab,) int64 slot -> sequence-position map
    (``seq0 + batch row``, -1 for pad slots) ``SegmentStore.append_delta``
    consumes; offsets are closed-form, so the bookkeeping never inspects
    the routed arrays. The slab width is the largest per-shard allocation
    rounded up to a coarse grid (8, then 64 past 256 slots): routing
    drifts the raw width by a few items between batches, and ``shard_size``
    is a static program shape — quantizing it keeps steady-state ingest on
    one compiled scatter+sort program instead of recompiling every batch.
    """
    b, _ = keys.shape
    s = alloc.size
    raw = max(int(alloc.max()), 1)
    q = 64 if raw >= 256 else 8
    slab = -(-raw // q) * q
    idx = np.full((s, slab), b, np.int64)
    pos = np.full((s, slab), -1, np.int64)
    for sh in range(s):
        c, o = int(alloc[sh]), int(offsets[sh])
        idx[sh, :c] = o + np.arange(c)
        pos[sh, :c] = seq0 + o + np.arange(c)
    keys_sh, sorted_keys, perm, corpus_sh, max_runs = _slab_scatter_sort(
        keys, corpus, jnp.asarray(idx.reshape(-1), jnp.int32),
        jnp.asarray(alloc, jnp.int32), shards=s, shard_size=slab)
    cap = min(int(bucket_cap), slab) if bucket_cap is not None \
        else max(int(np.asarray(max_runs).max()), 1)
    seg = ShardedSegment(keys=keys_sh, sorted_keys=sorted_keys, perm=perm,
                         corpus=corpus_sh, cap=cap,
                         counts=tuple(int(a) for a in alloc))
    return seg, pos.reshape(-1)


@functools.partial(jax.jit, static_argnames=("shard_size",))
def _slab_gather_sort(keys_cat, corpus_cat, idx, counts, *, shard_size):
    """Shard-local compaction fold: each shard gathers its own survivors
    from the concatenated base + delta slabs and re-sorts locally.

    ``keys_cat`` (S, W, L) / ``corpus_cat`` leaves (S, W, ...) are the
    per-shard slot axes of every segment concatenated (W = sum of slab
    widths); ``idx`` (S, shard_size) indexes into W (W = pad), ``counts``
    (S,) real survivors per shard. Every op is elementwise or a gather
    along the non-sharded slot axis, so under the mesh the program stays
    shard-local — no collective, no global gather. Also the
    ``compact_program`` the dry run AOT-profiles.
    """
    s, w, num_tables = keys_cat.shape
    keys_pad = jnp.concatenate(
        [keys_cat, jnp.full((s, 1, num_tables), _PAD_KEY, jnp.uint32)],
        axis=1)
    keys_n = jnp.take_along_axis(keys_pad, idx[:, :, None], axis=1)
    corpus_n = jax.tree.map(
        lambda a: jnp.take_along_axis(
            jnp.concatenate([a, jnp.zeros_like(a[:, :1])], axis=1),
            idx.reshape((s, shard_size) + (1,) * (a.ndim - 2)), axis=1),
        corpus_cat)
    perm, sorted_keys, _ = _sort_tables(keys_n.transpose(0, 2, 1))
    pad = perm >= counts[:, None, None]
    perm = jnp.where(pad, shard_size, perm)
    max_runs = jax.vmap(_max_run_length_masked)(sorted_keys, ~pad)
    return keys_n, sorted_keys, perm, corpus_n, max_runs


@jax.jit
def _slab_gather_keys(keys_cat, idx):
    """The keys half of ``_slab_gather_sort``'s gather (pad rows get
    ``_PAD_KEY``), kept as its own bounded program for the chunked shadow
    build: bucket keys are a few bytes per item, so this program stays
    small regardless of corpus width. -> (S, shard_size, L) keys."""
    s, w, num_tables = keys_cat.shape
    keys_pad = jnp.concatenate(
        [keys_cat, jnp.full((s, 1, num_tables), _PAD_KEY, jnp.uint32)],
        axis=1)
    return jnp.take_along_axis(keys_pad, idx[:, :, None], axis=1)


@functools.partial(jax.jit, static_argnames=("shard_size",))
def _sort_shard_table(keys_l, counts, *, shard_size):
    """Sort ONE table's (S, shard_size) fold keys — the same stable sort,
    pad sentinel, and masked max-run math ``_slab_gather_sort`` applies to
    all tables at once, so per-table outputs are bit-identical slices of
    the monolithic fold's. The chunked shadow build issues L of these
    (blocking between them) instead of one L-times-larger sort program."""
    perm = jnp.argsort(keys_l, axis=-1, stable=True).astype(jnp.int32)
    sorted_keys = jnp.take_along_axis(keys_l, perm, axis=-1)
    pad = perm >= counts[:, None]
    perm = jnp.where(pad, shard_size, perm)
    max_run = _max_run_length_masked(sorted_keys, ~pad)
    return perm, sorted_keys, max_run


_BUILD_YIELD_S = 0.0
_BUILD_BUSY_FN: Callable[[], bool] | None = None


@contextlib.contextmanager
def cooperative_build(yield_s: float = 0.008, busy=None):
    """Make the throttled build loops sleep ``yield_s`` after each bounded
    program while the block is active (and, with ``busy``, only while
    foreground work actually exists).

    Blocking per program keeps the *device* queue one program deep, but on
    a machine with few cores the build thread usually keeps the CPU after
    ``block_until_ready`` returns and enqueues its next program before a
    waiting query-lane thread ever runs — so a query still convoys behind
    several build programs in a row, and even once it runs, its program
    timeshares the core with the build's back-to-back programs at ~half
    speed. The sleep hands the core (and the GIL) over between programs,
    leaving a concurrent query the majority of the core for the duration
    of the build (measured on one core: compacting-phase p99 within
    ~1.4x of quiet vs ~2x with back-to-back programs). Build wall time is
    off the query path by design, so trading it for query latency is the
    right direction — but only when there is a query to trade for:
    ``busy`` (a nullary predicate, e.g. "any query in flight") gates each
    sleep so an unloaded build still runs at full speed instead of
    stretching its own wall — and with it the interference window the
    next query can land in — by a blanket slowdown.

    The flags are process-global on purpose: they are set by background
    mutation executors (the scheduler's ingest lane) around whole
    operations, and the loops they gate run several layers down the store
    build with no parameter path through ``SegmentStore.__init__``."""
    global _BUILD_YIELD_S, _BUILD_BUSY_FN
    prev = (_BUILD_YIELD_S, _BUILD_BUSY_FN)
    _BUILD_YIELD_S, _BUILD_BUSY_FN = yield_s, busy
    try:
        yield
    finally:
        _BUILD_YIELD_S, _BUILD_BUSY_FN = prev


def _yield_slot() -> None:
    """One cooperative-yield point between bounded build programs (no-op
    unless inside :func:`cooperative_build`, or when its ``busy``
    predicate says no foreground work is waiting)."""
    if _BUILD_YIELD_S > 0.0 and (_BUILD_BUSY_FN is None or _BUILD_BUSY_FN()):
        with tracing.span("lsh.yield"):
            time.sleep(_BUILD_YIELD_S)


def _sort_tables_throttled(keys_t: jax.Array):
    """``_sort_tables`` issued as one bounded program per table, blocking
    between programs — identical values (tables sort independently). The
    chunked shadow build uses it so the fold's sort never queues one
    all-tables program ahead of a concurrently dispatched query (span
    ``lsh.fold.sort``)."""
    outs = []
    with tracing.span("lsh.fold.sort"):
        for table in range(keys_t.shape[-2]):
            out = _sort_tables(keys_t[..., table:table + 1, :])
            jax.block_until_ready(out)
            _yield_slot()
            outs.append(out)
        perm = jnp.concatenate([o[0] for o in outs], axis=-2)
        sorted_keys = jnp.concatenate([o[1] for o in outs], axis=-2)
        return perm, sorted_keys, jnp.max(jnp.stack([o[2] for o in outs]))


def _row_flat(shape: tuple[int, ...]) -> tuple[int, ...]:
    """The shape the chunked copy writes a leaf of ``shape`` in: (rows,
    prod(rest)) for ndim > 2, else ``shape`` itself."""
    if len(shape) <= 2:
        return tuple(shape)
    return (shape[0], int(np.prod(shape[1:])))


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows_chunk(buf, src, src_idx, dst_idx):
    """One bounded program of the chunked shadow-build copy: gather
    ``src_idx`` rows from one source segment in its own layout, flatten
    them to the destination's row width, and scatter them into the donated
    row-flat destination buffers in place (``dst_idx`` past the end marks
    chunk padding and is dropped).

    The destination is row-flat (``_row_flat``) because a TPU keeps the row
    axis of an (n, 8, 16) buffer minor, while the scatter writes whole rows:
    against a 3-D destination the compiler copies the whole donated buffer
    into the scatter's layout and back in every chunk, so each chunk costs
    O(buffer). An (n, 128) destination is scattered in place, and donation
    keeps the update O(chunk): the runtime aliases the output onto the
    input allocation."""
    return jax.tree.map(
        lambda b, s: b.at[dst_idx].set(
            s[src_idx].reshape(src_idx.shape + b.shape[1:]), mode="drop"),
        buf, src)


def gather_rows_chunked(template, srcs, src_idxs, dst_idxs, out_rows, *,
                        chunk: int = 4096):
    """Assemble ``out_rows`` live corpus rows into fresh zero-initialized
    buffers via bounded per-chunk gather+scatter programs.

    The monolithic folds (``_slab_gather_sort``, ``effective_arrays``)
    move the whole store through one XLA program; a device executes
    programs in order, so on a single-stream backend every concurrently
    dispatched query waits the full store copy out — the exact serving
    stall ``prepare_compact`` exists to avoid. This path issues the same
    copy as ceil(rows/chunk) programs per source segment instead, each
    touching at most ``chunk`` rows, and blocks on every chunk before
    enqueuing the next — dispatch is async, so without the throttle the
    fold floods the device queue in one burst and a concurrent query
    waits behind all of it anyway. With it the queue stays one chunk deep
    and query programs interleave between chunks. Values are identical to
    the monolithic gather: every live row is written exactly once and
    unwritten rows stay zero, matching the pad-row zeros of the
    one-program path.

    The chunks write row-flat buffers (``_scatter_rows_chunk`` says why);
    after the last chunk one program per leaf of ndim > 2 re-shapes its
    buffer to ``(out_rows,) + leaf.shape[1:]``, one copy of the leaf.

    ``srcs`` are per-segment corpus pytrees with a flat leading axis;
    ``src_idxs``/``dst_idxs`` the matching host-side row maps into them
    and into the flat output. ``template`` supplies output leaf shapes.
    The whole copy is one ``lsh.fold.gather`` span; ``chunks=`` counts its
    chunk programs.
    """
    chunks = sum(-(-len(s_idx) // chunk) for s_idx in src_idxs)
    with tracing.span("lsh.fold.gather", rows=int(out_rows), chunks=chunks):
        buf = jax.tree.map(
            lambda a: jnp.zeros(_row_flat((out_rows,) + a.shape[1:]),
                                a.dtype), template)
        for src, s_idx, d_idx in zip(srcs, src_idxs, dst_idxs):
            for c0 in range(0, len(s_idx), chunk):
                s_c = np.asarray(s_idx[c0:c0 + chunk], np.int32)
                d_c = np.asarray(d_idx[c0:c0 + chunk], np.int32)
                if s_c.size < chunk:    # pad to the compiled chunk shape;
                    fill = chunk - s_c.size  # dst sentinel rows are dropped
                    s_c = np.pad(s_c, (0, fill))
                    d_c = np.pad(d_c, (0, fill), constant_values=out_rows)
                buf = _scatter_rows_chunk(buf, src, jnp.asarray(s_c),
                                          jnp.asarray(d_c))
                jax.block_until_ready(jax.tree.leaves(buf))
                _yield_slot()
        out = jax.tree.map(
            lambda b, a: b.reshape((out_rows,) + a.shape[1:]), buf, template)
        jax.block_until_ready(jax.tree.leaves(out))
        return out


# ---------------------------------------------------------------------------
# Probe / rank / merge — the shared query math
# ---------------------------------------------------------------------------


# The query program's stages, each a ``jax.named_scope`` in the functions
# below and in ``query_keys``, so every program built from them
# (``segmented_query``, ``sharded_query_vmap``, the ``shard_map`` body)
# names its HLO ops by stage in their ``op_name`` metadata, which a
# profiler trace carries. Scopes exist only at trace time: they change no
# value and no runtime work.
QUERY_STAGES = ("hash", "probe", "norms", "rerank", "select")


def _probe_windows(sorted_keys, perm, keys, cap, live, win=None):
    """Raw probe windows, pre-dedup -> (ids (B, W) local ids, hit (B, W)).

    ``keys`` is (L, B) single-probe or (L, T, B) multi-probe; every op
    broadcasts over the optional probe axis, which is then folded into the
    flattened window axis W = L[*T]*cap (query-major, table-major, probe-
    major, window-minor — the exact (L, B) flattening order extended by T).
    One (query, table, probe, window-slot) cell per output column: the same
    local id recurs once per probed bucket that holds it, which is what the
    weighted sampling mode counts; ``probe_tables`` sorts + masks the
    recurrences away for the top-k path. ``hit`` is True only for in-range
    slots of the probed bucket whose slot is live (``live`` is the (m+1,)
    lookup — entry m covers the sharded pad sentinel, tombstoned slots are
    False — so dead slots are filtered exactly like bucket misses).

    ``win`` (stores built with an explicit ``bucket_cap``) is the
    (live_rank (L, m+1), live_pos (L, m)) live-window lookup: the probe
    then gathers the first ``cap`` *live* positions of the bucket instead
    of the first ``cap`` positions, so tombstoned slots stop consuming
    truncation-window space (a dense window silently drops live bucket
    members past ``cap`` dead ones until compaction). The live-window
    bound is hoisted to one rank compare per (query, table, probe) — see
    ``repro.kernels.epilogues.probe_windows``, where the implementation
    lives (shared with the fused Pallas query kernel).
    """
    return _epi.probe_windows(sorted_keys, perm, keys, cap, live, win)


def probe_tables(sorted_keys, perm, keys, cap, live, win=None):
    """-> (cand (B, W) int32 with -1 for invalid, valid (B, W) bool),
    W = L[*T]*cap.

    keys: (L, B) uint32 query bucket keys (already hashed + combined), or
    (L, T, B) ranked multi-probe keys. For each query and table (and probe):
    searchsorted into the sorted key array, gather the next ``cap``
    positions, keep those still inside the bucket (same key) whose slot is
    live, then sort + mask duplicates so each local id appears at most
    once — including across the T probed buckets of one table, whose
    windows overlap whenever probes collide (padded expansions repeat the
    base key), so ``n_cand`` counts distinct members at any T.
    """
    m = sorted_keys.shape[1]
    with jax.named_scope("probe"):
        ids, hit = _probe_windows(sorted_keys, perm, keys, cap, live, win)
        cand, valid = _epi.dedup_windows(ids, hit, m)
        return jnp.where(valid, cand, -1).astype(jnp.int32), valid


def select_topk(metric, topk, cand, scores, valid):
    """Stable three-key sort -> (ids (B, topk) with -1 fill, scores (B, topk)).

    Primary key: validity (invalid slots strictly last, independent of their
    score values); secondary key: the score in rank order (ascending distance
    / descending similarity, NaN after every finite score — XLA's total
    order, matching np.argsort in the host path); tertiary key: the
    candidate id itself, so score ties resolve to the ascending id
    *regardless of candidate position*. Single-table probes present
    candidates in ascending-id order, where the id key reproduces the old
    stable positional tie-break bit-for-bit; merges over shards and routed
    delta slabs present them in partition order, where the explicit key is
    what keeps selection independent of how items are laid out — the
    invariant behind mutated-vs-fresh parity for any shard routing.
    """
    with jax.named_scope("select"):
        order_key = scores if metric == "euclidean" else -scores
        _, _, s_cand, s_scores, s_valid = jax.lax.sort(
            (~valid, order_key, cand, scores, valid),
            dimension=1, is_stable=True, num_keys=3)
        k = min(topk, cand.shape[1])
        bad = _bad_score(metric)
        ids = jnp.where(s_valid[:, :k], s_cand[:, :k], -1)
        out_scores = jnp.where(s_valid[:, :k], s_scores[:, :k], bad)
        if k < topk:
            ids = jnp.pad(ids, ((0, 0), (0, topk - k)), constant_values=-1)
            out_scores = jnp.pad(out_scores, ((0, 0), (0, topk - k)),
                                 constant_values=bad)
        return ids, out_scores


def rank_candidates(metric, topk, queries, corpus, cand, valid):
    """(cand, valid) (B, W) -> (ids (B, topk), scores (B, topk), n_cand (B,)).

    Exact in-format re-rank of every valid candidate followed by the
    validity-aware top-k selection. Rows with no valid candidate come out
    all -1 / bad-fill even when scores are NaN or +/-inf (e.g. a zero-norm
    query under cosine) — selection never trusts score sentinels alone.
    """
    n_cand = valid.sum(axis=1, dtype=jnp.int32)
    safe = jnp.where(valid, cand, 0)
    sub = tree_index(corpus, safe)                        # leaves (B, C, ...)
    score = _score_fn(metric)
    scores = jax.vmap(
        lambda q, ys: jax.vmap(lambda y: score(q, y))(ys))(queries, sub)
    scores = jnp.where(valid, scores, _bad_score(metric))
    ids, out_scores = select_topk(metric, topk, cand, scores, valid)
    return ids, out_scores, n_cand


def segment_candidates(seg_arrays, keys, cap):
    """One segment's probe -> (cand (B, L*cap) effective ids with -1 fill,
    valid (B, L*cap) bool). ``seg_arrays`` is the (corpus, sorted_keys,
    perm, live, eff, win) tuple; local ids are mapped through ``eff`` into
    the store's effective (live-corpus) numbering."""
    _, sorted_keys, perm, live, eff, win = seg_arrays
    cand, valid = probe_tables(sorted_keys, perm, keys, cap, live, win)
    safe = jnp.where(valid, cand, 0)
    return jnp.where(valid, eff[safe], -1), valid


def segment_topk(metric, topk, cap, queries, seg_arrays, keys):
    """One segment's probe + re-rank -> ((B, topk) effective ids, scores,
    n_cand). ``seg_arrays`` is the (corpus, sorted_keys, perm, live, eff,
    win) tuple; candidates come back already mapped through ``eff`` into
    the store's effective (live-corpus) numbering, -1 fill preserved."""
    corpus, sorted_keys, perm, live, eff, win = seg_arrays
    cand, valid = probe_tables(sorted_keys, perm, keys, cap, live, win)
    ids, scores, n_cand = rank_candidates(metric, topk, queries, corpus,
                                          cand, valid)
    return jnp.where(ids >= 0, eff[jnp.where(ids >= 0, ids, 0)], -1), \
        scores, n_cand


def merge_topk(metric, topk, ids, scores, n_cand):
    """(G, B, k) per-group top-k -> global (ids, scores, n_cand).

    Group-major concatenation + the same stable validity-aware selection as
    the single-table path. The effective id rides along as the third sort
    key, so score ties resolve identically however items are partitioned
    into groups — shards, delta slabs, or both — and the merged top-k is
    bit-identical to ranking all candidates in one table.
    """
    g, b, k = ids.shape
    flat_ids = ids.transpose(1, 0, 2).reshape(b, g * k)
    flat_scores = scores.transpose(1, 0, 2).reshape(b, g * k)
    out_ids, out_scores = select_topk(metric, topk, flat_ids, flat_scores,
                                      flat_ids >= 0)
    return out_ids, out_scores, n_cand.sum(axis=0)


def shard_topk_with_deltas(metric, topk, cap, delta_caps, queries, base_s,
                           deltas_s, keys):
    """One shard's merged top-k over its base slice + its delta slabs.

    ``base_s`` / each element of ``deltas_s`` is a per-shard (corpus,
    sorted_keys, perm, live, eff, win) tuple (no leading shard dim). The
    single body shared verbatim by the vmapped and the shard_map sharded
    query programs, which must stay bit-identical; the per-shard top-k
    covers base + deltas together, so the only cross-shard stage left is
    the final S-way merge."""
    outs = [segment_topk(metric, topk, cap, queries, base_s, keys)]
    for seg_arrays, dcap in zip(deltas_s, delta_caps):
        outs.append(segment_topk(metric, topk, dcap, queries, seg_arrays,
                                 keys))
    if len(outs) == 1:
        return outs[0]
    return merge_topk(metric, topk,
                      jnp.stack([o[0] for o in outs]),
                      jnp.stack([o[1] for o in outs]),
                      jnp.stack([o[2] for o in outs]))


# ---------------------------------------------------------------------------
# The fused probe schedule (probe_backend='xla'): one segment-major scan,
# hoisted-norm re-rank, one packed top-k over every segment's candidates
# ---------------------------------------------------------------------------


# Items per step of the per-item self-inner sweep. On TPU the sweep
# relayouts each item's cores so that a TT core's 12-wide mode dim pads to
# 128 lanes (10.7x); over 1M TT items at once that is 17.2 GB of
# temporaries, more than a v5e's HBM. Chunks of this many items keep it
# near 1 GB.
SELF_INNER_CHUNK = 1 << 16


def self_inners(corpus, chunk: int = SELF_INNER_CHUNK) -> jax.Array:
    """(m,) per-item <Y, Y>, ``chunk`` items at a time.

    Each chunk runs the same identity-gather-into-nested-vmap structure as
    one sweep over the whole segment (see ``hoisted_scores``), so the
    values are bit-equal to it; the tail chunk re-reads the last item and
    drops the extras."""
    inner = contractions.inner
    m = jax.tree.leaves(corpus)[0].shape[0]

    def sweep(idx):
        rows = tree_index(corpus, idx[None])              # leaves (1, c, ...)
        return jax.vmap(
            lambda ys: jax.vmap(lambda y: inner(y, y))(ys))(rows)[0]

    with jax.named_scope("norms"):
        if m <= chunk:
            return sweep(jnp.arange(m))
        starts = jnp.arange(-(-m // chunk)) * chunk
        out = jax.lax.map(
            lambda s: sweep(jnp.minimum(s + jnp.arange(chunk), m - 1)),
            starts)
        return out.reshape(-1)[:m]


def hoisted_scores(metric, queries, corpus, safe):
    """Exact re-rank scores of gathered candidates, hoisted-norm schedule.

    ``safe`` is the (B, W) clamped candidate matrix. Instead of evaluating
    the three-contraction score on every materialized (B, W) candidate pair
    (``rank_candidates``' schedule — the corpus self-inner <Y, Y> is
    recomputed per (query, candidate) cell), the per-item self-inners are
    computed once over the segment (m of them instead of B*W) and gathered
    as scalars; only the cross inner <Q, Y> touches the gathered corpus
    rows. The scalar combine is the exact expression of
    ``contractions.distance`` / ``cosine_similarity`` — the same three
    inner products flow through the same add/mul/sqrt order, so scores are
    bit-identical to the reference schedule (pinned by
    tests/test_fused_probe.py); only the redundant work is gone.

    The per-item <Y, Y> sweep deliberately runs through the SAME
    gather-into-nested-vmap structure the reference uses for its per-cell
    self-inners (an identity gather batched (1, m)): XLA's CPU backend
    picks the reduction lowering per program structure, and a plain
    row-vmap over the contiguous corpus can round the last bit differently
    from the reference's batched gathered dots on some shapes. Routing the
    hoisted sweep through the identical structure keeps the values
    bit-equal at every shape, not just the benchmarked ones. Large
    segments sweep in chunks (``self_inners``) to bound the temporaries.
    """
    inner = contractions.inner
    if (isinstance(corpus, jax.Array) and isinstance(queries, jax.Array)
            and corpus.ndim > 2):
        # dense items as flat rows (vdot flattens its operands anyway): a
        # gathered (B, W, d1, d2) block whose minor dim is narrower than
        # the TPU's 128 lanes is padded up to them — 8x for 8 x 16, which
        # at B*W = 4M gathered rows exceeds a v5e's 16 GB. The flat rows
        # exist for the re-rank's gather, so a relayout copy they cost is
        # counted there
        with jax.named_scope("rerank"):
            corpus = corpus.reshape(corpus.shape[0], -1)
            queries = queries.reshape(queries.shape[0], -1)
    yy = self_inners(corpus)                              # (m,)
    with jax.named_scope("rerank"):
        qq = jax.vmap(lambda q: inner(q, q))(queries)     # (B,)
        sub = tree_index(corpus, safe)                    # leaves (B, W, ...)
        qy = jax.vmap(
            lambda q, ys: jax.vmap(lambda y: inner(q, y))(ys))(queries, sub)
        if metric == "euclidean":
            d2 = qq[:, None] + yy[safe] - 2.0 * qy
            return jnp.sqrt(jnp.maximum(d2, 0.0))
        nq = jnp.sqrt(jnp.maximum(qq, 0.0))
        ny = jnp.sqrt(jnp.maximum(yy, 0.0))
        return qy / (nq[:, None] * ny[safe])


def segment_packed_candidates(metric, cap, queries, seg_arrays, keys):
    """One segment's probe + hoisted re-rank -> packed selection operands
    (hi (B, W) uint32 order keys, lo (B, W) int32 effective ids, n_cand
    (B,)). The probe epilogue stages (windows, dedup, packing) are the
    shared implementations in ``repro.kernels.epilogues``."""
    corpus, sorted_keys, perm, live, eff, win = seg_arrays
    m = sorted_keys.shape[1]
    with jax.named_scope("probe"):
        ids, hit = _epi.probe_windows(sorted_keys, perm, keys, cap, live, win)
        cand, valid = _epi.dedup_windows(ids, hit, m)
        safe = jnp.where(valid, cand, 0)
        n_cand = valid.sum(axis=1, dtype=jnp.int32)
    scores = hoisted_scores(metric, queries, corpus, safe)
    with jax.named_scope("select"):
        hi, lo = _epi.pack_candidates(metric, eff[safe], scores, valid)
    return hi, lo, n_cand


def _packed_query_segments(metric, topk, queries, segs, caps, keys):
    """Fused multi-segment top-k: every segment's packed candidates feed
    ONE flat packed selection. Bit-identical to per-segment ``segment_topk``
    + ``merge_topk``: both selections are keyed by (validity, score,
    effective id) — a strict total order, since effective ids are unique
    across a store's segments — so the merge tree and the flat sort pick
    the same top-k in the same order."""
    parts = [segment_packed_candidates(metric, cap, queries, sa, keys)
             for sa, cap in zip(segs, caps)]
    with jax.named_scope("select"):
        ids, scores = _epi.packed_select(
            metric, topk,
            jnp.concatenate([p[0] for p in parts], axis=1),
            jnp.concatenate([p[1] for p in parts], axis=1))
        n_cand = parts[0][2]
        for _, _, nc in parts[1:]:
            n_cand = n_cand + nc
    return ids, scores, n_cand


def shard_packed_topk_with_deltas(metric, topk, cap, delta_caps, queries,
                                  base_s, deltas_s, keys):
    """One shard's fused top-k over its base slice + delta slabs — the
    packed-selection counterpart of ``shard_topk_with_deltas``, shared by
    the vmapped and the shard_map sharded query programs (bit-identical to
    the reference body; see ``_packed_query_segments``)."""
    segs = (base_s,) + tuple(deltas_s)
    caps = (cap,) + tuple(delta_caps)
    return _packed_query_segments(metric, topk, queries, segs, caps, keys)


# ---------------------------------------------------------------------------
# The shared query planner (single-device / host / vmapped-sharded programs;
# the shard_map variant lives in repro.distributed.index_sharding)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("metric", "topk", "caps",
                                             "probes", "probe_backend"))
def segmented_query(family, segs, mults, queries, *, metric, topk, caps,
                    probes=1, probe_backend="auto"):
    """One program from query batch to top-k over every segment: hash once
    (expanding to T ranked bucket keys per table when ``probes`` > 1),
    probe + re-rank each segment, select. ``segs`` is a tuple of per-segment
    array tuples ordered by slot offset (base first, deltas in insert
    order); ``caps`` the matching static probe widths.

    ``probe_backend`` picks the probe/re-rank/select evaluation path (see
    ``resolved_probe_backend``): 'xla' runs the fused segment-major
    schedule in this module, 'pallas' the fused query kernel. Both are
    bit-identical to ``segmented_query_reference``.
    """
    if resolved_probe_backend(probe_backend) == "pallas":
        from repro.kernels import fused_query
        return fused_query.fused_query(family, segs, mults, queries,
                                       metric=metric, topk=topk, caps=caps,
                                       probes=probes)
    keys = query_keys(family, mults, queries, probes)
    return _packed_query_segments(metric, topk, queries, segs, caps, keys)


@functools.partial(jax.jit, static_argnames=("metric", "topk", "caps",
                                             "probes"))
def segmented_query_reference(family, segs, mults, queries, *, metric, topk,
                              caps, probes=1):
    """The reference planner: per-segment probe_tables + rank_candidates +
    merge_topk as separate stages. Every fused probe backend is pinned
    bit-identical to this program (tests/test_fused_probe.py); the
    sampling query modes and the candidate-inspection paths still run its
    stages directly."""
    keys = query_keys(family, mults, queries, probes)
    outs = [segment_topk(metric, topk, cap, queries, sa, keys)
            for sa, cap in zip(segs, caps)]
    return merge_topk(metric, topk,
                      jnp.stack([o[0] for o in outs]),
                      jnp.stack([o[1] for o in outs]),
                      jnp.stack([o[2] for o in outs]))


@functools.partial(jax.jit, static_argnames=("metric", "topk", "cap",
                                             "delta_caps", "probes",
                                             "probe_backend"))
def sharded_query_vmap(family, base, deltas, mults, queries, *, metric, topk,
                       cap, delta_caps, probes=1, probe_backend="auto"):
    """Single-program sharded query without a mesh: probe every (shard,
    segment) and select globally.

    Used when fewer devices than shards exist (e.g. the 1-device tier-1
    run); bit-identical to the shard_map program in
    repro.distributed.index_sharding. On the 'xla' probe backend the
    per-shard packed candidates (vmapped over the S axis) feed ONE flat
    packed selection — no per-shard top-k + S-way merge tree; the flat
    sort is keyed by (validity, score, effective id), and effective ids
    are globally unique across shards, so the result is bit-identical to
    ``sharded_query_vmap_reference`` (and to the merge tree). The 'pallas'
    backend runs the fused query kernel per shard and merges.
    """
    if resolved_probe_backend(probe_backend) == "pallas":
        from repro.kernels import fused_query
        return fused_query.fused_query_sharded(
            family, base, deltas, mults, queries, metric=metric, topk=topk,
            cap=cap, delta_caps=delta_caps, probes=probes)
    keys = query_keys(family, mults, queries, probes)

    def shard_packed(base_s, deltas_s):
        segs = (base_s,) + tuple(deltas_s)
        caps = (cap,) + tuple(delta_caps)
        parts = [segment_packed_candidates(metric, c, queries, sa, keys)
                 for sa, c in zip(segs, caps)]
        with jax.named_scope("select"):
            nc = parts[0][2]
            for _, _, n in parts[1:]:
                nc = nc + n
            return (jnp.concatenate([p[0] for p in parts], axis=1),
                    jnp.concatenate([p[1] for p in parts], axis=1), nc)

    hi, lo, nc = jax.vmap(shard_packed, in_axes=(0, 0))(base, deltas)
    s, b, w = hi.shape
    with jax.named_scope("select"):
        ids, scores = _epi.packed_select(
            metric, topk, hi.transpose(1, 0, 2).reshape(b, s * w),
            lo.transpose(1, 0, 2).reshape(b, s * w))
        return ids, scores, nc.sum(axis=0)


@functools.partial(jax.jit, static_argnames=("metric", "topk", "cap",
                                             "delta_caps", "probes"))
def sharded_query_vmap_reference(family, base, deltas, mults, queries, *,
                                 metric, topk, cap, delta_caps, probes=1):
    """Reference sharded planner: vmap the per-shard base + delta-slab
    merge-tree body (``shard_topk_with_deltas``) over the S axis, then the
    global S-way merge — the program every fused probe backend is pinned
    bit-identical to."""
    keys = query_keys(family, mults, queries, probes)
    per_shard = jax.vmap(
        lambda base_s, deltas_s: shard_topk_with_deltas(
            metric, topk, cap, delta_caps, queries, base_s, deltas_s, keys),
        in_axes=(0, 0))(base, deltas)                     # (S, B, k) each
    return merge_topk(metric, topk, *per_shard)


@functools.partial(jax.jit, static_argnames=("caps", "probes"))
def segmented_candidates(family, segs, mults, queries, *, caps, probes=1):
    """-> (cand (B, sum L[*T]*cap_g) effective ids with -1 fill, valid)."""
    keys = query_keys(family, mults, queries, probes)
    cands, valids = [], []
    for seg_arrays, cap in zip(segs, caps):
        cand, valid = segment_candidates(seg_arrays, keys, cap)
        cands.append(cand)
        valids.append(valid)
    return jnp.concatenate(cands, axis=1), jnp.concatenate(valids, axis=1)


@functools.partial(jax.jit, static_argnames=("cap", "delta_caps", "probes"))
def sharded_candidates(family, base, deltas, mults, queries, *, cap,
                       delta_caps, probes=1):
    """Sharded-base + sharded-delta-slab variant of
    ``segmented_candidates`` (vmap over shards for every segment)."""
    keys = query_keys(family, mults, queries, probes)
    parts = [jax.vmap(lambda b_s: segment_candidates(b_s, keys, cap))(base)]
    for seg_arrays, dcap in zip(deltas, delta_caps):
        parts.append(jax.vmap(
            lambda d_s, dcap=dcap: segment_candidates(d_s, keys, dcap)
        )(seg_arrays))                                    # (S, B, W) each
    cands, valids = [], []
    for cand, valid in parts:
        s, b, w = cand.shape
        cands.append(cand.transpose(1, 0, 2).reshape(b, s * w))
        valids.append(valid.transpose(1, 0, 2).reshape(b, s * w))
    return jnp.concatenate(cands, axis=1), jnp.concatenate(valids, axis=1)


# ---------------------------------------------------------------------------
# Sampling query modes (uniform / weighted over the probed bucket union)
# ---------------------------------------------------------------------------


def _segment_scored_hits(metric, cap, queries, seg_arrays, keys):
    """One segment's raw probe windows, scored and mapped to effective ids.

    -> (eid (B, W) int32 — the effective id of each raw window hit,
    ``_NO_ID`` for misses; scores (B, W) exact metric scores, bad-fill for
    misses), W = L[*T]*cap. Pre-dedup on purpose: the same item recurs once
    per (table, probe, segment-window) hit, and that multiplicity is the
    ``weighted`` sampling weight. Recurrences of one item gather the same
    corpus row, so their scores are bit-identical — any run member can
    represent the item after the id sort in ``_sample_topk``.
    """
    corpus, sorted_keys, perm, live, eff, win = seg_arrays
    ids, hit = _probe_windows(sorted_keys, perm, keys, cap, live, win)
    safe = jnp.where(hit, ids, 0)
    eid = jnp.where(hit, eff[safe], _NO_ID)
    sub = tree_index(corpus, safe)                        # leaves (B, W, ...)
    score = _score_fn(metric)
    scores = jax.vmap(
        lambda q, ys: jax.vmap(lambda y: score(q, y))(ys))(queries, sub)
    return eid, jnp.where(hit, scores, _bad_score(metric))


def _sample_topk(metric, topk, mode, rng, eid, scores):
    """Gumbel-top-k sample of ``topk`` distinct members from the probed
    union -> (ids (B, topk) with -1 fill, scores (B, topk), n_cand (B,)).

    ``eid``/``scores`` are the concatenated raw window hits of every
    segment ((B, W), misses = ``_NO_ID``/bad-fill). The rows are sorted by
    effective id (scores ride along), so each distinct member forms one
    run; the run length is its raw hit multiplicity. Per-run logits are 0
    for ``uniform`` (every distinct live member equally likely) and
    log(multiplicity) for ``weighted`` (a member is drawn with probability
    proportional to how many probed buckets hold it — equivalently,
    uniform over raw (bucket, member) tickets, so bigger probed buckets
    contribute proportionally more draws); non-run slots get -inf. Adding
    one Gumbel(0, 1) draw per slot and taking the top ``topk`` perturbed
    logits is then an exact without-replacement sample of ``topk`` distinct
    members from that distribution (the marginal of the first draw is the
    exact softmax categorical — what the seeded chi-square tests pin).
    Rows with fewer than ``topk`` distinct members sample them all.
    ``n_cand`` counts the distinct members, matching the top-k path at the
    same (L, T). The sampled subset is presented through ``select_topk``
    (score order, -1 fill), so the output contract matches ``query_batch``.
    """
    b, w = eid.shape
    s_eid, s_scores = jax.lax.sort((eid, scores), dimension=1, is_stable=True,
                                   num_keys=1)
    prev = jnp.concatenate(
        [jnp.full((b, 1), -1, s_eid.dtype), s_eid[:, :-1]], axis=1)
    newrun = s_eid != prev                   # first slot of each id run (the
    isfirst = newrun & (s_eid != _NO_ID)     # _NO_ID tail forms its own run)
    idx = jnp.arange(w, dtype=jnp.int32)
    bound = jnp.where(newrun, idx, w)
    nxt = jax.lax.cummin(bound[:, ::-1], axis=1)[:, ::-1]  # next boundary >= i
    nxt = jnp.concatenate(
        [nxt[:, 1:], jnp.full((b, 1), w, jnp.int32)], axis=1)  # strictly > i
    mult = jnp.where(isfirst, nxt - idx, 0)  # raw hit multiplicity of the run
    n_cand = isfirst.sum(axis=1, dtype=jnp.int32)
    if mode == "uniform":
        logits = jnp.where(isfirst, 0.0, -jnp.inf)
    elif mode == "weighted":
        logits = jnp.where(isfirst,
                           jnp.log(jnp.maximum(mult, 1).astype(jnp.float32)),
                           -jnp.inf)
    else:
        raise ValueError(f"unknown sampling mode {mode!r}")
    pert = logits + jax.random.gumbel(rng, (b, w), dtype=jnp.float32)
    k = min(topk, w)
    _, sel = jax.lax.top_k(pert, k)
    cand = jnp.take_along_axis(s_eid, sel, axis=1).astype(jnp.int32)
    cscores = jnp.take_along_axis(s_scores, sel, axis=1)
    cvalid = jnp.take_along_axis(isfirst, sel, axis=1)
    ids, out_scores = select_topk(metric, topk, cand, cscores, cvalid)
    return ids, out_scores, n_cand


@functools.partial(jax.jit, static_argnames=("metric", "topk", "caps",
                                             "probes", "mode"))
def segmented_sample(family, segs, mults, queries, rng, *, metric, topk,
                     caps, probes, mode):
    """Sampling-mode variant of ``segmented_query``: hash once (expanding
    to T probes), collect every segment's raw scored window hits, and draw
    ``topk`` distinct members per query from the probed union — uniform or
    bucket-size-weighted — with one explicit PRNG key per call (each query
    row consumes independent Gumbel noise from it)."""
    keys = query_keys(family, mults, queries, probes)
    parts = [_segment_scored_hits(metric, cap, queries, sa, keys)
             for sa, cap in zip(segs, caps)]
    return _sample_topk(metric, topk, mode, rng,
                        jnp.concatenate([p[0] for p in parts], axis=1),
                        jnp.concatenate([p[1] for p in parts], axis=1))


@functools.partial(jax.jit, static_argnames=("metric", "topk", "cap",
                                             "delta_caps", "probes", "mode"))
def sharded_sample_vmap(family, base, deltas, mults, queries, rng, *, metric,
                        topk, cap, delta_caps, probes, mode):
    """Sharded-base + sharded-delta-slab variant of ``segmented_sample``
    (vmap over shards for every segment, then one global draw over the
    cross-shard union — sampling is a global decision, so the sharded
    index always runs this single-program path, mesh or not)."""
    keys = query_keys(family, mults, queries, probes)
    parts = [jax.vmap(
        lambda b_s: _segment_scored_hits(metric, cap, queries, b_s, keys)
    )(base)]
    for seg_arrays, dcap in zip(deltas, delta_caps):
        parts.append(jax.vmap(
            lambda d_s, dcap=dcap: _segment_scored_hits(metric, dcap,
                                                        queries, d_s, keys)
        )(seg_arrays))                                    # (S, B, W) each
    eids, scoreses = [], []
    for eid, sc in parts:
        s, b, w = eid.shape
        eids.append(eid.transpose(1, 0, 2).reshape(b, s * w))
        scoreses.append(sc.transpose(1, 0, 2).reshape(b, s * w))
    return _sample_topk(metric, topk, mode, rng,
                        jnp.concatenate(eids, axis=1),
                        jnp.concatenate(scoreses, axis=1))


# ---------------------------------------------------------------------------
# Live-window lookups (explicit bucket_cap stores)
# ---------------------------------------------------------------------------


@jax.jit
@jax.jit
def _live_window_table(perm_l, live):
    """One table of ``_live_window_tables`` as its own bounded program."""
    live_sorted = live[perm_l]                            # (m,) bool
    rank = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(live_sorted, dtype=jnp.int32)])
    pos = jnp.argsort(~live_sorted, stable=True).astype(jnp.int32)
    return rank, pos


def _live_window_tables(perm, live):
    """(L, m) perm + (m+1,) live -> (live_rank (L, m+1), live_pos (L, m)).

    ``live_rank[p]`` counts the live slots among sorted positions [0, p) of
    the table; ``live_pos`` lists the live positions in ascending order
    (dead positions follow, also ascending — a probe walking past the live
    members of a bucket lands on dead slots that the liveness mask then
    filters). Together they let a truncated probe window address the j-th
    *live* member of a bucket directly. Issued as one bounded program per
    table (tables are independent, so values are unchanged), blocking
    between programs: window rebuilds run on the mutation plane — deletes,
    shadow-store builds — and must never queue one all-tables argsort
    ahead of a concurrently dispatched query."""
    outs = []
    for table in range(perm.shape[0]):
        out = _live_window_table(perm[table], live)
        jax.block_until_ready(out)
        _yield_slot()
        outs.append(out)
    return (jnp.stack([o[0] for o in outs]),
            jnp.stack([o[1] for o in outs]))


def _live_window_tables_sharded(perm, live):
    """Sharded variant of ``_live_window_tables``: perm (S, L, n_s) + live
    (S, n_s + 1) -> (rank (S, L, n_s + 1), pos (S, L, n_s)), one bounded
    per-(table, shard) program, throttled like the flat version. Shards
    are independent too, so splitting below the table level changes no
    value (integer sort/scan math) — it bounds each program at O(n_s)
    instead of O(S * n_s), which is what keeps a concurrent query's wait
    to one slab-sized program during a background delete at high S."""
    outs = []
    for table in range(perm.shape[1]):
        shards = []
        for sh in range(perm.shape[0]):
            out = _live_window_table(perm[sh, table], live[sh])
            jax.block_until_ready(out)
            _yield_slot()
            shards.append(out)
        outs.append((jnp.stack([o[0] for o in shards]),
                     jnp.stack([o[1] for o in shards])))
    return (jnp.stack([o[0] for o in outs], axis=1),
            jnp.stack([o[1] for o in outs], axis=1))


# ---------------------------------------------------------------------------
# Mutable store: base + deltas + tombstones
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class StoreView:
    """One immutable, internally-consistent snapshot of a store's queryable
    state — the handle a query pins for its whole program.

    ``SegmentStore`` publishes a fresh view (one atomic attribute write) at
    the end of every mutation; readers grab ``store.view`` once and derive
    every program input (segment arrays, liveness/effective-id lookups,
    probe caps) from that single object, so a query dispatched concurrently
    with an ``insert``/``delete``/``compact`` swap sees either the whole
    pre-mutation state or the whole post-mutation state — never a torn mix
    of segments from one generation and lookups from another. ``generation``
    increments with every publish; the double-buffered swap machinery in
    ``repro.core.index`` uses it to refuse publishing a shadow store whose
    source mutated while the shadow was building.
    """

    segments: tuple          # base + deltas, slot-offset order
    luts: tuple              # per-segment (live, eff) device lookups
    wins: tuple              # per-segment live-window lookups (or None)
    generation: int

    @property
    def base(self):
        return self.segments[0]

    @property
    def n_deltas(self) -> int:
        return len(self.segments) - 1

    def seg_arrays(self, i: int):
        """(corpus, sorted_keys, perm, live, eff, win) of segment i."""
        seg = self.segments[i]
        live, eff = self.luts[i]
        return (seg.corpus, seg.sorted_keys, seg.perm, live, eff,
                self.wins[i])

    @property
    def all_arrays(self) -> tuple:
        return tuple(self.seg_arrays(i) for i in range(len(self.segments)))

    @property
    def delta_arrays(self) -> tuple:
        return tuple(self.seg_arrays(i)
                     for i in range(1, len(self.segments)))

    @property
    def all_caps(self) -> tuple[int, ...]:
        return tuple(seg.cap for seg in self.segments)

    @property
    def delta_caps(self) -> tuple[int, ...]:
        return tuple(seg.cap for seg in self.segments[1:])


class SegmentStore:
    """LSM-style mutable view over immutable segments.

    Holds one base segment (``TableSegment`` or ``ShardedSegment``), a
    bounded list of delta segments (``TableSegment``s on the single-device
    store, routed ``ShardedSegment`` slabs on the sharded store), and a
    host-side tombstone mask over every slot (shard-pad slots are born
    dead). Each segment also carries a host-side ``slot_pos`` map from slot
    to *sequence position* — the order items entered the store — because
    routed slabs interleave shards, so slot order no longer equals arrival
    order. After each mutation the store re-derives the per-segment device
    arrays the planner consumes:

      live  (m+1,) bool   per segment (sharded: (S, n_s+1)) — slot liveness
                          with the pad-sentinel entry always False
      eff   (m,) int32    per segment (sharded: (S, n_s)) — the slot's
                          effective id: its rank among live slots in
                          sequence order, i.e. its index in
                          ``effective_corpus()``
      win   optional      (live_rank, live_pos) live-window lookups, built
                          only for ``live_window=True`` stores (explicit
                          bucket_cap indexes) so truncated probe windows
                          skip tombstoned slots

    Deletes only flip mask bits (same array shapes -> no recompilation);
    inserts append a segment (bounded recompiles, the index compacts past
    ``max_deltas``). ``place`` keeps every sharded segment's derived
    arrays on the index's mesh; ``base_pos`` overrides the base slot ->
    sequence map (shard-local compaction produces bases whose shards hold
    non-contiguous sequence ranges).

    Every mutation ends by publishing a fresh immutable ``StoreView`` (one
    atomic attribute write); queries read ``store.view`` once and serve the
    whole program from it, so mutations racing a query from another thread
    can never tear the segment/lookup pairing mid-read. A mutation's lookup
    rebuild is one ``lsh.store.refresh`` span (``op=append|delete``); a
    fold's new store is timed by its ``lsh.fold.tables`` span instead.
    """

    def __init__(self, base, *, place: Callable | None = None,
                 base_pos: np.ndarray | None = None,
                 live_window: bool = False):
        self.base = base
        self.deltas: list[TableSegment | ShardedSegment] = []
        self.place = place or (lambda t: t)
        self.live_window = bool(live_window)
        self._generation = 0
        if base_pos is None:
            real = np.zeros(base.slots, bool)
            if isinstance(base, ShardedSegment):
                n_s = base.shard_size
                for s, c in enumerate(base.counts):
                    real[s * n_s:s * n_s + c] = True
            else:
                real[:] = True
            base_pos = np.where(real, np.cumsum(real) - 1, -1)
        self.slot_pos = [np.asarray(base_pos, np.int64)]
        self.live_host = self.slot_pos[0] >= 0  # shard pads are born dead
        self.seq_len = int(base.items)
        self._refresh()

    # -- derived state ------------------------------------------------------

    def _segments(self) -> list:
        return [self.base] + self.deltas

    def _seg_luts(self, seg, live: np.ndarray, eff: np.ndarray):
        if isinstance(seg, ShardedSegment):
            s, n_s = seg.shards, seg.shard_size
            lut = (jnp.asarray(np.pad(live.reshape(s, n_s),
                                      ((0, 0), (0, 1)))),
                   jnp.asarray(eff.reshape(s, n_s).astype(np.int32)))
            return self.place(lut)
        return (jnp.asarray(np.append(live, False)),
                jnp.asarray(eff.astype(np.int32)))

    def _seg_win(self, seg, live_lut):
        if not self.live_window:
            return None
        if isinstance(seg, ShardedSegment):
            return self.place(_live_window_tables_sharded(seg.perm, live_lut))
        return _live_window_tables(seg.perm, live_lut)

    def _refresh(self, touched: set[int] | None = None) -> None:
        """Rebuild the sequence-order views and the segment lookups.

        ``touched`` is the set of segment indices whose live mask changed
        (None = rebuild everything). Segments are ordered blocks in
        sequence space (each delta's positions follow every earlier
        segment's), so segments before the first touched one keep both
        lookups untouched; later segments rebuild ``eff`` (ranks shifted)
        but reuse their live-window tables unless their own mask changed —
        deletes stay cheap even on capped stores with a big base and many
        slabs."""
        live_seq = np.zeros(self.seq_len, bool)
        pos_to_slot = np.full(self.seq_len, -1, np.int64)
        off = 0
        for pos, seg in zip(self.slot_pos, self._segments()):
            valid = pos >= 0
            live_seq[pos[valid]] = self.live_host[off:off + seg.slots][valid]
            pos_to_slot[pos[valid]] = off + np.flatnonzero(valid)
            off += seg.slots
        self._live_seq = live_seq
        self._pos_to_slot = pos_to_slot
        self.n_live = int(live_seq.sum())
        self.n_dead = self.seq_len - self.n_live
        eff_seq = (np.cumsum(live_seq) - 1).astype(np.int64)
        first = 0 if touched is None else min(touched, default=0)
        luts, wins, off = [], [], 0
        for i, (pos, seg) in enumerate(zip(self.slot_pos,
                                           self._segments())):
            if touched is not None and i < first:
                luts.append(self._luts[i])
                wins.append(self._wins[i])
                off += seg.slots
                continue
            live = self.live_host[off:off + seg.slots]
            eff = (eff_seq[np.clip(pos, 0, None)] if self.seq_len
                   else np.zeros(seg.slots, np.int64))
            eff = np.where(pos >= 0, eff, 0)
            lut = self._seg_luts(seg, live, eff)
            luts.append(lut)
            if touched is None or i in touched:
                wins.append(self._seg_win(seg, lut[0]))
            else:
                wins.append(self._wins[i])
            off += seg.slots
        self._luts, self._wins = luts, wins
        self._publish()

    def _publish(self) -> None:
        """Assemble + install a fresh immutable view (one atomic write)."""
        self._generation += 1
        self.view = StoreView(segments=tuple(self._segments()),
                              luts=tuple(self._luts),
                              wins=tuple(self._wins),
                              generation=self._generation)

    @property
    def generation(self) -> int:
        """Monotone mutation clock: bumps whenever a new view publishes."""
        return self.view.generation

    def seg_arrays(self, i: int):
        """(corpus, sorted_keys, perm, live, eff, win) of segment i
        (0 = base; ``win`` is None unless the store keeps live windows).
        Served from the published view — for a multi-access read sequence
        that must stay consistent under concurrent mutation, pin
        ``store.view`` once instead."""
        return self.view.seg_arrays(i)

    @property
    def delta_arrays(self) -> tuple:
        return self.view.delta_arrays

    @property
    def delta_caps(self) -> tuple[int, ...]:
        return self.view.delta_caps

    @property
    def all_arrays(self) -> tuple:
        return self.view.all_arrays

    @property
    def all_caps(self) -> tuple[int, ...]:
        return self.view.all_caps

    @property
    def mutated(self) -> bool:
        return bool(self.deltas) or self.n_dead > 0

    @property
    def shard_live_counts(self) -> np.ndarray:
        """(S,) live items per shard over the base + every sharded delta —
        the occupancy the routing policy balances against."""
        counts = None
        off = 0
        for seg in self._segments():
            live = self.live_host[off:off + seg.slots]
            if isinstance(seg, ShardedSegment):
                c = live.reshape(seg.shards, seg.shard_size).sum(axis=1)
                counts = c.astype(np.int64) if counts is None else counts + c
            off += seg.slots
        return counts

    # -- durability hooks ----------------------------------------------------

    def host_state(self) -> dict:
        """The host-side bookkeeping a snapshot must persist next to the
        segment arrays: the slot -> sequence-position maps, the tombstone
        mask, and the sequence clock. Everything else the store serves
        (liveness/effective-id/live-window lookups, the published view) is
        re-derived deterministically by ``restore`` via ``_refresh``, so a
        snapshot never has to serialize device lookups."""
        return {
            "slot_pos": [np.asarray(p, np.int64) for p in self.slot_pos],
            "live_host": np.asarray(self.live_host, bool),
            "seq_len": int(self.seq_len),
            "live_window": bool(self.live_window),
        }

    @classmethod
    def restore(cls, segs, state: dict, *,
                place: Callable | None = None) -> "SegmentStore":
        """Rebuild a store from snapshotted segments + ``host_state()``.

        Installs the raw host state, then re-derives every lookup and
        publishes a fresh view through ``_refresh`` — the same code path
        every live mutation ends with — so a restored store answers
        queries bit-identically to the one that was snapshotted."""
        if len(segs) != len(state["slot_pos"]):
            raise ValueError(
                f"{len(segs)} segments but {len(state['slot_pos'])} "
                "slot_pos maps in the snapshot state")
        store = cls.__new__(cls)
        store.base = segs[0]
        store.deltas = list(segs[1:])
        store.place = place or (lambda t: t)
        store.live_window = bool(state["live_window"])
        store._generation = 0
        store.slot_pos = [np.asarray(p, np.int64) for p in state["slot_pos"]]
        store.live_host = np.asarray(state["live_host"], bool)
        store.seq_len = int(state["seq_len"])
        store._refresh()
        return store

    # -- mutations ----------------------------------------------------------

    def append_delta(self, seg, positions: np.ndarray | None = None) -> None:
        """O(batch) append: earlier segments' liveness and effective ids are
        untouched (new items rank after every live item), so only the new
        segment's lookups are built — no base-array re-upload per insert.
        ``positions`` maps the segment's slots to sequence positions (``-1``
        pads); defaults to the identity continuation for flat deltas."""
        if positions is None:
            positions = np.arange(self.seq_len, self.seq_len + seg.slots)
        positions = np.asarray(positions, np.int64)
        valid = positions >= 0
        n_new = int(valid.sum())
        start, seq0, slots0 = self.n_live, self.seq_len, self.live_host.size
        self.deltas.append(seg)
        self.slot_pos.append(positions)
        self.live_host = np.concatenate([self.live_host, valid])
        self._live_seq = np.concatenate([self._live_seq,
                                         np.ones(n_new, bool)])
        p2s = np.full(n_new, -1, np.int64)
        p2s[positions[valid] - seq0] = slots0 + np.flatnonzero(valid)
        self._pos_to_slot = np.concatenate([self._pos_to_slot, p2s])
        self.seq_len += n_new
        self.n_live += n_new
        eff = np.where(valid, start + (positions - seq0), 0)
        with tracing.span("lsh.store.refresh", op="append"):
            lut = self._seg_luts(seg, valid, eff)
            self._luts.append(lut)
            self._wins.append(self._seg_win(seg, lut[0]))
        self._publish()

    def delete_effective(self, ids: np.ndarray) -> int:
        """Tombstone items by their current *effective* ids (the numbering
        queries return). Returns the number of newly-dead items."""
        ids = np.unique(np.asarray(ids, np.int64))
        if ids.size == 0:
            return 0
        if ids.size and (ids[0] < 0 or ids[-1] >= self.n_live):
            raise IndexError(
                f"delete ids must be in [0, {self.n_live}), got "
                f"[{ids[0]}, {ids[-1]}]")
        seq_ids = np.flatnonzero(self._live_seq)[ids]
        slots = self._pos_to_slot[seq_ids]
        self.live_host[slots] = False
        bounds = np.cumsum([seg.slots for seg in self._segments()])
        touched = set(np.searchsorted(bounds, slots,
                                      side="right").tolist())
        with tracing.span("lsh.store.refresh", op="delete"):
            self._refresh(touched)
        return int(ids.size)

    # -- effective (live) views --------------------------------------------

    def _flat_keys_and_corpus(self):
        flat_keys, flat_corpus = [], []
        for seg in self._segments():
            if isinstance(seg, ShardedSegment):
                flat_keys.append(seg.keys.reshape(-1, seg.keys.shape[-1]))
                flat_corpus.append(jax.tree.map(
                    lambda a: a.reshape((-1,) + a.shape[2:]), seg.corpus))
            else:
                flat_keys.append(seg.keys)
                flat_corpus.append(seg.corpus)
        keys = jnp.concatenate(flat_keys, axis=0)
        corpus = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *flat_corpus)
        return keys, corpus

    def _live_slots_seq_order(self) -> np.ndarray:
        """Flat slot indices of the live items, in sequence order."""
        live_slots = np.flatnonzero(self.live_host)
        pos = np.concatenate(self.slot_pos)[live_slots]
        return live_slots[np.argsort(pos, kind="stable")]

    def effective_arrays(self):
        """-> ((n_live, L) keys, corpus pytree) of live items in sequence
        (= effective id) order — the rebalance/global-compaction input;
        keys come from storage, never from re-hashing."""
        keys, corpus = self._flat_keys_and_corpus()
        idx = jnp.asarray(self._live_slots_seq_order())
        return keys[idx], tree_index(corpus, idx)

    def effective_arrays_chunked(self, chunk: int):
        """``effective_arrays`` with the corpus assembled by bounded
        gather+scatter programs (``gather_rows_chunked``) instead of one
        store-sized concatenate + gather. Bit-identical output; the
        shadow-build (``prepare_compact``) path uses it so concurrent
        queries never queue behind a store-sized program. Keys stay on the
        one-program path — they are a few bytes per item. The row order and
        the keys are one ``lsh.fold.order`` span."""
        with tracing.span("lsh.fold.order"):
            idx = self._live_slots_seq_order()
            flat_keys = []
            srcs, src_idxs, dst_idxs = [], [], []
            off = 0
            for seg in self._segments():
                if isinstance(seg, ShardedSegment):
                    flat_keys.append(seg.keys.reshape(-1, seg.keys.shape[-1]))
                    flat = jax.tree.map(
                        lambda a: a.reshape((-1,) + a.shape[2:]), seg.corpus)
                else:
                    flat_keys.append(seg.keys)
                    flat = seg.corpus
                w = seg.slots
                dst = np.flatnonzero((idx >= off) & (idx < off + w))
                srcs.append(flat)
                src_idxs.append(idx[dst] - off)
                dst_idxs.append(dst)
                off += w
            keys = jnp.concatenate(flat_keys, axis=0)[jnp.asarray(idx)]
        corpus = gather_rows_chunked(srcs[0], srcs, src_idxs, dst_idxs,
                                     idx.size, chunk=chunk)
        return keys, corpus

    def effective_corpus(self):
        """The live corpus in effective-id order. Zero-copy for a pristine
        flat base, a slice view when live slots are already a contiguous
        prefix in sequence order (pristine contiguous sharded base), and a
        corpus-only gather otherwise — the stored keys are never touched
        (``effective_arrays`` is the keys+corpus variant compaction needs).
        """
        if not self.mutated and isinstance(self.base, TableSegment):
            return self.base.corpus
        flats = [jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                              seg.corpus)
                 if isinstance(seg, ShardedSegment) else seg.corpus
                 for seg in self._segments()]
        corpus = flats[0] if len(flats) == 1 else jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *flats)
        idx = self._live_slots_seq_order()
        if np.array_equal(idx, np.arange(idx.size)):
            return tree_index(corpus, slice(0, idx.size))
        return tree_index(corpus, jnp.asarray(idx))
