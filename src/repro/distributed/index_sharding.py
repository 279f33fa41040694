"""Mesh placement + shard_map query program for ``ShardedLSHIndex``.

The index math (per-segment probe, re-rank, global top-k merge) lives in
``repro.core.segments``; this module decides *where* the sharded base
segment runs and provides the ``shard_map`` variant of the query program:

- ``resolve_mesh``: map a shard count to (mesh, axis). An active
  ``distributed.sharding.axis_rules`` context wins — the ``lsh_shard``
  logical name resolves through the same rule machinery as every other
  logical dim, so the index shards over ``data`` on the production meshes
  and over the dedicated 1-D ``shard`` mesh in tests. Without a context, a
  1-D mesh over the first S local devices is built; with fewer devices than
  shards the caller falls back to the vmapped single-device program.
- ``place_sharded``: NamedSharding placement of any (S, ...)-leading index
  arrays — the base segment AND the routed delta slabs (sorted keys,
  permutations, liveness/effective-id/live-window lookups, corpus slices)
  follow the same rules, so the mutation plane shards exactly like the
  query plane. ``place_shadow`` is the blocking variant the double-buffered
  swap uses to land a fully-materialized shadow store before the pointer
  flip publishes it.
- ``shard_map_query``: one jit program — replicated hashing outside the
  shard_map; inside it each device probes its base block *and* its slab of
  every delta segment (searchsorted/gather/tombstone-filter/re-rank) and
  merges them into one per-shard top-k; then the single global S-way merge.
  Deltas are never a replicated post-merge appendix.
"""

from __future__ import annotations

import functools

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import sharding

# Logical dim name of the corpus-shard axis (see sharding.DEFAULT_RULES) and
# the mesh axis name used when this module builds its own 1-D mesh.
SHARD_LOGICAL = "lsh_shard"
SHARD_AXIS = "shard"


def resolve_mesh(shards: int) -> tuple[Mesh, str] | tuple[None, None]:
    """-> (mesh, axis name) to lay the S-sharded index over, or (None, None).

    Inside an ``axis_rules`` context the ``lsh_shard`` rule must resolve to
    a single mesh axis whose size equals ``shards`` (the index's leading
    dim is exactly one slice per device along that axis); otherwise a
    dedicated 1-D mesh over the first ``shards`` local devices is built.
    """
    ctx = sharding.current()
    if ctx is not None:
        axes = ctx.rules.get(SHARD_LOGICAL)
        if axes and len(axes) == 1 and ctx.mesh.shape[axes[0]] == shards:
            return ctx.mesh, axes[0]
        return None, None  # context active but rule unusable -> vmap path
    devices = jax.devices()
    if shards <= len(devices):
        return Mesh(np.asarray(devices[:shards]), (SHARD_AXIS,)), SHARD_AXIS
    return None, None


def place_sharded(tree, mesh: Mesh, axis: str):
    """device_put every leaf with its leading dim sharded over ``axis``."""
    sh = NamedSharding(mesh, P(axis))
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def place_shadow(tree, mesh: Mesh, axis: str):
    """``place_sharded`` for the double-buffered swap's shadow store: the
    transfers are issued AND waited on here, off the query path, so the
    later pointer flip publishes a store whose every array has already
    landed on its shard — the first post-swap query pays zero placement
    cost and the flip itself does no device work."""
    placed = place_sharded(tree, mesh, axis)
    jax.block_until_ready(jax.tree.leaves(placed))
    return placed


@functools.partial(jax.jit, static_argnames=("metric", "topk", "cap",
                                             "delta_caps", "probes", "mesh",
                                             "axis", "probe_backend"))
def shard_map_query(family, base, deltas, mults, queries, *, metric, topk,
                    cap, delta_caps, mesh, axis, probes=1,
                    probe_backend="auto"):
    """One jit program: hash (replicated) -> per-shard fused probe/re-rank/
    top-k over the base block + every delta slab (shard_map) -> global S-way
    merge. Bit-identical to ``shard_map_query_reference`` and to
    ``core.segments.sharded_query_vmap``.

    ``base`` and each element of ``deltas`` is a (corpus, sorted_keys,
    perm, live, eff, win) tuple whose array leaves carry a leading shard
    dim laid over ``axis``; each device sees its (1, ...) blocks.
    ``probes`` = T > 1 replicates the (L, T, B) multi-probe key tensor
    instead of the (L, B) single-probe one — the shard body is
    shape-agnostic, so every device probes all T buckets of its blocks.

    ``probe_backend`` mirrors the knob on ``segments.segmented_query``.
    The 'xla' path runs the restructured packed schedule
    (``segments.shard_packed_topk_with_deltas``) inside the shard_map body;
    'pallas' currently falls back to the per-shard fused-kernel loop in
    ``segments.sharded_query_vmap`` — dispatching the Pallas program
    through shard_map itself is the deferred TPU measurement leg (ROADMAP).
    """
    from repro.core import segments

    if segments.resolved_probe_backend(probe_backend) == "pallas":
        return segments.sharded_query_vmap(
            family, base, deltas, mults, queries, metric=metric, topk=topk,
            cap=cap, delta_caps=delta_caps, probes=probes,
            probe_backend="pallas")

    # (L, B) / (L, T, B), replicated
    keys = segments.query_keys(family, mults, queries, probes)

    def body(base_blk, deltas_blk, keys_r, queries_r):
        # blocks carry a leading shard dim of 1 on the sharded operands
        take0 = lambda t: jax.tree.map(lambda a: a[0], t)
        ids, scores, n_cand = segments.shard_packed_topk_with_deltas(
            metric, topk, cap, delta_caps, queries_r,
            take0(base_blk), take0(deltas_blk), keys_r)
        return ids[None], scores[None], n_cand[None]

    sharded_spec, rep = P(axis), P()
    per_shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sharded_spec, sharded_spec, rep, rep),
        out_specs=(sharded_spec,) * 3,
        check_vma=False,
    )(base, deltas, keys, queries)
    return segments.merge_topk(metric, topk, *per_shard)


@functools.partial(jax.jit, static_argnames=("metric", "topk", "cap",
                                             "delta_caps", "probes", "mesh",
                                             "axis"))
def shard_map_query_reference(family, base, deltas, mults, queries, *, metric,
                              topk, cap, delta_caps, mesh, axis, probes=1):
    """The reference shard_map program: per-shard merge-tree top-k
    (``segments.shard_topk_with_deltas``) then the global S-way merge.
    The restructured ``shard_map_query`` above is pinned bit-identical to
    this program (tests/test_fused_probe.py)."""
    from repro.core import segments

    keys = segments.query_keys(family, mults, queries, probes)

    def body(base_blk, deltas_blk, keys_r, queries_r):
        take0 = lambda t: jax.tree.map(lambda a: a[0], t)
        ids, scores, n_cand = segments.shard_topk_with_deltas(
            metric, topk, cap, delta_caps, queries_r,
            take0(base_blk), take0(deltas_blk), keys_r)
        return ids[None], scores[None], n_cand[None]

    sharded_spec, rep = P(axis), P()
    per_shard = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sharded_spec, sharded_spec, rep, rep),
        out_specs=(sharded_spec,) * 3,
        check_vma=False,
    )(base, deltas, keys, queries)
    return segments.merge_topk(metric, topk, *per_shard)
