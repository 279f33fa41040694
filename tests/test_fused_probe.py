"""Bit-identity matrix of the fused query-to-candidates path.

Pins both probe backends — ``xla`` (the restructured segment-major
schedule in ``core.segments``) and ``pallas`` (the fused query kernel in
``kernels.fused_query``, interpret mode on CPU) — against the reference
planner, bitwise: candidate ids equal, scores equal as int32 bit
patterns, candidate counts equal. The grid is the shared layout suite
(tests/grids.py): 6 kinds x 2 metrics (non-canonical pairings marked
slow) x T in {1, 8} x {device, sharded S in {1, 2, 4}} x {fresh,
mutated}.

Reference pairing doctrine (mirrors the seed's own programs): XLA's CPU
backend picks reduction lowerings per program *structure*, so two
correct programs with different batching structures can round last bits
differently — the seed's vmapped no-mesh fallback and its unbatched
shard_map body already diverge this way (test_index_sharded tolerates it
with an rtol on the vmap path). Each backend therefore pins against the
reference sharing its batching structure:

- device (unbatched schedule)          -> ``segmented_query_reference``
- sharded + mesh (shard_map, xla)      -> ``shard_map_query_reference``
- sharded no-mesh (vmapped, xla)       -> ``sharded_query_vmap_reference``
- sharded pallas (per-shard unbatched) -> per-shard reference loop
                                          + ``merge_topk``

Cross-structure equality is NOT asserted anywhere in the repo and is not
a regression when absent.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import grids
from repro.core import (DeviceLSHIndex, ShardedLSHIndex, cp_random_data,
                        tt_random_data)
from repro.core import segments as seg
from repro.distributed import index_sharding
from repro.serving.lsh_service import build_service

N, B, TOPK = 53, 6, 5
BACKENDS = ("xla", "pallas")


def _assert_bitwise(tag, got, ref):
    gi, gs, gn = got
    ri, rs, rn = ref
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(ri),
                                  err_msg=f"{tag}: candidate ids differ")
    np.testing.assert_array_equal(
        np.asarray(gs).view(np.int32), np.asarray(rs).view(np.int32),
        err_msg=f"{tag}: scores differ in bit pattern")
    np.testing.assert_array_equal(np.asarray(gn), np.asarray(rn),
                                  err_msg=f"{tag}: candidate counts differ")


def _mutate(idx, corpus):
    idx.delete(jnp.arange(0, 12, 3))
    idx.insert(corpus[:7] * 1.01)


def _per_shard_reference(fam, idx, queries, metric, probes):
    """The pallas-structure reference: each shard queried as its own
    unbatched program (the shard_map body), merged once — matching the
    fused kernel's one-flat-launch-over-(shard, segment) schedule."""
    view = idx.store.view
    keys = seg.query_keys(fam, jnp.asarray(idx._mults), queries, probes)
    base = view.seg_arrays(0)
    deltas = view.delta_arrays
    s = jax.tree.leaves(base)[0].shape[0]
    outs = []
    for i in range(s):
        base_i = jax.tree.map(lambda a, i=i: a[i], base)
        deltas_i = tuple(jax.tree.map(lambda a, i=i: a[i], d)
                         for d in deltas)
        outs.append(seg.shard_topk_with_deltas(
            metric, TOPK, view.base.cap, view.delta_caps, queries,
            base_i, deltas_i, keys))
    if s == 1:
        return outs[0]
    return seg.merge_topk(metric, TOPK,
                          jnp.stack([o[0] for o in outs]),
                          jnp.stack([o[1] for o in outs]),
                          jnp.stack([o[2] for o in outs]))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kind,metric", grids.cell_params())
def test_device_bit_identity(kind, metric, backend):
    fam = grids.grid_family(kind)
    corpus, queries = grids.corpus_and_queries(N, B)
    idx = DeviceLSHIndex(fam, metric=metric, bucket_cap=4).build(corpus)
    for state in ("fresh", "mutated"):
        if state == "mutated":
            _mutate(idx, corpus)
        view = idx.store.view
        for probes in (1, 8):
            ref = seg.segmented_query_reference(
                fam, view.all_arrays, jnp.asarray(idx._mults), queries,
                metric=metric, topk=TOPK, caps=view.all_caps,
                probes=probes)
            got = dataclasses.replace(idx, probe_backend=backend) \
                .query_batch(queries, topk=TOPK, probes=probes)
            _assert_bitwise(f"device {state} T={probes} {backend}",
                            got, ref)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("shards", grids.SHARD_COUNTS)
@pytest.mark.parametrize("kind,metric", grids.cell_params())
def test_sharded_bit_identity(kind, metric, shards, backend):
    fam = grids.grid_family(kind)
    corpus, queries = grids.corpus_and_queries(N, B, seed=1)
    idx = ShardedLSHIndex(fam, metric=metric, shards=shards,
                          bucket_cap=4).build(corpus)
    for state in ("fresh", "mutated"):
        if state == "mutated":
            _mutate(idx, corpus)
        view = idx.store.view
        for probes in (1, 8):
            if backend == "pallas":
                ref = _per_shard_reference(fam, idx, queries, metric,
                                           probes)
            else:
                args = (fam, view.seg_arrays(0), view.delta_arrays,
                        jnp.asarray(idx._mults), queries)
                kwargs = dict(metric=metric, topk=TOPK,
                              cap=view.base.cap,
                              delta_caps=view.delta_caps, probes=probes)
                if idx.mesh is not None:
                    ref = index_sharding.shard_map_query_reference(
                        *args, mesh=idx.mesh, axis=idx.mesh_axis,
                        **kwargs)
                else:
                    ref = seg.sharded_query_vmap_reference(*args,
                                                           **kwargs)
            got = dataclasses.replace(idx, probe_backend=backend) \
                .query_batch(queries, topk=TOPK, probes=probes)
            _assert_bitwise(
                f"sharded S={shards} {state} T={probes} {backend}",
                got, ref)


@pytest.mark.parametrize("fmt", ["dense", "cp", "tt"])
def test_self_inners_chunked_bitwise(fmt):
    """The chunked per-item self-inner sweep (large segments) returns the
    one-sweep values bit for bit, the short tail chunk included."""
    key = jax.random.PRNGKey(3)
    if fmt == "dense":
        corpus = jax.random.normal(key, (N,) + grids.DIMS)
    else:
        gen = cp_random_data if fmt == "cp" else tt_random_data
        corpus = jax.vmap(lambda k: gen(k, grids.DIMS, 2))(
            jax.random.split(key, N))
    whole = jax.jit(seg.self_inners)(corpus)
    assert whole.shape == (N,)
    for chunk in (7, 16, N):
        got = jax.jit(functools.partial(seg.self_inners, chunk=chunk))(
            corpus)
        np.testing.assert_array_equal(
            np.asarray(got).view(np.int32), np.asarray(whole).view(np.int32),
            err_msg=f"chunk={chunk}")


def test_resolved_probe_backend(monkeypatch):
    monkeypatch.delenv("REPRO_PROBE_BACKEND", raising=False)
    assert seg.resolved_probe_backend("auto") == "xla"
    # explicit knob wins over everything
    monkeypatch.setenv("REPRO_PROBE_BACKEND", "pallas")
    assert seg.resolved_probe_backend("xla") == "xla"
    # env var steers 'auto' (read at trace time)
    assert seg.resolved_probe_backend("auto") == "pallas"
    with pytest.raises(ValueError):
        seg.resolved_probe_backend("mlir")


def test_probe_backend_threading():
    """The knob flows index -> service and is reported by probe_path."""
    fam = grids.grid_family("cp-e2lsh")
    corpus, queries = grids.corpus_and_queries(N, B)
    idx = DeviceLSHIndex(fam, metric="euclidean", bucket_cap=4,
                         probe_backend="pallas").build(corpus)
    assert idx.probe_path == "pallas"
    assert DeviceLSHIndex(fam, metric="euclidean").probe_path == "xla"
    svc = build_service(jax.random.PRNGKey(0), "cp-e2lsh", grids.DIMS,
                        corpus, num_codes=3, num_tables=4, rank=2,
                        bucket_width=6.0, bucket_cap=4,
                        probe_backend="pallas")
    assert svc.probe_path == "pallas"
    got = svc.query_arrays(queries, topk=TOPK)
    ref = build_service(jax.random.PRNGKey(0), "cp-e2lsh", grids.DIMS,
                        corpus, num_codes=3, num_tables=4, rank=2,
                        bucket_width=6.0, bucket_cap=4,
                        probe_backend="xla").query_arrays(queries,
                                                          topk=TOPK)
    _assert_bitwise("service pallas vs xla (same unbatched structure)",
                    got, ref)


def test_sharded_query_path_loud():
    """The 4-device CI leg must run the fused program inside shard_map —
    a silent vmap fallback on the xla backend is a failure; the pallas
    backend must report its (deferred-dispatch) single-program path."""
    fam = grids.grid_family("cp-e2lsh")
    corpus, _ = grids.corpus_and_queries(N, B)
    idx = ShardedLSHIndex(fam, metric="euclidean", shards=4,
                          bucket_cap=4, probe_backend="xla").build(corpus)
    grids.assert_query_path(idx)
    assert idx.probe_path == "xla"
    pallas_idx = dataclasses.replace(idx, probe_backend="pallas")
    assert pallas_idx.query_path == "vmap"
    assert pallas_idx.probe_path == "pallas"
