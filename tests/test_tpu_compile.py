"""Compile the served path for a TPU v5e chip that is described, not attached.

The TPU compiler is installed with jaxlib, so these tests lower and compile
the programs ``chip_smoke.py`` runs, at the smoke's deployment sizes, for
one chip of a described ``v5e:2x2`` topology (and for all four in the
sharded case). A compile that passes is not a chip run: nothing executes
and nothing is timed. What it catches is what the chip's compiler would
refuse — an op Mosaic or XLA:TPU cannot lower, a layout it rejects, a
program that does not fit the chip's 16 GB — before any chip time is
spent. The topology is described only inside the module fixture, never
while a module is imported (see the fixture's docstring).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import segments
from repro.core.lsh import make_family
from repro.core.tensor_formats import CPTensor, TTTensor, _tt_core_shapes
from repro.distributed import index_sharding

HBM_BYTES = 16e9        # one v5e chip
N = 1_000_000           # chip_smoke.py's dense and in-format corpora
B = 1024
L = K = 8
CAP = 64
MODES = (8, 16)         # 128-d vectors viewed as 8 x 16
TENSOR_DIMS = (12, 12, 12)
RANK = 4


@pytest.fixture(scope="module")
def topo():
    """The described v5e:2x2 topology. Built here, after collection, and
    never at import: only one process may load the TPU library, and
    several test workers import this file."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile written to the persistent cache cannot be read back
    # without a chip; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _dense_family():
    return make_family(jax.random.PRNGKey(0), "cp-e2lsh", MODES,
                       num_codes=K, num_tables=L, bucket_width=12.0)


def _segment(m, sharding, shards=None):
    """(corpus, sorted_keys, perm, live, eff, win) specs of one segment of
    m items with live-window lookups (an explicit bucket_cap); with
    ``shards``, m items per shard behind a leading shard axis."""
    lead = () if shards is None else (shards,)
    s = lambda shape, dt: _spec(lead + shape, dt, sharding)
    return (s((m,) + MODES, jnp.float32), s((L, m), jnp.uint32),
            s((L, m), jnp.int32), s((m + 1,), jnp.bool_),
            s((m,), jnp.int32),
            (s((L, m + 1), jnp.int32), s((L, m), jnp.int32)))


def _fits(compiled) -> tuple[float, float]:
    mem = compiled.memory_analysis()
    args, temp = mem.argument_size_in_bytes, mem.temp_size_in_bytes
    assert args + temp < HBM_BYTES, (args, temp)
    return args, temp


@pytest.mark.parametrize("deltas", [0, 1])
@pytest.mark.parametrize("probes", [1, 8])
def test_query_program_fits_one_chip(one_chip, probes, deltas):
    """The served dense query (hash, multi-probe, probe, re-rank, top-k)
    at 1M x 128, B=1024, L=K=8, cap=64 compiles for one v5e on the xla
    path and fits its 16 GB, compacted and with a 4096-item delta
    outstanding."""
    fam = _dense_family()
    segs = (_segment(N, one_chip),) + (_segment(4096, one_chip),) * deltas
    lowered = segments.segmented_query.lower(
        _shapes(fam, one_chip), segs, _spec((K,), jnp.uint32, one_chip),
        _spec((B,) + MODES, jnp.float32, one_chip),
        metric="euclidean", topk=10, caps=(CAP,) * len(segs),
        probes=probes)
    compiled = lowered.compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()   # no Pallas kernel


def test_build_program_fits_one_chip(one_chip):
    """Build at 1M: the fused hash of the corpus plus the per-table sort."""
    fam = _dense_family()

    def build(family, corpus, mults):
        return segments._sort_tables(family.hash_keys(corpus, mults).T)

    compiled = jax.jit(build).lower(
        _shapes(fam, one_chip), _spec((N,) + MODES, jnp.float32, one_chip),
        _spec((K,), jnp.uint32, one_chip)).compile()
    _fits(compiled)


def _tensor_family(kind):
    return make_family(jax.random.PRNGKey(0), kind, TENSOR_DIMS,
                       num_codes=K if kind == "cp-e2lsh" else 16,
                       num_tables=L, rank=RANK, bucket_width=1.0)


def _tensors(kind, n, sharding):
    """Specs of n CP (12,12,12) rank-4 or TT rank-4 tensors."""
    if kind == "cp-e2lsh":
        return CPTensor(factors=tuple(
            _spec((n, d, RANK), jnp.float32, sharding)
            for d in TENSOR_DIMS), scale=1.0)
    return TTTensor(cores=tuple(
        _spec((n,) + s, jnp.float32, sharding)
        for s in _tt_core_shapes(TENSOR_DIMS, RANK)), scale=1.0)


@pytest.mark.parametrize("kind", ["cp-e2lsh", "tt-srp"])
def test_in_format_hash_keys_compile(one_chip, kind):
    """In-format hashing at B=1024: CP (12,12,12) rank-4 tensors under
    cp-e2lsh, TT rank-4 tensors under tt-srp, through ``hash_keys`` with
    the default backend (xla)."""
    fam = _tensor_family(kind)
    compiled = jax.jit(lambda f, x, m: f.hash_keys(x, m)).lower(
        _shapes(fam, one_chip), _tensors(kind, B, one_chip),
        _spec((fam.num_codes,), jnp.uint32, one_chip)).compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("kind,n", [("cp-e2lsh", N), ("tt-srp", N)])
def test_in_format_query_fits_one_chip(one_chip, kind, n):
    """The in-format query at chip_smoke.py's sizes: 1M CP tensors and 1M
    TT tensors. The per-item norm sweep runs in chunks: over all 1M TT
    items at once its tile-padded temporaries take 17.2 GB, more than the
    chip has."""
    fam = _tensor_family(kind)
    seg = (_tensors(kind, n, one_chip),) + _segment(n, one_chip)[1:]
    compiled = segments.segmented_query.lower(
        _shapes(fam, one_chip), (seg,),
        _spec((fam.num_codes,), jnp.uint32, one_chip),
        _tensors(kind, B, one_chip),
        metric="euclidean" if kind == "cp-e2lsh" else "cosine", topk=10,
        caps=(CAP,)).compile()
    _fits(compiled)


def test_shard_map_query_quarter_bytes_per_chip(topo, one_chip):
    """The S=4 shard_map query on a 2x2 mesh compiles, and each chip
    holds about a quarter of the S=1 program's store bytes."""
    shards = 4
    mesh = Mesh(np.asarray(topo.devices[:shards]), ("shard",))
    sharded, rep = NamedSharding(mesh, P("shard")), NamedSharding(mesh, P())
    fam = _dense_family()
    compiled = index_sharding.shard_map_query.lower(
        _shapes(fam, rep), _segment(N // shards, sharded, shards), (),
        _spec((K,), jnp.uint32, rep), _spec((B,) + MODES, jnp.float32, rep),
        metric="euclidean", topk=10, cap=CAP, delta_caps=(), mesh=mesh,
        axis="shard").compile()
    args4, _ = _fits(compiled)
    args1, _ = _fits(segments.segmented_query.lower(
        _shapes(fam, one_chip), (_segment(N, one_chip),),
        _spec((K,), jnp.uint32, one_chip),
        _spec((B,) + MODES, jnp.float32, one_chip),
        metric="euclidean", topk=10, caps=(CAP,)).compile())
    assert 0.2 < args4 / args1 < 0.3, (args4, args1)


def test_auto_resolves_to_xla_on_tpu(monkeypatch):
    """On a TPU both 'auto' knobs pick xla: the chip's compiler refuses
    the Pallas kernels. An explicit 'pallas' is still honoured."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("REPRO_HASH_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_PROBE_BACKEND", raising=False)
    fam = _dense_family()
    assert fam.hash_backend == "auto"
    assert fam.resolved_backend() == "xla"
    assert segments.resolved_probe_backend("auto") == "xla"
    assert segments.resolved_probe_backend("pallas") == "pallas"
    pinned = make_family(jax.random.PRNGKey(0), "cp-e2lsh", MODES,
                         hash_backend="pallas")
    assert pinned.resolved_backend() == "pallas"
