"""The shared parametrization grid of the layout/parity suites.

Every cross-layout test file sweeps the same 6 family kinds x 2 metrics
over the same small canonical families and clustered corpus/query fixtures.
That grid used to be copy-pasted per file (test_index_device,
test_index_sharded, test_index_mutation, test_hash_backends) and drifted
one constant at a time; it lives here once so a new layout suite (e.g.
tests/test_multiprobe.py) states *which* cells it covers, not how to
build them.

Not a test module — pytest prepends this directory to sys.path, so suites
just ``import grids``.
"""

import jax
import pytest

from repro.core import CPTensor, make_family
from repro.core.lsh import ALL_KINDS, E2LSH_KINDS, SRP_KINDS  # noqa: F401
                                      # (re-exported: the grid axes)

METRICS = ("euclidean", "cosine")
DIMS = (4, 4, 4)
SHARD_COUNTS = (1, 2, 4)   # corpus sizes are kept coprime to these so the
                           # padded last shard is always exercised


def metric_for(kind: str) -> str:
    """The metric the kind's collision guarantees target (SRP hashes
    angles -> cosine; E2LSH hashes offsets -> euclidean)."""
    return "cosine" if kind.endswith("srp") else "euclidean"


def cell_params(kinds=ALL_KINDS, metrics=METRICS):
    """The kind x metric grid as parametrize cells, with every
    *non-canonical* metric pairing marked ``slow``.

    The canonical-metric half (SRP kinds -> cosine, E2LSH kinds ->
    euclidean) already drives both scoring paths across the kind axis, so
    the cross-metric half re-checks metric handling the fast leg has
    covered with a different hash family in front of it — real coverage,
    but redundant per-push. ``make test`` / the full CI leg still sweeps
    the whole grid; ``make test-fast`` / the fast leg runs the canonical
    half. Use as ``@pytest.mark.parametrize("kind,metric", cell_params())``
    in place of stacking a kind and a metric decorator.
    """
    return [pytest.param(kind, metric,
                         marks=() if metric == metric_for(kind)
                         else (pytest.mark.slow,))
            for kind in kinds for metric in metrics]


def grid_family(kind: str, dims=DIMS, num_tables: int = 4, rank: int = 2,
                seed: int = 42, hash_backend: str = "auto"):
    """The canonical small test family of the parity suites.

    (num_codes, bucket_width) are tuned per hash type so every kind lands
    a useful bucket structure on the ~50-70 item fixtures: K=3 wide-bucket
    E2LSH, K=6 SRP. Keep in sync with nothing — this IS the definition the
    suites share.
    """
    k, w = (3, 6.0) if "e2lsh" in kind else (6, 0.0)
    return make_family(jax.random.PRNGKey(seed), kind, dims, num_codes=k,
                       num_tables=num_tables, rank=rank,
                       bucket_width=max(w, 1.0), hash_backend=hash_backend)


def corpus_and_queries(n_corpus: int, n_queries: int, dims=DIMS,
                       seed: int = 0, noise: float = 0.1):
    """Gaussian corpus + queries perturbed off its first rows, so every
    query has a planted near neighbour (the fixture all parity suites
    share)."""
    kc, kq = jax.random.split(jax.random.PRNGKey(seed))
    corpus = jax.random.normal(kc, (n_corpus,) + dims)
    queries = corpus[:n_queries] + noise * jax.random.normal(
        kq, (n_queries,) + dims)
    return corpus, queries


def cp_corpus_and_queries(n_corpus: int, n_queries: int, dims=DIMS,
                          rank: int = 3, seed: int = 0, noise: float = 0.1):
    """The CP-format twin of ``corpus_and_queries``: rank-``rank``
    ``CPTensor`` items, each factor a (n, d, rank) leaf, with queries
    perturbed off the first items' factors."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 2 * len(dims))
    factors = tuple(jax.random.normal(k, (n_corpus, d, rank)) / d ** 0.5
                    for k, d in zip(keys, dims))
    queries = tuple(
        f[:n_queries] + noise * jax.random.normal(k, f[:n_queries].shape)
        for f, k in zip(factors, keys[len(dims):]))
    return CPTensor(factors=factors), CPTensor(factors=queries)


def assert_query_path(index) -> None:
    """Shard-native coverage must fail loudly: whenever the platform has
    enough devices for every shard, the shard_map program MUST be the one
    that executes — a silent vmap fallback is a bug, not a degradation."""
    want = "shard_map" if len(jax.devices()) >= index.shards else "vmap"
    assert index.query_path == want, (
        f"expected the {want} query path on {len(jax.devices())} devices "
        f"with S={index.shards}, got {index.query_path}")
