"""A configuration's deployment, made from the seed: its data, the hash
family's random parameters, the planted query pool, and the served index.

The data and the family's parameters are made on the device in one jitted
call, in float32 as they are served; the service under test then gets
them through its public constructors. Host copies of the same arrays feed
the plain reference (``reference.py``), which imports nothing of the
program.

Data kinds (``config["data"]["kind"]``):

* ``clustered_dense``: Gaussian clusters of ``cluster_size`` items around
  standard-normal centers, ``cluster_spread`` std per coordinate, each
  item viewed with shape ``dims``; planted queries are corpus members
  plus ``query_noise`` std of noise.
* ``cp_random``: rank-``rank`` CP tensors of shape ``dims``, each factor
  entry N(0, 1/d); planted queries perturb every factor entry by
  ``query_noise`` std.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import shutil
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


def jax_key(seed: int, *salt: int) -> jax.Array:
    """A PRNG key for any non-negative seed (the driver's exceed 32
    bits), mixed with ``salt`` so each use draws its own stream."""
    word = np.random.SeedSequence([int(seed), *salt]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def host_rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *salt])


def _family_arrays(key, kind: str, dims, rank: int, codes: int, tables: int,
                   width: float):
    if kind != "cp-e2lsh":
        raise ValueError(f"family kind {kind!r} is not made by the bench yet")
    h = codes * tables
    keys = jax.random.split(key, len(dims) + 1)
    factors = tuple(
        2.0 * jax.random.bernoulli(k, 0.5, (h, d, rank)).astype(jnp.float32)
        - 1.0 for k, d in zip(keys[:-1], dims))
    offsets = jax.random.uniform(keys[-1], (h,), jnp.float32, 0.0, width)
    return factors, offsets


@functools.partial(jax.jit, static_argnames=("data", "fam"))
def _make(key, data, fam):
    """Corpus, cluster centers (dense only) and family parameters."""
    k_data, k_fam = jax.random.split(key)
    d = dict(data)
    dims = tuple(d["dims"])
    n = d["n"]
    if d["kind"] == "clustered_dense":
        size = math.prod(dims)
        kc, ka, kx = jax.random.split(k_data, 3)
        centers = jax.random.normal(kc, (max(n // d["cluster_size"], 1),
                                         size))
        assign = jax.random.randint(ka, (n,), 0, centers.shape[0])
        x = centers[assign] + d["cluster_spread"] * jax.random.normal(
            kx, (n, size))
        corpus = x.reshape((n,) + dims)
    elif d["kind"] == "cp_random":
        keys = jax.random.split(k_data, len(dims))
        corpus = tuple(jax.random.normal(k, (n, dm, d["rank"]))
                       / math.sqrt(dm) for k, dm in zip(keys, dims))
        centers = None
    else:
        raise ValueError(f"unknown data kind {d['kind']!r}")
    f = dict(fam)
    factors, offsets = _family_arrays(
        k_fam, f["kind"], tuple(f.get("dims", dims)), f["rank"],
        f["num_codes"], f["num_tables"], f["bucket_width"])
    return corpus, centers, factors, offsets


@functools.partial(jax.jit, static_argnames=("noise",))
def _plant(key, corpus, ids, *, noise: float):
    rows = jax.tree.map(lambda a: a[ids], corpus)
    leaves, treedef = jax.tree.flatten(rows)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        a + noise * jax.random.normal(k, a.shape, a.dtype)
        for k, a in zip(keys, leaves)])


@functools.partial(jax.jit, static_argnames=("n", "spread", "dims"))
def fresh_items(key, centers, *, n: int, spread: float, dims):
    """``n`` new dense items drawn around the corpus's own centers."""
    ka, kx = jax.random.split(key)
    assign = jax.random.randint(ka, (n,), 0, centers.shape[0])
    x = centers[assign] + spread * jax.random.normal(
        kx, (n, centers.shape[1]))
    return x.reshape((n,) + dims)


def _frozen(d: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


@dataclasses.dataclass
class Deployment:
    config: dict
    corpus: object            # device arrays: (n, *dims) or CP factor tuple
    centers: object           # dense cluster centers (device) or None
    family: object            # the program's LSHFamily
    host_family: reference.HostFamily
    service: object = None

    @property
    def n(self) -> int:
        return self.config["data"]["n"]

    def items_host(self, corpus=None):
        """Host float32 copy of device items, in the reference's layout."""
        corpus = self.corpus if corpus is None else corpus
        if hasattr(corpus, "factors"):
            return tuple(np.asarray(f) for f in corpus.factors)
        corpus = np.asarray(corpus)
        return corpus.reshape(corpus.shape[0], -1)

    def program_items(self, host_items):
        """Reference-layout host items -> the program's input format."""
        from repro.core.tensor_formats import CPTensor
        if isinstance(host_items, tuple):
            return CPTensor(factors=tuple(host_items), scale=1.0)
        return host_items.reshape(
            (host_items.shape[0],) + tuple(self.config["data"]["dims"]))


def make(config: dict, seed: int) -> Deployment:
    """Data and family parameters on the device, from the seed."""
    from repro.core.lsh import LSHFamily
    from repro.core.projections import CPProjection
    from repro.core.tensor_formats import CPTensor

    data, fam = config["data"], config["family"]
    corpus, centers, factors, offsets = jax.block_until_ready(
        _make(jax_key(seed, 0), _frozen(data), _frozen(fam)))
    rank = fam["rank"]
    family = LSHFamily(
        projection=CPProjection(factors=factors, scale=1.0 / math.sqrt(rank)),
        offsets=offsets, kind=fam["kind"], num_codes=fam["num_codes"],
        num_tables=fam["num_tables"], bucket_width=float(fam["bucket_width"]),
        hash_backend="auto")
    if data["kind"] == "cp_random":
        corpus = CPTensor(factors=tuple(corpus), scale=1.0)
    host = reference.HostFamily(
        factors=tuple(np.asarray(f, np.float64) for f in factors),
        scale=1.0 / math.sqrt(rank),
        offsets=np.asarray(offsets, np.float64),
        num_codes=fam["num_codes"], num_tables=fam["num_tables"],
        width=float(fam["bucket_width"]),
        mults=reference.universal_mults(config["index"]["mults_seed"],
                                        fam["num_codes"]))
    return Deployment(config=config, corpus=corpus, centers=centers,
                      family=family, host_family=host)


def query_pool(dep: Deployment, seed: int, size: int):
    """(planted ids, host queries in the program's format): ``size``
    distinct corpus members plus noise, held on the host as a client
    would hold them."""
    data = dep.config["data"]
    ids = host_rng(seed, 1).choice(dep.n, size=size, replace=False)
    corpus = (dep.corpus.factors if hasattr(dep.corpus, "factors")
              else dep.corpus)
    q = _plant(jax_key(seed, 1), corpus, jnp.asarray(ids),
               noise=float(data["query_noise"]))
    q = jax.tree.map(np.asarray, q)
    if isinstance(q, (tuple, list)):
        from repro.core.tensor_formats import CPTensor
        q = CPTensor(factors=tuple(q), scale=1.0)
    return ids, q


def serve(dep: Deployment, durable_dir: Path | None = None):
    """Build the served index over the corpus: ``LSHService`` or, with a
    directory, ``DurableLSHService`` (WAL committed before every ack)."""
    from repro.serving.durability import DurableLSHService
    from repro.serving.lsh_service import LSHService

    cfg = dep.config
    kw = dict(metric=cfg["metric"], bucket_cap=cfg["index"]["bucket_cap"],
              max_deltas=cfg["index"]["max_deltas"])
    if durable_dir is None:
        svc = LSHService(dep.family, **kw)
    else:
        shutil.rmtree(durable_dir, ignore_errors=True)
        svc = DurableLSHService(
            dep.family, str(durable_dir),
            snapshot_every=cfg["durability"]["snapshot_every"], **kw)
    svc.build(dep.corpus, batch_size=cfg["index"]["build_batch"])
    jax.block_until_ready(svc.index.store.view.all_arrays)
    dep.service = svc
    return svc


def rows(tree, idx):
    """Host rows of a host query pool (any format)."""
    return jax.tree.map(lambda a: a[idx], tree)
