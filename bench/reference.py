"""The plain reference: the served index's semantics in NumPy, written
from the method's definition and sharing no code with the program.

Semantics (CP-E2LSH, Definition 10 of arXiv:2402.07189, with (K, L)
amplification, a capped bucket window and query-directed multi-probe):

* hash value h(X) = <P_h, X>, P_h a rank-R CP tensor with +-1 factors
  scaled by 1/sqrt(R); code = floor((h(X) + b_h) / w); the K codes of a
  table combine into one uint32 key, sum_k code_k * mult_k mod 2^32,
  with the odd multipliers ``universal_mults`` draws;
* the store is a run of segments (a base, then one delta per insert
  since the last fold), each a run of arrival ids; table l's bucket of
  key k in a segment lists the segment's items in arrival order, and a
  probe reads the first ``min(cap, segment size)`` of them that are live
  (tombstoned items are skipped, not counted); the live items are a run
  of arrival ids, and an item's effective id is its rank among them;
* T probes per table: the base key, then the T-1 perturbations with the
  smallest boundary distance, singles (+1: (1-r)^2, -1: r^2, r the floor
  residual) and pairs on distinct codes (the sum), ranked stably in that
  order;
* candidates = the distinct items of every window; answer = the ``topk``
  candidates by exact distance, ties to the lower id.

Precision is explicit. The hash operands are rounded to the precision the
configuration states for them (``hash_operands``: the chip's default
matrix precision rounds float32 operands to bfloat16), multiplied exactly
and summed in float32. Scores are exact (float64) for the reference, or
computed from operands rounded to a lower precision for the control.
"""

from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

_DTYPES = {"float64": np.float64, "float32": np.float32,
           "bfloat16": ml_dtypes.bfloat16,
           "float8_e4m3fn": ml_dtypes.float8_e4m3fn}
BLOCK = 1 << 16


def rounded(a, precision: str) -> np.ndarray:
    """``a`` rounded to ``precision`` (round to nearest even), as float64."""
    a = np.asarray(a)
    if precision == "float64":
        return a.astype(np.float64)
    return a.astype(np.float32).astype(_DTYPES[precision]).astype(np.float64)


def universal_mults(seed: int, num_codes: int) -> np.ndarray:
    """The index's per-position odd multipliers, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 32, size=(num_codes,), dtype=np.uint32) | 1


@dataclasses.dataclass(frozen=True)
class HostFamily:
    factors: tuple            # per mode (H, d_m, R) float64 (+-1)
    scale: float              # 1/sqrt(R)
    offsets: np.ndarray       # (H,) float64, float32 values in [0, w)
    num_codes: int            # K
    num_tables: int           # L
    width: float              # w
    mults: np.ndarray         # (K,) uint32


def _count(items) -> int:
    return (items[0] if isinstance(items, tuple) else items).shape[0]


def _take(items, idx):
    if isinstance(items, tuple):
        return tuple(f[idx] for f in items)
    return items[idx]


def _values_fn(cp: bool):
    """One block of hash values, <P_h, X> without the 1/sqrt(R) scale, as
    a jitted program: plain contractions at the highest matrix precision,
    so rounded operands multiply exactly and sums are float32's."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def dense(x, p):                          # (z, D) x (H, D)
        return jnp.matmul(x, p.T, precision=hi)

    def in_format(xs, fs):                    # per mode (z, d, R^), (H, d, R)
        prod = None
        for a, f in zip(xs, fs):
            g = jnp.einsum("zir,hiq->zhrq", a, f, precision=hi)
            prod = g if prod is None else prod * g
        return prod.sum(axis=(2, 3))

    return jax.jit(in_format if cp else dense)


def project(fam: HostFamily, items, precision: str) -> np.ndarray:
    """(n, H) hash values <P_h, X> of dense rows (n, D) or CP factor
    tuples ((n, d_m, R^) per mode), operands rounded to ``precision``.

    The values are float32 sums of exact products of the rounded operands
    (computed on the default JAX device, in blocks of ``BLOCK`` rows);
    a value within float32's last bit of a bucket edge is rare, and the
    program's own sums are no finer."""
    h = fam.factors[0].shape[0]
    n = _count(items)
    fs = [rounded(f, precision) for f in fam.factors]
    cp = isinstance(items, tuple)
    if cp:
        weights = tuple(f.astype(np.float32) for f in fs)
        scale = fam.scale
    else:
        dense = fs[0]
        for f in fs[1:]:
            dense = np.einsum("h...r,hir->h...ir", dense, f)
        weights = (fam.scale * dense.sum(-1).reshape(h, -1)).astype(
            np.float32)                       # small integers / 2: exact
        scale = 1.0
    fn = _values_fn(cp)
    out = np.empty((n, h), np.float64)
    rows = min(BLOCK, n)
    for s in range(0, n, rows):
        stop = min(s + rows, n)
        block = _take(items, slice(s, stop))
        pad = rows - (stop - s)               # one block shape, one compile
        if cp:
            block = tuple(np.pad(rounded(a, precision).astype(np.float32),
                                 ((0, pad), (0, 0), (0, 0))) for a in block)
        else:
            block = np.pad(rounded(block, precision).astype(np.float32),
                           ((0, pad), (0, 0)))
        out[s:stop] = scale * np.asarray(fn(block, weights),
                                         np.float64)[:stop - s]
    return out


def codes_and_residuals(fam: HostFamily, values: np.ndarray):
    """(n, H) values -> (codes (n, L, K) int64, residuals (n, L, K))."""
    t = (values + fam.offsets) / fam.width
    codes = np.floor(t)
    shape = (values.shape[0], fam.num_tables, fam.num_codes)
    return codes.astype(np.int64).reshape(shape), (t - codes).reshape(shape)


def combine(fam: HostFamily, codes: np.ndarray) -> np.ndarray:
    """(..., K) int codes -> (...) uint32 keys, mod 2^32."""
    c = (codes % (1 << 32)).astype(np.uint32)
    return (c * fam.mults).sum(axis=-1, dtype=np.uint32)


def probe_keys(fam: HostFamily, codes, resid, probes: int) -> np.ndarray:
    """(Q, L, K) codes and residuals -> (Q, L, T) keys, base key first."""
    base = combine(fam, codes)
    if probes == 1:
        return base[..., None]
    k = fam.num_codes
    s1 = np.concatenate([(1.0 - resid) ** 2, resid ** 2], axis=-1)
    d1 = np.concatenate([fam.mults, np.uint32(0) - fam.mults])
    coord = np.concatenate([np.arange(k), np.arange(k)])
    pa, pb = np.triu_indices(2 * k, k=1)
    keep = coord[pa] != coord[pb]
    pa, pb = pa[keep], pb[keep]
    scores = np.concatenate([s1, s1[..., pa] + s1[..., pb]], axis=-1)
    deltas = np.concatenate([d1, d1[pa] + d1[pb]])
    order = np.argsort(scores, axis=-1, kind="stable")[..., :probes - 1]
    keys = np.concatenate([base[..., None], base[..., None] + deltas[order]],
                          axis=-1)
    if keys.shape[-1] < probes:
        pad = np.repeat(base[..., None], probes - keys.shape[-1], axis=-1)
        keys = np.concatenate([keys, pad], axis=-1)
    return keys


@dataclasses.dataclass(frozen=True)
class Store:
    """One state of the served store: its segments, each a run of arrival
    ids [a, b) (base first), and the live run [live_lo, live_hi)."""

    segments: tuple
    live_lo: int
    live_hi: int


class Index:
    """Every item's keys (arrival order) and, per segment of a store, its
    per-table buckets in arrival order."""

    def __init__(self, fam: HostFamily, items, hash_precision: str):
        self.fam, self.items, self.precision = fam, items, hash_precision
        codes, _ = codes_and_residuals(fam, project(fam, items,
                                                    hash_precision))
        self.keys = combine(fam, codes)                    # (n, L)
        self.whole = Store(((0, _count(items)),), 0, _count(items))
        self._tables = {}

    def tables(self, a: int, b: int) -> list:
        """Per table, (arrival ids in bucket order, sorted keys) of the
        segment [a, b)."""
        key = (a, b)
        if key not in self._tables:
            out = []
            for l in range(self.fam.num_tables):
                order = a + np.argsort(self.keys[a:b, l], kind="stable")
                out.append((order, self.keys[order, l]))
            self._tables[key] = out
        return self._tables[key]

    def query_keys(self, queries, probes: int) -> np.ndarray:
        """(Q, L, T) probe keys, base key first."""
        codes, resid = codes_and_residuals(
            self.fam, project(self.fam, queries, self.precision))
        return probe_keys(self.fam, codes, resid, probes)

    def window_ids(self, keys, cap: int, store: Store | None = None) -> list:
        """Per query's (L, T) keys, the sorted distinct arrival ids of its
        probe windows in ``store`` (default: every item, one segment)."""
        store = self.whole if store is None else store
        out = []
        for q in range(keys.shape[0]):
            ids = []
            for a, b in store.segments:
                seg_cap = min(cap, b - a)
                for l, (order, sk) in enumerate(self.tables(a, b)):
                    lo = np.searchsorted(sk, keys[q, l], side="left")
                    hi = np.searchsorted(sk, keys[q, l], side="right")
                    for s, e in zip(lo, hi):
                        w = order[s:e]
                        w = w[(w >= store.live_lo) & (w < store.live_hi)]
                        ids.append(w[:seg_cap])
            out.append(np.unique(np.concatenate(ids)) if ids
                       else np.zeros(0, np.int64))
        return out


def _batch_of(x, m: int):
    """One item repeated ``m`` times, as a batch."""
    if isinstance(x, tuple):
        return tuple(np.broadcast_to(f, (m,) + f.shape) for f in x)
    return np.broadcast_to(x, (m,) + x.shape)


def _inner_rows(xs, ys, precision: str) -> np.ndarray:
    """Row-wise <xs[i], ys[i]> of dense rows or CP factor tuples, from
    operands rounded to ``precision``, summed in float64."""
    if isinstance(xs, tuple):
        g = None
        for a, b in zip(xs, ys):
            m = np.einsum("mir,mis->mrs", rounded(a, precision),
                          rounded(b, precision))
            g = m if g is None else g * m
        return g.sum(axis=(1, 2))
    return np.einsum("md,md->m", rounded(xs, precision),
                     rounded(ys, precision))


def _densify(x) -> np.ndarray:
    """A batch of dense rows or CP factor tuples -> (m, D) float64."""
    if not isinstance(x, tuple):
        return np.asarray(x, np.float64)
    fs = [np.asarray(f, np.float64) for f in x]
    acc = fs[0]
    for f in fs[1:]:
        acc = np.einsum("m...r,mir->m...ir", acc, f)
    return acc.sum(-1).reshape(acc.shape[0], -1)


def sq_norms(items) -> np.ndarray:
    """||y||^2 of each item of a batch, exact."""
    return np.sum(_densify(items) ** 2, axis=-1)


def scores(metric: str, query, items, ids, precision: str = "float64"):
    """Distance (or cosine) of one query to ``items[ids]``: exact from
    float64 rows, or, for the control, through the expanded three-inner
    formula from operands rounded to ``precision``."""
    ys = _take(items, np.asarray(ids))
    m = _count(ys)
    if precision == "float64":
        q, y = _densify(_batch_of(query, 1))[0], _densify(ys)
        if metric == "euclidean":
            return np.linalg.norm(y - q, axis=-1)
        return (y @ q) / (np.linalg.norm(y, axis=-1) * np.linalg.norm(q))
    qb = _batch_of(query, m)
    qq = _inner_rows(qb, qb, precision)
    yy = _inner_rows(ys, ys, precision)
    qy = _inner_rows(qb, ys, precision)
    if metric == "euclidean":
        return np.sqrt(np.maximum(qq + yy - 2.0 * qy, 0.0))
    return qy / (np.sqrt(qq) * np.sqrt(yy))


def answer(index: Index, metric: str, queries, probes: int, cap: int,
           topk: int, score_precision: str = "float64",
           store: Store | None = None, keys=None):
    """The served answer of each query in ``store`` (default: every item
    live in one segment): (effective ids (Q, topk) with -1 fill, scores
    (Q, topk), n_cand (Q,)). ``keys`` are the queries' probe keys, where
    the caller has them already."""
    store = index.whole if store is None else store
    if keys is None:
        keys = index.query_keys(queries, probes)
    cands = index.window_ids(keys, cap, store)
    nq = len(cands)
    ids = np.full((nq, topk), -1, np.int64)
    out = np.full((nq, topk), np.inf if metric == "euclidean" else -np.inf)
    n_cand = np.array([c.size for c in cands])
    for q, cand in enumerate(cands):
        if cand.size == 0:
            continue
        s = scores(metric, _take(queries, q), index.items, cand,
                   score_precision)
        key = s if metric == "euclidean" else -s
        order = np.lexsort((cand, key))[:topk]
        ids[q, :order.size] = cand[order] - store.live_lo
        out[q, :order.size] = s[order]
    return ids, out, n_cand
