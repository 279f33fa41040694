"""The paper's four LSH families (Definitions 10-13) + naive baselines.

  CP-E2LSH (Def. 10):  g(X)  = floor((<P, X> + b) / w),  P ~ CP_Rad(R)
  TT-E2LSH (Def. 11):  g~(X) = floor((<T, X> + b) / w),  T ~ TT_Rad(R)
  CP-SRP   (Def. 12):  h(X)  = sign(<P, X>),             P ~ CP_Rad(R)
  TT-SRP   (Def. 13):  h~(X) = sign(<T, X>),             T ~ TT_Rad(R)

plus the naive baselines the paper compares against:

  E2LSH (Datar et al. [11], Def. 3): dense Gaussian projection + floor
  SRP   (Charikar [6], Def. 2):      dense Gaussian projection + sign

A family carries K x L hash functions (K concatenated codes per table,
L tables — the standard (K, L) LSH amplification).

Hashing is batch-native: ``hash_batch`` maps a (B, ...) input batch to
(B, L, K) integer codes as ONE fused program — batched projection
contractions -> discretization (floor-quantize / sign) — and ``hash_keys``
additionally fuses the uint32 radix code-combine, going straight to the
(B, L) bucket keys the indexes probe with. ``hash(x)`` is the batch-of-1
case. Which backend evaluates the fused program is controlled by the
``hash_backend`` knob:

  * ``"xla"``    — the explicit batched einsum contractions of
                   ``repro.core.projections.project_batch`` plus the jnp
                   discretize/combine tail, fused by jit.
  * ``"pallas"`` — the batch-native Pallas kernels in ``repro.kernels``
                   (CP Gram / TT chain with the discretize + combine
                   epilogues fused in-kernel), for CP-format inputs under
                   CP projections and TT-format inputs under TT
                   projections with equal mode dims; other combinations
                   fall back to the XLA path. Codes are bit-identical
                   across backends (pinned by tests/test_hash_backends.py).
  * ``"auto"``   — the ``REPRO_HASH_BACKEND`` env var if set (read at
                   trace time), else xla on every platform. The TPU v5e
                   compiler refuses the hash kernels (an (L, K) offsets
                   block off the (8, 128) tiling, a ``scatter`` in the TT
                   chain start, a uint32 reduction, and 4-D/5-D shape
                   casts around the batched dot), so ``auto`` stays on xla
                   until a kernel compiles and beats it in a benchmark
                   cell. An explicit ``"pallas"`` still runs the kernel,
                   and its compiler error reaches the caller.
"""

from __future__ import annotations

import dataclasses
import math
import os
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projections as proj_lib
from repro.core.projections import (CPProjection, DenseProjection, Projection,
                                    TTProjection)
from repro.core.tensor_formats import CPTensor, TTTensor

E2LSH_KINDS = ("cp-e2lsh", "tt-e2lsh", "e2lsh")
SRP_KINDS = ("cp-srp", "tt-srp", "srp")
ALL_KINDS = E2LSH_KINDS + SRP_KINDS
HASH_BACKENDS = ("auto", "xla", "pallas")


def e2lsh_discretize(values: jax.Array, b: jax.Array, w: float) -> jax.Array:
    """floor((v + b) / w) -> int32 hashcode (paper Eq. 3.3 / 4.1 / 4.20)."""
    return jnp.floor((values + b) / w).astype(jnp.int32)


def srp_discretize(values: jax.Array) -> jax.Array:
    """sign(v) in {0, 1} (paper Eq. 3.1 / 4.34 / 4.61): 1 iff v > 0."""
    return (values > 0).astype(jnp.int32)


def pack_bits(bits: jax.Array) -> jax.Array:
    """Pack {0,1} int codes along the last axis into uint32 words (pad 0)."""
    k = bits.shape[-1]
    pad = (-k) % 32
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), bits.dtype)], axis=-1)
    words = bits.reshape(bits.shape[:-1] + (-1, 32)).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(words << shifts, axis=-1, dtype=jnp.uint32)


def unpack_bits(words: jax.Array, k: int) -> jax.Array:
    """Inverse of pack_bits, truncated back to K bits."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(words.shape[:-1] + (-1,))[..., :k].astype(jnp.int32)


def _combine_codes(codes, mults):
    """(..., L, K) int codes -> (..., L) uint32 bucket keys.

    sum_k codes[k] * mults[k] in uint32 arithmetic. Distinct per-position
    multipliers make the key permutation-sensitive; the mod-2^32 wraparound
    is identical between numpy (host tables) and jnp (device tables), and
    int32 codes of any magnitude cast to uint32 without overflow errors.
    """
    xp = jnp if isinstance(codes, jax.Array) else np
    prods = codes.astype(xp.uint32) * xp.asarray(mults).astype(xp.uint32)
    return prods.sum(axis=-1, dtype=xp.uint32)


def make_mults(seed: int, num_codes: int) -> np.ndarray:
    """Per-position odd uint32 multipliers for the universal bucket hash."""
    rng = np.random.default_rng(seed)
    return rng.integers(0, 1 << 32, size=(num_codes,), dtype=np.uint32) | 1


def _batched_dims(xs) -> tuple[int, ...]:
    """Mode dims of a batched input pytree (leading B axis on every leaf)."""
    if isinstance(xs, CPTensor):
        return tuple(f.shape[-2] for f in xs.factors)
    if isinstance(xs, TTTensor):
        return tuple(c.shape[-2] for c in xs.cores)
    return tuple(xs.shape[1:])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LSHFamily:
    """A (K, L)-amplified LSH family of one of the six kinds.

    The underlying projection holds K*L stacked projection tensors; `offsets`
    (E2LSH only) holds the b ~ U[0, w] per hash function. ``hash_backend``
    picks the fused-hash evaluation path (see the module docstring).
    """

    projection: Projection
    offsets: jax.Array | None            # (L*K,) or None for SRP kinds
    kind: str = dataclasses.field(metadata=dict(static=True))
    num_codes: int = dataclasses.field(metadata=dict(static=True))    # K
    num_tables: int = dataclasses.field(metadata=dict(static=True))   # L
    bucket_width: float = dataclasses.field(default=0.0, metadata=dict(static=True))
    hash_backend: str = dataclasses.field(default="auto",
                                          metadata=dict(static=True))

    # -- backend dispatch ----------------------------------------------------

    def resolved_backend(self) -> str:
        """'xla' or 'pallas': the explicit knob, else the REPRO_HASH_BACKEND
        env var (read at trace time), else xla on every platform (see the
        module docstring for why not pallas on TPU)."""
        b = self.hash_backend
        if b == "auto":
            b = os.environ.get("REPRO_HASH_BACKEND", "").strip().lower() or "auto"
        if b == "auto":
            b = "xla"
        if b not in ("xla", "pallas"):
            raise ValueError(
                f"hash_backend must be one of {HASH_BACKENDS}, got {b!r}")
        return b

    def _kernel_supported(self, xs) -> bool:
        """The Pallas kernels cover CP-format inputs under CP projections and
        TT-format inputs under TT projections, with equal mode dims (the
        stacked kernel layout); everything else serves through XLA."""
        p = self.projection
        if not ((isinstance(p, CPProjection) and isinstance(xs, CPTensor)) or
                (isinstance(p, TTProjection) and isinstance(xs, TTTensor))):
            return False
        dims = p.dims
        return len(set(dims)) == 1 and _batched_dims(xs) == tuple(dims)

    def _use_pallas(self, xs) -> bool:
        return self.resolved_backend() == "pallas" and self._kernel_supported(xs)

    # -- fused batch-native hashing ------------------------------------------

    def _discretize(self, values: jax.Array) -> jax.Array:
        """(B, L*K) raw values -> (B, L, K) int32 codes."""
        if self.kind in E2LSH_KINDS:
            codes = e2lsh_discretize(values, self.offsets, self.bucket_width)
        else:
            codes = srp_discretize(values)
        return codes.reshape(values.shape[0], self.num_tables, self.num_codes)

    def raw_projections(self, x) -> jax.Array:
        """(L*K,) raw <P_k, X> values."""
        return proj_lib.project(self.projection, x)

    def hash_batch_aux(self, xs) -> tuple[jax.Array, jax.Array]:
        """(codes (B, L, K) int32, aux (B, L, K) float32) for multi-probe.

        ``aux`` is the per-code perturbation evidence the query-directed
        expansion in ``repro.core.probing`` ranks by: the floor residual
        (v + b) / w - floor((v + b) / w) in [0, 1) for E2LSH kinds, and the
        raw projection value v (sign = the bit, |v| = the margin) for SRP
        kinds. Always evaluated through the XLA projection path — codes are
        pinned bit-identical across hash backends (tests/test_hash_backends
        .py), so the expansion composes with any ``hash_backend``.
        """
        values = proj_lib.project_batch(self.projection, xs)
        codes = self._discretize(values)
        if self.kind in E2LSH_KINDS:
            t = (values + self.offsets) / self.bucket_width
            aux = t.reshape(codes.shape) - codes.astype(values.dtype)
        else:
            aux = values.reshape(codes.shape)
        return codes, aux

    def hash_batch(self, xs) -> jax.Array:
        """(B, L, K) int32 codes for a batch of tensors, as one fused
        projection -> discretize program (no per-example vmap)."""
        if self._use_pallas(xs):
            from repro.kernels import ops
            return ops.fused_hash(xs, self.projection, epilogue="codes",
                                  kind=self.kind, num_tables=self.num_tables,
                                  num_codes=self.num_codes,
                                  offsets=self.offsets, w=self.bucket_width)
        return self._discretize(proj_lib.project_batch(self.projection, xs))

    def hash_keys(self, xs, mults) -> jax.Array:
        """(B, L) uint32 bucket keys: projection -> discretize -> uint32
        radix combine, fused end to end. ``mults`` is the (K,) uint32
        multiplier vector of the universal bucket hash (see make_mults);
        bit-identical to ``_combine_codes(self.hash_batch(xs), mults)``."""
        if self._use_pallas(xs):
            from repro.kernels import ops
            return ops.fused_hash(xs, self.projection, epilogue="keys",
                                  kind=self.kind, num_tables=self.num_tables,
                                  num_codes=self.num_codes,
                                  offsets=self.offsets, w=self.bucket_width,
                                  mults=mults)
        return _combine_codes(self._discretize(
            proj_lib.project_batch(self.projection, xs)), mults)

    def hash_packed_batch(self, xs) -> jax.Array:
        """SRP only: (B, L, ceil(K/32)) uint32 packed signatures (sign +
        bit-pack fused)."""
        if self.kind not in SRP_KINDS:
            raise ValueError("hash_packed is defined for SRP kinds only")
        if self._use_pallas(xs):
            from repro.kernels import ops
            return ops.fused_hash(xs, self.projection, epilogue="packed",
                                  kind=self.kind, num_tables=self.num_tables,
                                  num_codes=self.num_codes)
        return pack_bits(self._discretize(
            proj_lib.project_batch(self.projection, xs)))

    def hash(self, x) -> jax.Array:
        """Integer hashcodes, shape (L, K) — the batch-of-1 case."""
        return self.hash_batch(jax.tree.map(lambda a: a[None], x))[0]

    def hash_packed(self, x) -> jax.Array:
        """SRP only: (L, ceil(K/32)) uint32 packed signatures."""
        return self.hash_packed_batch(jax.tree.map(lambda a: a[None], x))[0]

    def storage_size(self) -> int:
        """Stored scalars for the projection parameters (paper Tables 1-2)."""
        return self.projection.storage_size()


def make_family(key: jax.Array, kind: str, dims: Sequence[int],
                num_codes: int = 8, num_tables: int = 1, rank: int = 4,
                bucket_width: float = 4.0, dist: str = "rademacher",
                hash_backend: str = "auto", dtype=jnp.float32) -> LSHFamily:
    """Construct any of the paper's families or the naive baselines.

    kind: 'cp-e2lsh' | 'tt-e2lsh' | 'cp-srp' | 'tt-srp' | 'e2lsh' | 'srp'.
    The naive kinds ('e2lsh', 'srp') always use Gaussian dense projections
    (Definitions 2-3); the tensorized kinds default to Rademacher entries
    (Definitions 6-7), with dist='gaussian' giving CP_N / TT_N variants.
    hash_backend: 'auto' | 'xla' | 'pallas' (see the module docstring).
    """
    if kind not in ALL_KINDS:
        raise ValueError(f"kind must be one of {ALL_KINDS}, got {kind!r}")
    if hash_backend not in HASH_BACKENDS:
        raise ValueError(
            f"hash_backend must be one of {HASH_BACKENDS}, got {hash_backend!r}")
    total = num_codes * num_tables
    kp, kb = jax.random.split(key)
    if kind.startswith("cp-"):
        p = proj_lib.sample_cp_projection(kp, total, dims, rank, dist=dist, dtype=dtype)
    elif kind.startswith("tt-"):
        p = proj_lib.sample_tt_projection(kp, total, dims, rank, dist=dist, dtype=dtype)
    else:
        p = proj_lib.sample_dense_projection(kp, total, dims, dist="gaussian", dtype=dtype)
    offsets = None
    if kind in E2LSH_KINDS:
        offsets = jax.random.uniform(kb, (total,), dtype, 0.0, bucket_width)
    return LSHFamily(projection=p, offsets=offsets, kind=kind,
                     num_codes=num_codes, num_tables=num_tables,
                     bucket_width=float(bucket_width),
                     hash_backend=hash_backend)


def naive_storage_size(dims: Sequence[int], num_codes: int, num_tables: int) -> int:
    """O(K d^N) scalars the naive method stores (paper Tables 1-2)."""
    return num_codes * num_tables * int(math.prod(dims))
