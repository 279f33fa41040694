"""Inner products between dense / CP / TT tensors with the paper's costs.

The LSH hash code (Definitions 10-13) is a discretization of <P, X> where P
is a CP- or TT-Rademacher projection tensor. The whole efficiency claim of the
paper rests on evaluating <P, X> *without reshaping X to a d^N vector*:

  <CP(R^), CP(R)>  : O(N d max{R,R^}^2)   — per-mode Gram matrices, Hadamard
  <CP(R^), TT(R)>  : O(N d max{R,R^}^3)   — chain with a (R^ x r) state
  <TT(R^), TT(R)>  : O(N d max{R,R^}^3)   — chain with a (r^ x r) state
  <dense,  CP(R)>  : O(R d^N)             — mode-by-mode contraction
  <dense,  TT(R)>  : O(R^2 d^N)           — mode-by-mode contraction
  <dense,  dense>  : O(d^N)               — the naive-method primitive

(paper Remarks 1-6 and Tables 1-2). All functions are jit-compatible and
dispatch via `inner(x, y)`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.tensor_formats import CPTensor, TTTensor

# Scores are exact float32 on every backend. On TPU an f32 matmul at the
# default precision rounds its operands to bfloat16, which in the expanded
# ||X||^2 + ||Y||^2 - 2<X,Y> distance leaves near neighbours with no correct
# digit; HIGHEST keeps f32 there and changes nothing on CPU.
_EXACT = jax.lax.Precision.HIGHEST


def inner_dense_dense(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.vdot(x, y, precision=_EXACT)


def inner_cp_cp(x: CPTensor, y: CPTensor) -> jax.Array:
    """<X, Y> for two CP tensors: sum of Hadamard product of per-mode Grams.

    <X, Y> = sx*sy * sum_{r,q} prod_n (A_x^(n)T A_y^(n))[r, q]
    Cost: N matmuls of (R^ x d)(d x R) -> O(N d R^ R).
    """
    h = None
    for fx, fy in zip(x.factors, y.factors):
        g = jnp.matmul(fx.T, fy, precision=_EXACT)  # (R^, R)
        h = g if h is None else h * g
    return (x.scale * y.scale) * jnp.sum(h)


def inner_tt_tt(x: TTTensor, y: TTTensor) -> jax.Array:
    """<X, Y> for two TT tensors via the transfer-matrix chain.

    State S in R^{r^_{n} x r_{n}}; per mode: S' = sum_i Gx[:,i,:]^T S Gy[:,i,:],
    computed as einsum('ab,aic,bid->cd'). Cost O(N d max{R^,R}^3).
    """
    s = jnp.ones((1, 1), x.cores[0].dtype)
    for gx, gy in zip(x.cores, y.cores):
        s = jnp.einsum("ab,aic,bid->cd", s, gx, gy, precision=_EXACT)
    return (x.scale * y.scale) * s.reshape(())


def inner_cp_tt(x: CPTensor, y: TTTensor) -> jax.Array:
    """<X, Y> for X in CP format and Y in TT format.

    For each CP rank r the rank-1 component contracts through the TT chain;
    batched over r with a (R^ x r_n) state. Cost O(N d max{R^,R}^3) — matches
    the paper's CP-E2LSH-on-TT-input / TT-E2LSH-on-CP-input complexity.
    """
    rank = x.rank
    s = jnp.ones((rank, 1), x.factors[0].dtype)
    for a, g in zip(x.factors, y.cores):
        # s: (R^, r_prev), g: (r_prev, d, r_next), a: (d, R^)
        s = jnp.einsum("ra,aib,ir->rb", s, g, a, precision=_EXACT)
    return (x.scale * y.scale) * jnp.sum(s)


def inner_dense_cp(x: jax.Array, y: CPTensor) -> jax.Array:
    """<X, Y> for dense X, CP Y: contract one mode at a time, keep rank axis.

    Cost O(R d^N) and O(d^{N-1} R) intermediate memory — never materializes
    the d^N projection vector of the naive method.
    """
    t = jnp.tensordot(y.factors[0], x, axes=(0, 0),
                      precision=_EXACT)  # (R, d2, ..., dN)
    for f in y.factors[1:]:
        # t: (R, d_k, rest...), f: (d_k, R) -> diagonal in R
        t = jnp.einsum("ri...,ir->r...", t, f, precision=_EXACT)
    return y.scale * jnp.sum(t)


def inner_dense_tt(x: jax.Array, y: TTTensor) -> jax.Array:
    """<X, Y> for dense X, TT Y: sweep cores left to right. Cost O(R^2 d^N)."""
    g0 = y.cores[0]  # (1, d1, r1)
    t = jnp.tensordot(g0[0], x, axes=(0, 0),
                      precision=_EXACT)  # (r1, d2, ..., dN)
    for core in y.cores[1:]:
        # t: (r_prev, d_k, rest...), core: (r_prev, d_k, r_next)
        t = jnp.einsum("ai...,air->r...", t, core, precision=_EXACT)
    return y.scale * t.reshape(())


def inner(x, y) -> jax.Array:
    """Polymorphic <x, y> over {dense, CP, TT} x {dense, CP, TT}."""
    if isinstance(x, CPTensor):
        if isinstance(y, CPTensor):
            return inner_cp_cp(x, y)
        if isinstance(y, TTTensor):
            return inner_cp_tt(x, y)
        return inner_dense_cp(y, x)
    if isinstance(x, TTTensor):
        if isinstance(y, CPTensor):
            return inner_cp_tt(y, x)
        if isinstance(y, TTTensor):
            return inner_tt_tt(x, y)
        return inner_dense_tt(y, x)
    if isinstance(y, CPTensor):
        return inner_dense_cp(x, y)
    if isinstance(y, TTTensor):
        return inner_dense_tt(x, y)
    return inner_dense_dense(x, y)


def norm(x) -> jax.Array:
    """Frobenius norm ||X||_F computed in-format (paper §3.3)."""
    return jnp.sqrt(jnp.maximum(inner(x, x), 0.0))


def distance(x, y) -> jax.Array:
    """Euclidean distance ||X - Y||_F (paper Eq. 3.5) computed in-format via
    ||X||^2 + ||Y||^2 - 2<X,Y> (no densification)."""
    d2 = inner(x, x) + inner(y, y) - 2.0 * inner(x, y)
    return jnp.sqrt(jnp.maximum(d2, 0.0))


def cosine_similarity(x, y) -> jax.Array:
    """cos(theta) = <X,Y> / (||X||_F ||Y||_F) (paper Eq. 3.6), in-format."""
    return inner(x, y) / (norm(x) * norm(y))
