"""Pallas TPU kernel: batch-native fused TT x TT hashing (transfer-matrix
chain).

For a (B,)-batch of TT inputs X_z and L*K stacked TT projection tensors
T_{l,k}, computes in one kernel

    v[z, l, k] = scale * e_0^T ( prod_n sum_i Gx_z^(n)[:,i,:] (x)
                                 Gp_{l,k}^(n)[:,i,:] ) e_0

via the standard chain: state S in R^{Rx x Rp} per (input, hash) pair,
S <- sum_i Gx[:,i,:]^T S Gp[:,i,:] per mode — the hash hot loop of
TT-E2LSH / TT-SRP (Definitions 11, 13), O(B L K N d max{Rx,Rp}^3) FLOPs —
plus the fused discretization epilogue (floor-quantize / sign / uint32
radix combine / bit-pack, see kernels/epilogues.py) so raw projections
never round-trip through HBM.

TPU mapping
-----------
* Boundary cores are zero-padded to rank R by ops.py and the chain starts
  from S0 = e_00, so every mode is a uniform (R, d, R) block — one
  BlockSpec, no boundary specialization inside the kernel.
* The running states S_{z,t} live in one VMEM scratch (BBLK, T, Rx, Rp)
  across the whole mode loop; per mode the update is two MXU matmuls:
      tmp(z,t; b, i c) = S^T(z,t; b,a) @ Gx_z(a, i c)
      S'(t; z,c, e)    = tmp(z,t; (b i), c)^T @ Gp_t((b i), e)
  batched over the (B-block, table-block) pair. Nothing but the final
  (BBLK, T) values sees the epilogue; only its output leaves VMEM.
* Mode loop is a static unroll (N is small); (B-blocks, table-blocks) form
  the grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogues import (apply_epilogue, default_interpret,
                                     out_struct)


def _tt_hash_kernel(x_ref, p_ref, b_ref, m_ref, o_ref, s_ref, *,
                    n_modes: int, epilogue: str, w: float, scale: float):
    # x_ref: (BBLK, N, Rx, d, Rx); p_ref: (N, LBLK, K, Rp, d, Rp)
    # b_ref: (LBLK, K) f32; m_ref: (1, K) u32
    # s_ref: VMEM scratch (BBLK, T, Rx, Rp), T = LBLK*K
    bb, t, rx, rp = s_ref.shape
    _, lb, k, _, d, _ = p_ref.shape
    s_ref[...] = jnp.zeros((bb, t, rx, rp), jnp.float32).at[:, :, 0, 0].set(1.0)
    for m in range(n_modes):  # static unroll
        gx = x_ref[:, m].reshape(bb, rx, d * rx)          # (z; a, i*c)
        gp = p_ref[m].reshape(t, rp * d, rp)              # (t; b*i, e)
        s = s_ref[...]                                    # (z, t, a, b)
        # tmp[z, t, b, i*c] = sum_a s[z, t, a, b] * gx[z, a, i*c]
        tmp = jax.lax.dot_general(
            jnp.swapaxes(s, 2, 3),                        # (z, t, b, a)
            gx,
            dimension_numbers=(((3,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                 # (z, t, b, i*c)
        tmp = tmp.reshape(bb, t, rp * d, rx)              # (z, t, b*i, c)
        # s'[z, t, c, e] = sum_{b,i} tmp[z, t, (b i), c] * gp[t, (b i), e]
        s_new = jax.lax.dot_general(
            tmp, gp,
            dimension_numbers=(((2,), (1,)), ((1,), (0,))),
            preferred_element_type=jnp.float32,
        )                                                 # (t, z, c, e)
        s_ref[...] = jnp.swapaxes(s_new, 0, 1)
    v = scale * s_ref[:, :, 0, 0]                         # (BBLK, T)
    v = v.reshape(bb, lb, k)
    o_ref[...] = apply_epilogue(v, b_ref[...], m_ref[...],
                                epilogue=epilogue, w=w)


@functools.partial(jax.jit, static_argnames=("epilogue", "w", "scale",
                                             "block_b", "block_l", "interpret"))
def tt_inner_pallas(x_cores: jax.Array, p_cores: jax.Array,
                    offsets: jax.Array | None = None,
                    mults: jax.Array | None = None, *,
                    epilogue: str = "raw", w: float = 1.0, scale: float = 1.0,
                    block_b: int = 8, block_l: int = 1,
                    interpret: bool | None = None) -> jax.Array:
    """x_cores (B, N, Rx, d, Rx), p_cores (N, L, K, Rp, d, Rp) ->
    (B, L, K) values/codes, (B, L) keys or (B, L, K/32) packed words, per
    ``epilogue`` (see kernels/epilogues.py).

    Mode-0 cores must be zero-padded into row 0 (ops.py does this); padded
    B entries are all-zero cores giving exactly 0 raw values, and their
    outputs are sliced off. Requires B % block_b == 0, L % block_l == 0.
    """
    b, n, rx, d, _ = x_cores.shape
    _, l, k, rp, _, _ = p_cores.shape
    assert b % block_b == 0, (b, block_b)
    assert l % block_l == 0, (l, block_l)
    if offsets is None:
        offsets = jnp.zeros((l, k), jnp.float32)
    if mults is None:
        mults = jnp.zeros((1, k), jnp.uint32)
    out = out_struct(b, l, k, epilogue)
    if out.ndim == 3:
        out_spec = pl.BlockSpec((block_b, block_l, out.shape[-1]),
                                lambda i, j: (i, j, 0))
    else:
        out_spec = pl.BlockSpec((block_b, block_l), lambda i, j: (i, j))
    grid = (b // block_b, l // block_l)
    kernel = functools.partial(_tt_hash_kernel, n_modes=n, epilogue=epilogue,
                               w=w, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n, rx, d, rx), lambda i, j: (i, 0, 0, 0, 0)),
            pl.BlockSpec((n, block_l, k, rp, d, rp),
                         lambda i, j: (0, j, 0, 0, 0, 0)),
            pl.BlockSpec((block_l, k), lambda i, j: (j, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((block_b, block_l * k, rx, rp),
                                   jnp.float32)],
        interpret=default_interpret(interpret),
    )(x_cores, p_cores, offsets, mults)
