"""Pallas TPU kernel: batch-native fused CP x CP hashing.

For a (B,)-batch of CP inputs X_z and L*K stacked CP projection tensors
P_{l,k} (equal mode dims, stacked factors) this computes, in one kernel,

    v[z, l, k] = scale * sum_{r,q}  prod_n  (X_{z,n}^T P_{(l,k),n})[r, q]

and (optionally, see kernels/epilogues.py) the discretization tail fused in
the same program — E2LSH floor-quantize, SRP sign, the uint32 radix
code-combine down to (B, L) bucket keys, or the SRP bit-pack — so the raw
projection values never round-trip through HBM. This is the build/insert/
query hash hot path of CP-E2LSH / CP-SRP (paper Definitions 10, 12):
O(B L K N d Rx Rp) FLOPs total.

TPU mapping
-----------
* Grid over (B-blocks, table-blocks): each program owns BBLK inputs and
  LBLK tables x K codes = T projection tensors.
* Per mode n the Gram X_n^T P_n is ONE (BBLK*Rx, d) x (d, T*Rp) MXU matmul
  (dot_general with d contracted, everything else free); the cross-mode
  Hadamard product accumulates in a VMEM scratch so the (BBLK, Rx, T, Rp)
  intermediates never leave the core — an XLA-naive lowering writes N Gram
  tensors to HBM.
* The epilogue (discretize / combine / pack) runs on the VPU on the final
  (BBLK, T) block before the single output store.
* ops.py pads d to a multiple of 8 (zero rows are exact: they add 0 to the
  Gram) and B to the B-block (zero inputs, outputs sliced off).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.epilogues import (apply_epilogue, default_interpret,
                                     out_struct)


def _cp_hash_kernel(x_ref, p_ref, b_ref, m_ref, o_ref, acc_ref, *,
                    n_modes: int, epilogue: str, w: float, scale: float):
    # x_ref: (BBLK, N, d, Rx); p_ref: (N, LBLK, K, d, Rp)
    # b_ref: (LBLK, K) f32; m_ref: (1, K) u32
    # acc_ref: VMEM scratch (BBLK, Rx, LBLK*K, Rp)
    _, lb, k, d, rp = p_ref.shape
    for m in range(n_modes):  # static unroll over modes
        x_m = x_ref[:, m]                   # (BBLK, d, Rx)
        p_m = p_ref[m].reshape(lb * k, d, rp)
        # Gram: contract d -> (BBLK, Rx, T, Rp), one batched MXU matmul
        g = jax.lax.dot_general(
            x_m, p_m,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if m == 0:
            acc_ref[...] = g
        else:
            acc_ref[...] = acc_ref[...] * g
    v = scale * jnp.sum(acc_ref[...], axis=(1, 3))        # (BBLK, T)
    v = v.reshape(v.shape[0], lb, k)
    o_ref[...] = apply_epilogue(v, b_ref[...], m_ref[...],
                                epilogue=epilogue, w=w)


@functools.partial(jax.jit, static_argnames=("epilogue", "w", "scale",
                                             "block_b", "block_l", "interpret"))
def cp_gram_pallas(x_factors: jax.Array, p_factors: jax.Array,
                   offsets: jax.Array | None = None,
                   mults: jax.Array | None = None, *,
                   epilogue: str = "raw", w: float = 1.0, scale: float = 1.0,
                   block_b: int = 8, block_l: int = 1,
                   interpret: bool | None = None) -> jax.Array:
    """x_factors (B, N, d, Rx), p_factors (N, L, K, d, Rp) ->
    (B, L, K) values/codes, (B, L) keys or (B, L, K/32) packed words,
    per ``epilogue`` (see kernels/epilogues.py).

    Requires B % block_b == 0 and L % block_l == 0 (ops.py pads; padded
    inputs are zeros, whose outputs are sliced off). ``offsets`` (L, K) and
    ``mults`` (1, K) default to zeros when the epilogue ignores them.
    """
    b, n, d, rx = x_factors.shape
    _, l, k, _, rp = p_factors.shape
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
    else:  # (B, L) bucket keys
        out_spec = pl.BlockSpec((block_b, block_l), lambda i, j: (i, j))
    grid = (b // block_b, l // block_l)
    kernel = functools.partial(_cp_hash_kernel, n_modes=n, epilogue=epilogue,
                               w=w, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, n, d, rx), lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((n, block_l, k, d, rp), lambda i, j: (0, j, 0, 0, 0)),
            pl.BlockSpec((block_l, k), lambda i, j: (j, 0)),
            pl.BlockSpec((1, k), lambda i, j: (0, 0)),
        ],
        out_specs=out_spec,
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((block_b, rx, block_l * k, rp),
                                   jnp.float32)],
        interpret=default_interpret(interpret),
    )(x_factors, p_factors, offsets, mults)
