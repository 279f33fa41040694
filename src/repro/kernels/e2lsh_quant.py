"""Pallas TPU kernel: E2LSH discretization — floor((v + b) / w) -> int32.

g(X) = floor((<P, X> + b) / w) (paper Definitions 10-11, Eq. 4.1/4.20).
A trivial VPU kernel fused at the tail of the projection so the float
values stay in VMEM; w is folded in as a compile-time reciprocal multiply
(no divide unit pressure). Grid over B-blocks; offsets broadcast.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.epilogues import default_interpret


def _e2lsh_quant_kernel(v_ref, b_ref, o_ref, *, inv_w: float):
    o_ref[...] = jnp.floor((v_ref[...] + b_ref[...]) * inv_w).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("w", "block_b", "interpret"))
def e2lsh_quant_pallas(values: jax.Array, offsets: jax.Array, w: float,
                       block_b: int = 8,
                       interpret: bool | None = None) -> jax.Array:
    """values (B, K), offsets (K,), bucket width w -> int32 (B, K)."""
    b, k = values.shape
    assert b % block_b == 0, (b, block_b)
    grid = (b // block_b,)
    kernel = functools.partial(_e2lsh_quant_kernel, inv_w=1.0 / w)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k), jnp.int32),
        interpret=default_interpret(interpret),
    )(values, offsets[None, :])
