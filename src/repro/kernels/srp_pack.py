"""Pallas TPU kernel: SRP discretization — sign bits packed into uint32 words.

h(X) = sign(<P, X>) (paper Definitions 12-13). Given a (B, K) block of raw
projection values this kernel emits (B, K/32) packed signatures: bit j of
word w is 1 iff values[b, 32w + j] > 0 (little-endian within the word).

A pure VPU kernel: compare, shift, lane-reduce. Fused at the tail of the
projection matmuls so the (B, K) float values never reach HBM — only the
32x smaller signatures do. Grid over B-blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.epilogues import default_interpret


def _srp_pack_kernel(v_ref, o_ref):
    # v_ref: (BBLK, K); o_ref: (BBLK, K // 32)
    v = v_ref[...]
    bblk, k = v.shape
    bits = (v > 0).astype(jnp.uint32)
    words = bits.reshape(bblk, k // 32, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    o_ref[...] = jnp.sum(words << shifts, axis=-1, dtype=jnp.uint32)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def srp_pack_pallas(values: jax.Array, block_b: int = 8,
                    interpret: bool | None = None) -> jax.Array:
    """values (B, K) with K % 32 == 0, B % block_b == 0 -> uint32 (B, K/32).

    ops.py pads K to a multiple of 32 with -1.0 (sign bit 0) and B to a
    multiple of block_b, then slices the padding back off.
    """
    b, k = values.shape
    assert k % 32 == 0 and b % block_b == 0, (b, k)
    grid = (b // block_b,)
    return pl.pallas_call(
        _srp_pack_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_b, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_b, k // 32), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, k // 32), jnp.uint32),
        interpret=default_interpret(interpret),
    )(values)
