"""Pallas TPU kernels for the paper's compute hot-spots.

  cp_gram.py     batch-native fused CP x CP hashing (Gram + cross-mode
                 Hadamard + discretize/combine epilogues)
  tt_inner.py    batch-native fused TT x TT chain + the same epilogues
  epilogues.py   the shared in-kernel tails (E2LSH floor, SRP sign, uint32
                 radix code-combine, bit-pack)
  srp_pack.py    standalone sign + 32-lane bit pack (SRP tail)
  e2lsh_quant.py standalone floor((v + b)/w) quantization (E2LSH tail)
  ops.py         jit'd wrappers (padding/alignment, format adaptation) +
                 ``fused_hash``, the hash_backend='pallas' entry point of
                 the LSH families
  ref.py         pure-jnp oracles for allclose/bit-exact validation

Kernels are written for TPU (pl.pallas_call + BlockSpec VMEM tiling) and
are tested off TPU in the Pallas interpreter (``interpret=None`` resolves
by platform). None of them compiles for a TPU v5e yet, so the LSH
families' ``auto`` backends resolve to xla there.
"""

from repro.kernels.epilogues import on_tpu
from repro.kernels.ops import (cp_inner_products, tt_inner_products,
                               srp_pack, e2lsh_quantize, fused_hash)
from repro.kernels import ref
