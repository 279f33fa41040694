"""Public jit'd wrappers for the Pallas kernels.

These adapt the core-library formats (CPTensor/TTTensor/CPProjection/
TTProjection with per-mode tuples) to the stacked, padded, MXU-aligned
layouts the kernels want, and slice the padding back off:

  * mode dims padded to a multiple of 8 with zero rows (Grams unchanged);
  * the batch axis padded to the B-block with zero inputs (outputs sliced);
  * TT boundary ranks zero-padded to R, chain started from e_00;
  * SRP K padded to a multiple of 32 with zero projections (sign bit 0)
    for the packed epilogue; E2LSH quantize pads K to the lane width.

``fused_hash`` is the batch-native entry the LSH families dispatch to when
``hash_backend`` resolves to pallas: one kernel launch takes a (B, ...)
batch of CP/TT inputs straight to integer codes, combined uint32 bucket
keys, or packed SRP signatures (see kernels/epilogues.py) — the raw
projection values never round-trip through HBM.

Every wrapper and kernel takes ``interpret=None`` by default: compiled on
TPU, the Pallas interpreter on any other backend. None of these kernels
compiles for a TPU v5e yet (the LSH families' ``hash_backend='auto'``
therefore resolves to xla; see ``repro.core.lsh``), so on TPU only an
explicit ``pallas`` request reaches them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.projections import CPProjection, TTProjection
from repro.core.tensor_formats import CPTensor, TTTensor
from repro.kernels.cp_gram import cp_gram_pallas
from repro.kernels.e2lsh_quant import e2lsh_quant_pallas
from repro.kernels.epilogues import default_interpret
from repro.kernels.srp_pack import srp_pack_pallas
from repro.kernels.tt_inner import tt_inner_pallas

_LANES = 128  # TPU VPU lane width (f32 tile is (8, 128))


def _pad_axis(a: jax.Array, axis: int, mult: int, value=0.0) -> jax.Array:
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _check_equal_dims(dims):
    if len(set(dims)) != 1:
        raise ValueError(
            f"kernel path needs equal mode dims, got {dims}; use the "
            "repro.core.projections path for ragged modes")


def _pick_block_l(l: int, cap: int = 8) -> int:
    """Largest power-of-two table-block (<= cap) dividing L."""
    return max(c for c in (64, 32, 16, 8, 4, 2, 1) if c <= cap and l % c == 0)


# Per-format-pair fused-hash block defaults: (block_b cap, block_t cap),
# clamped to the batch size / the largest power-of-two divisor of L.
# Measured by the ``make bench-kernels`` sweep (benchmarks/kernels.py,
# interpret mode on this CPU container, B=256 L=8 K=4 R=2 d=8; median of
# 5, noise ~10%):
#
#   CP x CP: grid-program count dominates — (32, 4) runs ~2.4x faster
#     than the old fixed (8, 1) tiling, with (16, 8) / (32, 8) / (64, 2)
#     all within noise of it (jit-wrapped, the same sweep reads 5-10x:
#     dispatch amortization compounds the grid shrink).  The VMEM
#     accumulator (BBLK, Rx, LBLK*K, Rp) f32 at 32*2*16*2 = 8 KiB stays
#     far under a core's VMEM; wider B-blocks are safe until
#     BBLK*Rx*L*K*Rp*4 nears ~4 MiB.
#   TT x TT: per-table work is R^3 per mode so the program body, not the
#     grid, dominates; gains come almost entirely from block_b.  (64, 8)
#     measured ~2.4x over (8, 1), with every (64, *) within ~6% of it.
#
# TPU re-measurement belongs with the deferred shard_map-vs-fused leg
# (ROADMAP); these caps only tile the grid — every (block_b, block_t)
# combination is bit-identical (pinned by tests/test_kernels.py).
_HASH_BLOCK_DEFAULTS = {"cp": (32, 4), "tt": (64, 8)}


# ---------------------------------------------------------------------------
# Format stacking (batched inputs, stacked projections)
# ---------------------------------------------------------------------------


def _stack_cp_batch(x: CPTensor) -> jax.Array:
    """Batched CP factors (each (B, d, R)) -> (B, N, d, Rx), d padded to 8."""
    xf = jnp.stack([f.astype(jnp.float32) for f in x.factors], axis=1)
    return _pad_axis(xf, 2, 8)


def _stack_cp_proj(p: CPProjection, num_tables: int) -> jax.Array:
    """Projection factors (each (L*K, d, R)) -> (N, L, K, d, Rp), d -> 8."""
    pf = jnp.stack([f.astype(jnp.float32) for f in p.factors], 0)
    pf = _pad_axis(pf, 2, 8)
    n, kt, d, rp = pf.shape
    return pf.reshape(n, num_tables, kt // num_tables, d, rp)


def _stack_tt_cores(cores, rank: int) -> jax.Array:
    """Zero-pad boundary cores to (rank, d, rank) and stack -> (N, R, d, R).

    ``_pad_axis`` pads to a multiple of ``rank``; every core rank is in
    [1, rank] (rank is the chain max), so both rank axes land exactly on
    ``rank`` — boundary cores (rank 1) and truncated interior ranks alike.
    """
    return jnp.stack([
        _pad_axis(_pad_axis(c.astype(jnp.float32), 0, rank), 2, rank)
        for c in cores])


def _stack_tt_batch(x: TTTensor, rank: int) -> jax.Array:
    """Batched TT cores (each (B, r, d, r)) -> (B, N, Rx, d, Rx), d -> 8."""
    cores = [_pad_axis(_pad_axis(c.astype(jnp.float32), 1, rank), 3, rank)
             for c in x.cores]
    return _pad_axis(jnp.stack(cores, axis=1), 3, 8)


def _stack_tt_proj(p: TTProjection, rank: int, num_tables: int) -> jax.Array:
    """Projection cores (each (L*K, r, d, r)) -> (N, L, K, Rp, d, Rp)."""
    cores = [_pad_axis(_pad_axis(c.astype(jnp.float32), 1, rank), 3, rank)
             for c in p.cores]
    pc = _pad_axis(jnp.stack(cores, axis=0), 3, 8)
    n, kt, rp, d, _ = pc.shape
    return pc.reshape(n, num_tables, kt // num_tables, rp, d, rp)


# ---------------------------------------------------------------------------
# Fused batch-native hashing (the hash_backend='pallas' entry point)
# ---------------------------------------------------------------------------


def hash_blocks(fmt: str, b: int, num_tables: int,
                block_b: int | None = None,
                block_t: int | None = None) -> tuple[int, int]:
    """Resolve the (block_b, block_t) grid tiling ``fused_hash`` runs with.

    ``fmt`` is the format pair ('cp' | 'tt'); ``None`` knobs take the
    documented per-format-pair default cap (``_HASH_BLOCK_DEFAULTS``).
    block_t is clamped to the largest power-of-two divisor of L so any
    requested cap stays a legal grid; block_b only tiles the padded batch,
    so it is used as-is (the batch axis is padded up to it).
    """
    db, dt = _HASH_BLOCK_DEFAULTS[fmt]
    block_b = db if block_b is None else int(block_b)
    if block_b < 1:
        raise ValueError(f"block_b must be >= 1, got {block_b}")
    block_t = dt if block_t is None else int(block_t)
    if block_t < 1:
        raise ValueError(f"block_t must be >= 1, got {block_t}")
    # never tile wider than the 8-aligned batch: a batch-of-1 hash must not
    # pay a 64-row zero-padded program
    block_b = min(block_b, max(8, -(-b // 8) * 8))
    return block_b, _pick_block_l(num_tables, cap=block_t)


def fused_hash(xs, p, *, epilogue: str, kind: str, num_tables: int,
               num_codes: int, offsets: jax.Array | None = None,
               w: float = 0.0, mults=None, block_b: int | None = None,
               block_t: int | None = None,
               interpret: bool | None = None) -> jax.Array:
    """One fused kernel launch from a (B, ...) batch to hash outputs.

    xs: batched CPTensor (under a CPProjection) or batched TTTensor (under
    a TTProjection), equal mode dims. epilogue:

      'codes'  -> (B, L, K) int32 hashcodes (E2LSH floor / SRP sign fused)
      'keys'   -> (B, L) uint32 bucket keys (discretize + radix combine
                  with the (K,) uint32 ``mults`` fused)
      'packed' -> (B, L, ceil(K/32)) uint32 SRP signatures (sign + pack)

    ``kind`` picks the discretizer ('*e2lsh' vs '*srp'); ``offsets``/``w``
    are the E2LSH quantizer parameters. ``block_b``/``block_t`` tile the
    kernel grid over the (padded) batch and the tables — tuning knobs only
    (see ``hash_blocks`` for the per-format-pair defaults); every setting
    is bit-identical to the XLA path of ``LSHFamily`` (pinned by
    tests/test_hash_backends.py).
    """
    e2 = kind.endswith("e2lsh")
    kernel_epilogue = {
        "codes": "e2lsh" if e2 else "srp",
        "keys": "e2lsh-keys" if e2 else "srp-keys",
        "packed": "srp-packed",
    }[epilogue]
    interpret = default_interpret(interpret)

    b = jax.tree.leaves(xs)[0].shape[0]
    if isinstance(p, CPProjection) and isinstance(xs, CPTensor):
        block_b, block_l = hash_blocks("cp", b, num_tables, block_b, block_t)
        xf = _pad_axis(_stack_cp_batch(xs), 0, block_b)
        pf = _stack_cp_proj(p, num_tables)
        kernel = cp_gram_pallas
        k_axis = 2
    elif isinstance(p, TTProjection) and isinstance(xs, TTTensor):
        block_b, block_l = hash_blocks("tt", b, num_tables, block_b, block_t)
        rx = max(max(c.shape[1], c.shape[3]) for c in xs.cores)
        rp = max(max(c.shape[1], c.shape[3]) for c in p.cores)
        xf = _pad_axis(_stack_tt_batch(xs, rx), 0, block_b)
        pf = _stack_tt_proj(p, rp, num_tables)
        kernel = tt_inner_pallas
        k_axis = 2
    else:
        raise TypeError(
            f"fused_hash needs matching CP/TT formats, got {type(p).__name__}"
            f" projection on {type(xs).__name__} inputs")

    if epilogue == "packed":
        # zero projections give v = 0 -> sign bit 0, matching pack_bits' pad
        pf = _pad_axis(pf, k_axis, 32)

    offs = None
    if e2:
        offs = offsets.astype(jnp.float32).reshape(num_tables, num_codes)
    mults_arr = None
    if epilogue == "keys":
        mults_arr = jnp.asarray(mults).astype(jnp.uint32).reshape(1, num_codes)

    out = kernel(xf, pf, offs, mults_arr, epilogue=kernel_epilogue,
                 w=float(w) if e2 else 1.0,
                 scale=float(xs.scale * p.scale),
                 block_b=block_b, block_l=block_l,
                 interpret=interpret)
    return out[:b]


# ---------------------------------------------------------------------------
# CP x CP / TT x TT raw inner products (single input, test/benchmark API)
# ---------------------------------------------------------------------------


def cp_inner_products(x: CPTensor, p: CPProjection,
                      interpret: bool | None = None) -> jax.Array:
    """(K,) raw <P_k, X> values (scales applied) via the fused Gram kernel
    — the batch-of-1 case of the batch-native kernel."""
    _check_equal_dims(x.dims)
    _check_equal_dims(p.dims)
    xb = jax.tree.map(lambda a: a[None], x)
    xf = _pad_axis(_stack_cp_batch(xb), 0, 8)
    pf = _stack_cp_proj(p, 1)
    out = cp_gram_pallas(xf, pf, epilogue="raw",
                         interpret=default_interpret(interpret))
    return (x.scale * p.scale) * out[0, 0]


def tt_inner_products(x: TTTensor, p: TTProjection,
                      interpret: bool | None = None) -> jax.Array:
    """(K,) raw <T_k, X> values (scales applied) via the chain kernel —
    the batch-of-1 case of the batch-native kernel."""
    _check_equal_dims(x.dims)
    _check_equal_dims(p.dims)
    rx = max(max(c.shape[0], c.shape[2]) for c in x.cores)
    rp = max(max(c.shape[1], c.shape[3]) for c in p.cores)
    xb = jax.tree.map(lambda a: a[None], x)
    xf = _pad_axis(_stack_tt_batch(xb, rx), 0, 8)
    pf = _stack_tt_proj(p, rp, 1)
    out = tt_inner_pallas(xf, pf, epilogue="raw",
                          interpret=default_interpret(interpret))
    return (x.scale * p.scale) * out[0, 0]


# ---------------------------------------------------------------------------
# Discretization tails (standalone kernels; the fused path inlines these)
# ---------------------------------------------------------------------------


def srp_pack(values: jax.Array, block_b: int = 8,
             interpret: bool | None = None) -> jax.Array:
    """(B, K) raw values -> (B, ceil(K/32)) packed uint32 signatures."""
    b, k = values.shape
    v = _pad_axis(values.astype(jnp.float32), 1, 32, value=-1.0)
    v = _pad_axis(v, 0, block_b, value=-1.0)
    out = srp_pack_pallas(v, block_b=block_b,
                          interpret=default_interpret(interpret))
    return out[:b]


def e2lsh_quantize(values: jax.Array, offsets: jax.Array, w: float,
                   block_b: int = 8, interpret: bool | None = None) -> jax.Array:
    """(B, K) values + (K,) offsets -> int32 (B, K) hashcodes.

    Both the batch axis and the K axis are padded — K to the f32 lane
    width with zero values/offsets (codes floor(0/w) = 0, sliced off), so
    non-lane-aligned K never reaches the kernel's (block_b, K) tiles.
    """
    b, k = values.shape
    v = _pad_axis(values.astype(jnp.float32), 0, block_b)
    v = _pad_axis(v, 1, _LANES)
    offs = _pad_axis(offsets.astype(jnp.float32), 0, _LANES)
    out = e2lsh_quant_pallas(v, offs, float(w), block_b=block_b,
                             interpret=default_interpret(interpret))
    return out[:b, :k]
