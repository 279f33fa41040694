"""Shared in-kernel epilogues for the fused hashing and query kernels.

Both projection kernels (cp_gram, tt_inner) end with the same (BBLK, LBLK*K)
block of scaled raw <P, X> values sitting in registers/VMEM; these helpers
turn it into the final output *inside the kernel* so the float values never
reach HBM:

  "raw"        (BBLK, LBLK, K) float32   the values themselves
  "e2lsh"      (BBLK, LBLK, K) int32     floor((v + b) / w)   (Defs 10-11)
  "srp"        (BBLK, LBLK, K) int32     1 iff v > 0          (Defs 12-13)
  "e2lsh-keys" (BBLK, LBLK)    uint32    radix combine of the e2lsh codes
  "srp-keys"   (BBLK, LBLK)    uint32    radix combine of the srp codes
  "srp-packed" (BBLK, LBLK, K/32) uint32 sign bits packed little-endian

The radix combine is sum_k codes[k] * mults[k] in uint32 arithmetic —
exactly ``repro.core.lsh._combine_codes`` (int32 -> uint32 casts wrap mod
2^32). The E2LSH quantize uses the same ``(v + b) / w`` division as
``lsh.e2lsh_discretize`` so codes stay bit-comparable with the XLA path.

The second half of this module is the *probe epilogue* — the stages that
take a block of hashed bucket keys the rest of the way to (id, score)
candidate pairs: binary search over per-segment sorted keys, the bounded
cap-wide gather with bucket-boundary / duplicate / tombstone masking, and
the packed top-k selection. They are written as plain jnp array functions
on purpose: ``repro.core.segments`` calls them on full (B, ...) arrays
(the restructured XLA query schedule) and ``repro.kernels.fused_query``
calls the very same functions on (BBLK, ...) blocks inside a Pallas kernel
body — one implementation, so the two probe backends are bit-identical by
construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret(interpret: bool | None) -> bool:
    """Every kernel's ``interpret=None`` default: compiled for the chip on
    TPU, the Pallas interpreter on any other backend."""
    return (not on_tpu()) if interpret is None else interpret


EPILOGUES = ("raw", "e2lsh", "srp", "e2lsh-keys", "srp-keys", "srp-packed")

# Packed-selection sentinels: an invalid candidate slot carries the largest
# uint32 order key (sorts after every real score — the only colliding real
# key would be a NaN with all-ones payload, which IEEE arithmetic never
# produces; hardware NaNs are canonical 0x7FC00000) and the largest int32
# id payload (sorts after every real effective id on key ties).
# numpy scalars on purpose: they inline as jaxpr literals, so the Pallas
# kernel body doesn't capture device-array constants
PROBE_PAD_KEY = np.uint32(0xFFFFFFFF)
PROBE_PAD_ID = np.int32(0x7FFFFFFF)


def out_struct(b: int, l: int, k: int, epilogue: str) -> jax.ShapeDtypeStruct:
    """Full-array output shape/dtype of a fused hash kernel."""
    if epilogue == "raw":
        return jax.ShapeDtypeStruct((b, l, k), jnp.float32)
    if epilogue in ("e2lsh", "srp"):
        return jax.ShapeDtypeStruct((b, l, k), jnp.int32)
    if epilogue in ("e2lsh-keys", "srp-keys"):
        return jax.ShapeDtypeStruct((b, l), jnp.uint32)
    if epilogue == "srp-packed":
        assert k % 32 == 0, k
        return jax.ShapeDtypeStruct((b, l, k // 32), jnp.uint32)
    raise ValueError(f"epilogue must be one of {EPILOGUES}, got {epilogue!r}")


def apply_epilogue(v: jax.Array, offs: jax.Array, mults: jax.Array, *,
                   epilogue: str, w: float) -> jax.Array:
    """(BBLK, LBLK, K) scaled raw values -> the kernel's output block.

    offs: (LBLK, K) float32 E2LSH offsets (ignored by srp/raw);
    mults: (1, K) uint32 radix multipliers (ignored unless *-keys).
    """
    if epilogue == "raw":
        return v
    if epilogue.startswith("e2lsh"):
        codes = jnp.floor((v + offs[None]) / w).astype(jnp.int32)
    else:
        codes = (v > 0).astype(jnp.int32)
    if epilogue in ("e2lsh", "srp"):
        return codes
    if epilogue.endswith("keys"):
        return jnp.sum(codes.astype(jnp.uint32) * mults[0][None, None, :],
                       axis=-1, dtype=jnp.uint32)
    # srp-packed: K % 32 == 0 (ops.py pads with zero projections -> bit 0)
    bb, lb, k = codes.shape
    words = codes.astype(jnp.uint32).reshape(bb, lb, k // 32, 32)
    shifts = jax.lax.broadcasted_iota(jnp.uint32, (1, 1, 1, 32), 3)
    return jnp.sum(words << shifts, axis=-1, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# Probe epilogue: bucket windows -> dedup -> packed (id, score) selection
# ---------------------------------------------------------------------------


def probe_windows(sorted_keys, perm, keys, cap, live, win=None):
    """Raw probe windows, pre-dedup -> (ids (B, W) local ids, hit (B, W)).

    ``keys`` is (L, B) single-probe or (L, T, B) multi-probe; every op
    broadcasts over the optional probe axis, which is then folded into the
    flattened window axis W = L[*T]*cap (query-major, table-major, probe-
    major, window-minor). One (query, table, probe, window-slot) cell per
    output column: the same local id recurs once per probed bucket that
    holds it; callers sort + mask the recurrences away for the top-k path
    and count them for the weighted sampling mode.

    Dense stores (``win`` is None) gather the first ``cap`` sorted
    positions after the binary-search start and keep slots still inside
    the bucket (same key) whose slot is live. ``win`` stores (explicit
    ``bucket_cap``) instead gather through the (live_rank (L, m+1),
    live_pos (L, m)) live-window lookup: the window covers the first
    ``cap`` *live* members of the bucket, and because the bucket's live
    members occupy exactly the live ranks [live_rank[start], live_rank[end])
    — ``end`` from the side='right' binary search — the window bound is one
    rank compare. No per-slot key gather + equality scan and no tombstone
    mask re-check: every position the live window yields is live and
    in-bucket by construction.
    """
    m = sorted_keys.shape[1]
    starts = jax.vmap(
        lambda sk, q: jnp.searchsorted(sk, q, side="left"))(sorted_keys, keys)
    if win is None:
        pos = starts[..., None] + jnp.arange(cap, dtype=starts.dtype)
        in_range = pos < m                                # (L[, T], B, cap)
        posc = jnp.minimum(pos, max(m - 1, 0))
        key_at = jax.vmap(lambda sk, p: sk[p])(sorted_keys, posc)
        hit = in_range & (key_at == keys[..., None])
        ids = jax.vmap(lambda pm, p: pm[p])(perm, posc)   # (L[, T], B, cap)
        hit &= live[ids]                                  # tombstones + pads
    else:
        live_rank, live_pos = win
        ends = jax.vmap(
            lambda sk, q: jnp.searchsorted(sk, q, side="right"))(sorted_keys,
                                                                 keys)
        rank0 = jax.vmap(lambda lr, st: lr[st])(live_rank, starts)
        rank_end = jax.vmap(lambda lr, en: lr[en])(live_rank, ends)
        j = rank0[..., None] + jnp.arange(cap, dtype=rank0.dtype)
        hit = j < rank_end[..., None]                     # (L[, T], B, cap)
        pos = jax.vmap(lambda lp, p: lp[p])(
            live_pos, jnp.minimum(j, max(m - 1, 0)))
        ids = jax.vmap(lambda pm, p: pm[p])(perm, pos)
    b = keys.shape[-1]
    ids = jnp.moveaxis(ids, -2, 0).reshape(b, -1)
    hit = jnp.moveaxis(hit, -2, 0).reshape(b, -1)
    return ids, hit


def dedup_windows(ids, hit, m):
    """(ids, hit) raw windows -> (cand (B, W) sorted local ids, valid).

    Sort each row's hits ascending (misses carry the ``m`` sentinel, so
    they sink to the tail) and mask duplicates, so each local id appears at
    most once — including across the T probed buckets of one table, whose
    windows overlap whenever probes collide. ``cand`` keeps the sentinel on
    invalid slots; callers clamp before gathering.
    """
    b = ids.shape[0]
    cand = jnp.sort(jnp.where(hit, ids, m), axis=1)       # invalid (>=m) last
    dup = jnp.concatenate(
        [jnp.zeros((b, 1), bool), cand[:, 1:] == cand[:, :-1]], axis=1)
    valid = (cand < m) & ~dup
    return cand, valid


def order_key_bits(metric, scores):
    """f32 scores -> uint32 keys whose unsigned order is the metric's rank
    order (ascending distance / descending similarity), matching XLA's f32
    total order bit for bit: flip all bits of negatives, set the sign bit
    of non-negatives. Bijective, so the score is recoverable exactly."""
    order = scores if metric == "euclidean" else -scores
    bits = order.view(jnp.uint32)
    return jnp.where(bits >> 31 != 0, ~bits, bits | jnp.uint32(0x80000000))


def decode_order_key(metric, key32):
    """Inverse of ``order_key_bits`` (exact, including the cosine
    negation — a sign-bit flip is an involution on every f32 pattern)."""
    bits = jnp.where(key32 >> 31 != 0, key32 & jnp.uint32(0x7FFFFFFF), ~key32)
    order = bits.view(jnp.float32)
    return order if metric == "euclidean" else -order


def pack_candidates(metric, eid, scores, valid):
    """One segment's scored candidates -> (hi (B, W) uint32, lo (B, W)
    int32) packed selection operands: hi is the order key (pad-key on
    invalid slots), lo the effective id (pad-id on invalid slots)."""
    key32 = order_key_bits(metric, scores)
    hi = jnp.where(valid, key32, PROBE_PAD_KEY)
    lo = jnp.where(valid, eid.astype(jnp.int32), PROBE_PAD_ID)
    return hi, lo


def packed_select(metric, topk, hi, lo):
    """Packed top-k: one two-operand two-key sort on (order key, effective
    id) -> (ids (B, topk) with -1 fill, scores (B, topk) with bad fill).

    Equivalent to ``segments.select_topk`` on the same candidates, key for
    key: validity is folded into the order key (invalid slots carry the
    pad key, after every real score in XLA's f32 total order), and the
    effective id is the explicit tie-break — so the selection is
    independent of candidate position, which is what makes one flat sort
    over every segment's concatenated candidates bit-identical to the
    per-segment top-k + merge tree it replaces. ``is_stable=False`` is
    safe: effective ids are unique across a store's segments, so the key
    pair is already a strict total order on valid slots.
    """
    shi, slo = jax.lax.sort((hi, lo), dimension=1, is_stable=False,
                            num_keys=2)
    k = min(topk, hi.shape[1])
    shi, slo = shi[:, :k], slo[:, :k]
    sv = shi != PROBE_PAD_KEY
    bad = jnp.float32(jnp.inf if metric == "euclidean" else -jnp.inf)
    ids = jnp.where(sv, slo, -1)
    scores = jnp.where(sv, decode_order_key(metric, shi), bad)
    if k < topk:
        ids = jnp.pad(ids, ((0, 0), (0, topk - k)), constant_values=-1)
        scores = jnp.pad(scores, ((0, 0), (0, topk - k)),
                         constant_values=bad)
    return ids, scores
