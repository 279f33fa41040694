"""Pallas kernel: one program from a query batch to (id, score) pairs.

The probe side of the query path — multi-probe key expansion, per-segment
binary search over sorted bucket keys, the bounded cap-wide gather with
bucket-boundary / duplicate / tombstone masking, exact in-format re-rank,
and top-k selection — used to be a chain of separate XLA dispatches with
HBM round-trips between every stage. This kernel runs the whole chain as
ONE ``pl.pallas_call``: a query block goes HBM->VMEM once and the program
emits the final (effective id, score) pairs plus the candidate count.

Stage map (all inside the kernel body, per B-block):

  raw projections -> discretize (E2LSH floor / SRP sign, the exact ops of
  ``lsh.LSHFamily.hash_batch_aux``) -> multi-probe expansion (the
  perturbation scores/deltas of ``core.probing.scores_and_deltas`` +
  stable top-T ranking, folded in front of the radix code-combine) ->
  uint32 combine -> per-segment probe windows -> dedup -> hoisted-norm
  re-rank -> packed top-k.

The probe-epilogue stages are the *shared* implementations in
``repro.kernels.epilogues`` and ``repro.core.segments.hoisted_scores`` —
the same functions the restructured XLA schedule calls on full arrays —
so the two probe backends are bit-identical by construction, and both are
pinned bit-identical to the reference planner by tests/test_fused_probe.py.

The only stage left outside is the batched projection contraction itself
(``project_batch``): the multi-probe expansion ranks floor residuals /
sign margins, and those are defined on the XLA projection path for every
``hash_backend`` (see ``hash_batch_aux``) — keeping that contract here
keeps kernel keys bit-identical to the planner's under any hash backend.

The kernel does not compile for a TPU v5e yet: Mosaic refuses the uint32
reduction of the key combine, and past it the body still calls
``searchsorted``, ``argsort`` and a two-operand ``lax.sort``. Each
segment's arrays are also passed as whole-array VMEM blocks, which at
deployment sizes (1M x 128 f32 is 512 MB) exceed a core's VMEM. So
``probe_backend='auto'`` resolves to xla on every platform, and off TPU
this kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import probing
from repro.core import projections as proj_lib
from repro.core import segments as _seg
from repro.kernels import epilogues as _epi
from repro.kernels.ops import _pad_axis


def _expand_probe_keys(aux, base, mults, *, e2, probes, pa, pb):
    """(aux (bb, L, K), base (bb, L) uint32) -> (bb, L, T) ranked keys.

    The in-kernel body of ``probing.probe_keys``'s expansion: the same
    perturbation scores/deltas (``scores_and_deltas``), the same stable
    ascending top-(T-1), the same base-key slot-0 / base-key padding.
    ``pa``/``pb`` are the static pair indices as Python ints, so the pair
    sums unroll into static slices — no constant index arrays captured by
    the kernel trace.
    """
    if probes == 1:
        return base[..., None]
    if e2:
        r = aux
        s1 = jnp.concatenate([(1.0 - r) ** 2, r ** 2], axis=-1)
        d1 = jnp.concatenate([mults, jnp.uint32(0) - mults])
        d1 = jnp.broadcast_to(d1, s1.shape)
    else:
        s1 = jnp.abs(aux)
        d1 = jnp.where(aux > 0, jnp.uint32(0) - mults, mults)
    scores, deltas = s1, d1
    if len(pa):
        scores = jnp.concatenate(
            [s1, jnp.stack([s1[..., i] + s1[..., j]
                            for i, j in zip(pa, pb)], axis=-1)], axis=-1)
        deltas = jnp.concatenate(
            [d1, jnp.stack([d1[..., i] + d1[..., j]
                            for i, j in zip(pa, pb)], axis=-1)], axis=-1)
    n = min(probes - 1, scores.shape[-1])
    order = jnp.argsort(scores, axis=-1, stable=True)[..., :n]
    keys = base[..., None] + jnp.take_along_axis(deltas, order, axis=-1)
    keys = jnp.concatenate([base[..., None], keys], axis=-1)
    if 1 + n < probes:
        pad = jnp.broadcast_to(base[..., None],
                               base.shape + (probes - 1 - n,))
        keys = jnp.concatenate([keys, pad], axis=-1)
    return keys


def _fused_query_kernel(*refs, metric, topk, caps, probes, e2, w, num_tables,
                        num_codes, pa, pb, q_treedef, n_qleaves, seg_specs):
    """Kernel body: refs are (values, offsets, mults, *query leaves,
    *per-segment arrays..., ids_out, scores_out, ncand_out)."""
    refs = list(refs)
    values_ref, offs_ref, mults_ref = refs[:3]
    pos = 3
    qleaves = [refs[pos + i][...] for i in range(n_qleaves)]
    pos += n_qleaves
    queries = jax.tree.unflatten(q_treedef, qleaves)
    ids_ref, scores_ref, nc_ref = refs[-3:]

    # hash: discretize exactly as LSHFamily.hash_batch_aux
    v = values_ref[...]                                   # (bb, LK)
    bb = v.shape[0]
    mults = mults_ref[...][0]                             # (K,) uint32
    if e2:
        t = (v + offs_ref[...][0]) / w
        codes = jnp.floor(t).astype(jnp.int32)
        aux = (t - codes.astype(v.dtype)).reshape(bb, num_tables, num_codes)
        codes = codes.reshape(bb, num_tables, num_codes)
    else:
        codes = (v > 0).astype(jnp.int32).reshape(bb, num_tables, num_codes)
        aux = v.reshape(bb, num_tables, num_codes)
    # radix combine (lsh._combine_codes) + multi-probe expansion in front
    base = jnp.sum(codes.astype(jnp.uint32) * mults[None, None, :],
                   axis=-1, dtype=jnp.uint32)             # (bb, L)
    keys = _expand_probe_keys(aux, base, mults, e2=e2, probes=probes,
                              pa=pa, pb=pb)               # (bb, L, T)
    keys = jnp.moveaxis(keys, 0, -1)                      # (L, T, bb)

    # probe epilogue per segment: windows -> dedup -> re-rank -> pack
    his, los = [], []
    n_cand = jnp.zeros((bb,), jnp.int32)
    for spec, cap in zip(seg_specs, caps):
        n_leaves, treedef, has_win = spec
        corpus = jax.tree.unflatten(
            treedef, [refs[pos + i][...] for i in range(n_leaves)])
        pos += n_leaves
        sorted_keys = refs[pos][...]
        perm = refs[pos + 1][...]
        live = refs[pos + 2][...][0]
        eff = refs[pos + 3][...][0]
        pos += 4
        win = None
        if has_win:
            win = (refs[pos][...], refs[pos + 1][...])
            pos += 2
        m = sorted_keys.shape[1]
        ids, hit = _epi.probe_windows(sorted_keys, perm, keys, cap, live, win)
        cand, valid = _epi.dedup_windows(ids, hit, m)
        safe = jnp.where(valid, cand, 0)
        scores = _seg.hoisted_scores(metric, queries, corpus, safe)
        hi, lo = _epi.pack_candidates(metric, eff[safe], scores, valid)
        his.append(hi)
        los.append(lo)
        n_cand = n_cand + valid.sum(axis=1, dtype=jnp.int32)

    out_ids, out_scores = _epi.packed_select(
        metric, topk, jnp.concatenate(his, axis=1),
        jnp.concatenate(los, axis=1))
    ids_ref[...] = out_ids
    scores_ref[...] = out_scores
    nc_ref[...] = n_cand[:, None]


def fused_query(family, segs, mults, queries, *, metric, topk, caps,
                probes=1, block_b=None, interpret=None):
    """One fused kernel launch from a query batch to ((B, topk) effective
    ids, (B, topk) scores, (B,) candidate counts) over every segment.

    Drop-in for ``segments.segmented_query`` (the probe_backend='pallas'
    path): same arguments, bit-identical results. ``block_b`` tiles the
    query batch over the kernel grid (default: up to 256 queries per
    program); segment arrays are whole-array refs shared by every block.
    """
    e2 = family.offsets is not None
    k = family.num_codes
    values = proj_lib.project_batch(family.projection, queries)  # (B, L*K)
    b = values.shape[0]
    if block_b is None:
        block_b = min(b, 256)
    offs = family.offsets if e2 else jnp.zeros(values.shape[1:], jnp.float32)
    coord = np.concatenate([np.arange(k), np.arange(k)]) if e2 \
        else np.arange(k)
    pa, pb = probing._pair_indices(coord)
    pa = tuple(int(i) for i in np.asarray(pa))
    pb = tuple(int(i) for i in np.asarray(pb))

    q_leaves, q_treedef = jax.tree.flatten(queries)
    inputs = [_pad_axis(values.astype(jnp.float32), 0, block_b),
              offs.astype(jnp.float32)[None],               # (1, L*K)
              jnp.asarray(mults).astype(jnp.uint32)[None]]  # (1, K)
    in_specs = [
        pl.BlockSpec((block_b, values.shape[1]), lambda i: (i, 0)),
        pl.BlockSpec(inputs[1].shape, lambda i: (0, 0)),
        pl.BlockSpec(inputs[2].shape, lambda i: (0, 0)),
    ]

    def add_block_leaf(leaf):
        leaf = _pad_axis(leaf, 0, block_b)
        nd = leaf.ndim
        in_specs.append(pl.BlockSpec(
            (block_b,) + leaf.shape[1:],
            lambda i, nd=nd: (i,) + (0,) * (nd - 1)))
        inputs.append(leaf)

    def add_full(arr):
        arr = jnp.asarray(arr)
        if arr.ndim == 1:
            arr = arr[None]
        nd = arr.ndim
        in_specs.append(pl.BlockSpec(
            arr.shape, lambda i, nd=nd: (0,) * nd))
        inputs.append(arr)

    for leaf in q_leaves:
        add_block_leaf(leaf)

    seg_specs = []
    for seg_arrays in segs:
        corpus, sorted_keys, perm, live, eff, win = seg_arrays
        c_leaves, c_treedef = jax.tree.flatten(corpus)
        seg_specs.append((len(c_leaves), c_treedef, win is not None))
        for leaf in c_leaves:
            add_full(leaf)
        add_full(sorted_keys)
        add_full(perm)
        add_full(live)
        add_full(eff)
        if win is not None:
            add_full(win[0])
            add_full(win[1])

    b_pad = inputs[0].shape[0]
    grid = (b_pad // block_b,)
    kernel = functools.partial(
        _fused_query_kernel, metric=metric, topk=topk, caps=tuple(caps),
        probes=int(probes), e2=e2, w=float(family.bucket_width),
        num_tables=family.num_tables, num_codes=k, pa=pa, pb=pb,
        q_treedef=q_treedef, n_qleaves=len(q_leaves),
        seg_specs=tuple(seg_specs))
    out_ids, out_scores, out_nc = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((block_b, topk), lambda i: (i, 0)),
                   pl.BlockSpec((block_b, topk), lambda i: (i, 0)),
                   pl.BlockSpec((block_b, 1), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((b_pad, topk), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, topk), jnp.float32),
                   jax.ShapeDtypeStruct((b_pad, 1), jnp.int32)),
        interpret=_epi.default_interpret(interpret),
    )(*inputs)
    return out_ids[:b], out_scores[:b], out_nc[:b, 0]


def fused_query_sharded(family, base, deltas, mults, queries, *, metric,
                        topk, cap, delta_caps, probes=1, block_b=None,
                        interpret=None):
    """Sharded-layout entry: ONE fused kernel launch over every (shard,
    segment) pair — each shard's base slice and delta slabs become
    independent segments of the same program, and the kernel's flat packed
    selection subsumes the S-way merge (effective ids are unique across
    shards, so the (validity, score, id) sort key is a strict total order;
    see ``segments.merge_topk``). Drop-in for
    ``segments.sharded_query_vmap`` on the pallas probe backend.

    Per-segment scoring runs unbatched inside the kernel, so results are
    bit-identical to the *per-shard* reference program (the shard_map
    body); the vmapped no-mesh fallback rounds some last bits differently
    — the same known divergence the seed's sharded parity test tolerates
    between its own two programs. The mesh shard_map dispatch of this
    kernel is the deferred TPU leg (see ROADMAP)."""
    s = jax.tree.leaves(base)[0].shape[0]
    segs, caps = [], []
    for i in range(s):
        segs.append(jax.tree.map(lambda a, i=i: a[i], base))
        caps.append(cap)
        for d, dcap in zip(deltas, delta_caps):
            segs.append(jax.tree.map(lambda a, i=i: a[i], d))
            caps.append(dcap)
    return fused_query(family, tuple(segs), mults, queries, metric=metric,
                       topk=topk, caps=tuple(caps), probes=probes,
                       block_b=block_b, interpret=interpret)
