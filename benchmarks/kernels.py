"""Kernel-level benchmark: the batch-native Pallas kernels (the Pallas
interpreter off TPU, compiled on TPU) validated against ref.py and timed
against the equivalent XLA path. On CPU interpret mode measures Python-level
kernel semantics, so the numbers that matter here are the allclose/exact
checks + the arithmetic-intensity report used in the §Perf kernel
discussion.

CSV: name,us_per_call,derived (derived = max|kernel - ref| for allclose
rows, mismatch count for exact rows; 'flops/byte' rows report the kernel's
arithmetic intensity at benchmark shape — the fused '*_keys' variants also
show the HBM-traffic shrink from emitting (B, L) uint32 keys instead of
(B, L*K) float values).

``run()`` appends a trajectory entry to BENCH_index.json (tagged
``"bench": "kernels"``) so kernel-validation drift is tracked alongside the
index benchmarks; runnable standalone (``make bench-kernels``) or via
``python -m benchmarks.run``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import append_trajectory, emit, time_fn
from repro.core.lsh import _combine_codes, make_mults
from repro.kernels import ref
from repro.kernels.cp_gram import cp_gram_pallas
from repro.kernels.tt_inner import tt_inner_pallas
from repro.kernels.srp_pack import srp_pack_pallas


def run() -> list[str]:
    rows = []
    errs = {}
    key = jax.random.PRNGKey(0)

    # CP gram kernel: B=64, N=4, d=64, R=32, L=8, K=8
    b, n, d, rx, rp, l, k = 64, 4, 64, 32, 32, 8, 8
    kx, kp = jax.random.split(key)
    xf = jax.random.normal(kx, (b, n, d, rx))
    pf = jax.random.normal(kp, (n, l, k, d, rp))
    got = cp_gram_pallas(xf, pf, epilogue="raw", block_l=2)
    want = ref.cp_inner_ref(xf, pf.reshape(n, l * k, d, rp)).reshape(b, l, k)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    us_ref = time_fn(jax.jit(ref.cp_inner_ref), xf,
                     pf.reshape(n, l * k, d, rp))
    rows.append(emit("kernels/cp_gram/allclose", us_ref, f"{err:.2e}"))
    errs["cp_gram_rel_err"] = err
    flops = b * l * k * n * d * rx * rp * 2
    bytes_ = 4 * (xf.size + pf.size + b * l * k)
    rows.append(emit("kernels/cp_gram/intensity", us_ref,
                     f"{flops / bytes_:.2f}"))
    # fused keys epilogue: bit-exact vs the tail oracles composed on the
    # kernel's own raw values (raw accuracy is the allclose row above —
    # composing on the jnp raws would let ulp-level reassociation flip
    # boundary codes and pollute the epilogue check), 4*K fewer out bytes
    mults = make_mults(0, k)
    offs = jax.random.uniform(key, (l, k), minval=0.0, maxval=4.0)
    got_keys = cp_gram_pallas(xf, pf, offs, jnp.asarray(mults)[None],
                              epilogue="e2lsh-keys", w=4.0, block_l=2)
    want_keys = _combine_codes(
        ref.e2lsh_quant_ref(got.reshape(b, l * k), offs.reshape(-1), 4.0)
        .reshape(b, l, k), mults)
    n_bad = int(jnp.sum(got_keys != want_keys))
    rows.append(emit("kernels/cp_gram/fused_keys_exact", us_ref, f"{n_bad}"))
    errs["cp_gram_keys_mismatch"] = n_bad
    bytes_keys = 4 * (xf.size + pf.size + b * l)
    rows.append(emit("kernels/cp_gram/fused_keys_intensity", us_ref,
                     f"{flops / bytes_keys:.2f}"))

    # TT inner kernel: B=32, N=4, d=32, R=16, L=4, K=8
    b, n, d, r, l, k = 32, 4, 32, 16, 4, 8
    xc = jax.random.normal(kx, (b, n, r, d, r))
    pc = jax.random.normal(kp, (n, l, k, r, d, r))
    got = tt_inner_pallas(xc, pc, epilogue="raw", block_l=2)
    want = ref.tt_inner_ref(xc, pc.reshape(n, l * k, r, d, r)).reshape(b, l, k)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    us_ref = time_fn(jax.jit(ref.tt_inner_ref), xc,
                     pc.reshape(n, l * k, r, d, r))
    rows.append(emit("kernels/tt_inner/allclose", us_ref, f"{err:.2e}"))
    errs["tt_inner_rel_err"] = err
    flops = b * l * k * n * d * (r ** 3) * 4
    bytes_ = 4 * (xc.size + pc.size + b * l * k)
    rows.append(emit("kernels/tt_inner/intensity", us_ref,
                     f"{flops / bytes_:.2f}"))
    got_keys = tt_inner_pallas(xc, pc, None, jnp.asarray(make_mults(0, k))[None],
                               epilogue="srp-keys", block_l=2)
    want_keys = _combine_codes((got > 0).astype(jnp.int32), make_mults(0, k))
    n_bad = int(jnp.sum(got_keys != want_keys))
    rows.append(emit("kernels/tt_inner/fused_keys_exact", us_ref, f"{n_bad}"))
    errs["tt_inner_keys_mismatch"] = n_bad

    # Fused-hash block sweep: a few (block_b, block_t) tilings of the
    # in-format CP x CP keys kernel vs the (8, 1) untiled grid, at the
    # L/K/R/d shape the _HASH_BLOCK_DEFAULTS comment in kernels/ops.py
    # documents (B=64 here to keep the interpret-mode run short).
    # Interpret mode times Python-level grid overhead, which is exactly
    # what the tiling removes, so the ratios are meaningful on CPU.
    from repro.core import cp_random_data, make_family
    from repro.kernels import ops

    dims, bb, ll, kk, rr = (8, 8, 8), 64, 8, 4, 2
    fam = make_family(key, "cp-e2lsh", dims, num_codes=kk, num_tables=ll,
                      rank=rr, bucket_width=4.0)
    xs = jax.vmap(lambda s: cp_random_data(s, dims, rr))(
        jax.random.split(kx, bb))
    sweep_mults = jnp.asarray(make_mults(0, kk))
    base_us = None
    for blk_b, blk_t in ((8, 1), (32, 4), (64, 8)):
        f = jax.jit(lambda x, blk_b=blk_b, blk_t=blk_t: ops.fused_hash(
            x, fam.projection, epilogue="keys", kind="cp-e2lsh",
            num_tables=ll, num_codes=kk, offsets=fam.offsets,
            w=fam.bucket_width, mults=sweep_mults,
            block_b=blk_b, block_t=blk_t))
        us = time_fn(f, xs, iters=5)
        if base_us is None:
            base_us = us
        rows.append(emit(f"kernels/fused_hash_cp/blocks_{blk_b}x{blk_t}",
                         us, f"{base_us / us:.2f}x"))
    errs["fused_hash_block_speedup"] = round(base_us / us, 3)

    # SRP pack kernel
    v = jax.random.normal(key, (256, 256))
    got = srp_pack_pallas(v, block_b=8)
    want = ref.srp_pack_ref(v)
    n_bad = int(jnp.sum(got != want))
    us_ref = time_fn(jax.jit(ref.srp_pack_ref), v)
    rows.append(emit("kernels/srp_pack/exact", us_ref, f"{n_bad}"))
    errs["srp_pack_mismatch"] = n_bad

    append_trajectory({
        "bench": "kernels",
        "n_devices": len(jax.devices()),
        "interpret": jax.default_backend() != "tpu",
        **errs,
    })
    return rows


if __name__ == "__main__":
    run()
