"""Backend parity matrix for the batch-native fused hashing pipeline.

Pins the tentpole contract: for every family kind, the pallas(interpret)
and xla hash backends produce BIT-IDENTICAL integer codes, bucket keys, and
packed SRP signatures, across batch sizes and deliberately awkward shapes
(odd mode dims, K and rank not multiples of 8 — the ops.py padding paths).
Kinds whose format combination has no kernel (dense projections, and
CP/TT projections over dense inputs) must fall back to the XLA path
inside the pallas backend, trivially but verifiably equal.

Also covers the dispatch knob itself: make_family validation, the
REPRO_HASH_BACKEND env override of 'auto', batched-vs-single consistency,
and index-level build parity (identical sorted bucket keys either way).
"""

import os
import subprocess
import sys

import grids
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DeviceLSHIndex, cp_random_data, make_family,
                        tt_random_data)
from repro.core.lsh import (ALL_KINDS, SRP_KINDS, _combine_codes, make_mults,
                            pack_bits)

# odd mode dims, odd K, odd rank, odd L: nothing is a multiple of 8
DIMS = (7, 7, 7)
K, L, RANK = 5, 3, 3
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _key(seed):
    return jax.random.PRNGKey(seed)


def _families(kind, seed=0):
    mk = lambda backend: make_family(_key(seed), kind, DIMS, num_codes=K,
                                     num_tables=L, rank=RANK,
                                     bucket_width=4.0, hash_backend=backend)
    return mk("xla"), mk("pallas")


def _batch(kind, b, fmt, seed=1):
    if fmt == "dense":
        return jax.random.normal(_key(seed), (b,) + DIMS)
    maker = cp_random_data if fmt == "cp" else tt_random_data
    return jax.vmap(lambda k: maker(k, DIMS, 2))(jax.random.split(_key(seed), b))


def _native_fmt(kind):
    """The input format the pallas kernels cover for this kind."""
    if kind.startswith("cp"):
        return "cp"
    if kind.startswith("tt"):
        return "tt"
    return "dense"


class TestBackendParityMatrix:
    """pallas(interpret) vs xla: bit-identical codes for all 6 kinds x
    batch {1, 64} x {kernel-native format, dense fallback}."""

    @pytest.mark.parametrize("batch", [1, 64])
    @pytest.mark.parametrize("kind", ALL_KINDS)
    def test_codes_bit_identical(self, kind, batch):
        fam_x, fam_p = _families(kind)
        xs = _batch(kind, batch, _native_fmt(kind))
        cx = np.asarray(fam_x.hash_batch(xs))
        cp = np.asarray(fam_p.hash_batch(xs))
        assert cx.shape == (batch, L, K) and cx.dtype == np.int32
        np.testing.assert_array_equal(cx, cp, err_msg=(kind, batch))

    @pytest.mark.parametrize("kind", ALL_KINDS)
    def test_keys_bit_identical_and_consistent(self, kind):
        """hash_keys: fused combine equals combine-of-codes, both backends."""
        fam_x, fam_p = _families(kind)
        xs = _batch(kind, 16, _native_fmt(kind))
        mults = make_mults(3, K)
        kx = np.asarray(fam_x.hash_keys(xs, jnp.asarray(mults)))
        kp = np.asarray(fam_p.hash_keys(xs, jnp.asarray(mults)))
        assert kx.shape == (16, L) and kx.dtype == np.uint32
        np.testing.assert_array_equal(kx, kp, err_msg=kind)
        np.testing.assert_array_equal(
            kx, _combine_codes(np.asarray(fam_x.hash_batch(xs)), mults))

    @pytest.mark.parametrize("kind", ["cp-e2lsh", "tt-srp"])
    def test_dense_inputs_fall_back_identically(self, kind):
        """CP/TT projections over dense inputs have no kernel; the pallas
        backend must serve them through XLA with identical codes."""
        fam_x, fam_p = _families(kind)
        xs = _batch(kind, 9, "dense")
        np.testing.assert_array_equal(np.asarray(fam_x.hash_batch(xs)),
                                      np.asarray(fam_p.hash_batch(xs)))

    @pytest.mark.parametrize("kind", SRP_KINDS)
    def test_packed_bit_identical(self, kind):
        fam_x, fam_p = _families(kind)
        xs = _batch(kind, 8, _native_fmt(kind))
        px = np.asarray(fam_x.hash_packed_batch(xs))
        pp = np.asarray(fam_p.hash_packed_batch(xs))
        assert px.shape == (8, L, 1)  # K=5 -> one uint32 word per table
        np.testing.assert_array_equal(px, pp, err_msg=kind)
        np.testing.assert_array_equal(px, pack_bits(fam_x.hash_batch(xs)))

    @pytest.mark.parametrize("kind", ["cp-srp", "tt-e2lsh"])
    def test_single_hash_matches_batch_row(self, kind):
        """hash(x) is the batch-of-1 case on both backends."""
        for fam in _families(kind):
            xs = _batch(kind, 4, _native_fmt(kind))
            hb = np.asarray(fam.hash_batch(xs))
            h0 = np.asarray(fam.hash(jax.tree.map(lambda a: a[0], xs)))
            np.testing.assert_array_equal(h0, hb[0])


class TestIndexLevelParity:
    """The segment build consumes hash_keys: a pallas-backed index must
    produce bit-identical sorted bucket tables and query results."""

    @pytest.mark.parametrize("kind", ["cp-e2lsh", "tt-srp"])
    def test_build_and_query_parity(self, kind):
        fmt = _native_fmt(kind)
        corpus = _batch(kind, 48, fmt, seed=5)
        queries = _batch(kind, 6, fmt, seed=6)
        fam_x, fam_p = _families(kind, seed=7)
        metric = grids.metric_for(kind)
        ix = DeviceLSHIndex(fam_x, metric=metric).build(corpus)
        ip = DeviceLSHIndex(fam_p, metric=metric).build(corpus)
        np.testing.assert_array_equal(np.asarray(ix.sorted_keys),
                                      np.asarray(ip.sorted_keys))
        np.testing.assert_array_equal(np.asarray(ix.perm),
                                      np.asarray(ip.perm))
        for a, b in zip(ix.query_batch(queries, topk=5),
                        ip.query_batch(queries, topk=5)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestBackendDispatch:
    def test_make_family_rejects_unknown_backend(self):
        with pytest.raises(ValueError, match="hash_backend"):
            make_family(_key(0), "cp-srp", DIMS, hash_backend="cuda")

    def test_resolved_backend_explicit_wins(self):
        fam_x, fam_p = _families("cp-srp")
        assert fam_x.resolved_backend() == "xla"
        assert fam_p.resolved_backend() == "pallas"

    def test_auto_resolves_by_platform(self):
        """'auto' is xla on every platform (the v5e compiler refuses the
        hash kernels; tests/test_tpu_compile.py checks it on TPU)."""
        fam = make_family(_key(0), "cp-srp", DIMS, num_codes=K, num_tables=L)
        assert fam.hash_backend == "auto"
        env = os.environ.get("REPRO_HASH_BACKEND", "").strip().lower()
        assert fam.resolved_backend() == (env or "xla")

    def test_env_var_overrides_auto_not_explicit(self):
        """REPRO_HASH_BACKEND steers 'auto' families (the CI pallas leg)
        but never an explicitly-pinned backend."""
        code = """
        import os
        os.environ["REPRO_HASH_BACKEND"] = "pallas"
        import jax
        from repro.core import make_family
        auto = make_family(jax.random.PRNGKey(0), "cp-srp", (7, 7, 7))
        pinned = make_family(jax.random.PRNGKey(0), "cp-srp", (7, 7, 7),
                             hash_backend="xla")
        assert auto.resolved_backend() == "pallas", auto.resolved_backend()
        assert pinned.resolved_backend() == "xla", pinned.resolved_backend()
        print("env override ok")
        """
        import textwrap
        env = dict(os.environ)
        env["PYTHONPATH"] = SRC
        out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "env override ok" in out.stdout
