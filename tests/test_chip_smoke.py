"""CPU rehearsal of ``chip_smoke.py``: every phase function, at ~2k items.

The script itself refuses to run without a TPU; these tests run its phase
functions directly, at sizes the CPU handles in seconds, so a wrong path,
argument or check fails here before any chip time is spent. The sharded
phase runs in a child process on four forced host devices (the parent's
single CPU device would take the vmap path, which the phase rejects).
"""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]
N = 2000
BATCH = 64


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


@pytest.fixture(scope="module")
def log():
    return smoke.Log()


@pytest.fixture(scope="module")
def dense(log):
    return smoke.phase_dense(log, jax.random.PRNGKey(0), n=N, batch=BATCH)


def test_dense_phase(dense):
    svc = dense["svc"]
    assert svc.index.size == N
    assert svc.index.cap == smoke.CAP
    assert dense["planted"].shape == (BATCH,)


def test_mutate_phase(log, dense):
    smoke.phase_mutate(log, dense, inserts=2 * BATCH, batch=BATCH)
    assert dense["svc"].index.size == N + BATCH   # +2B inserts, -B deletes
    assert not dense["svc"].index.store.deltas    # compacted


def test_serve_phase(log, dense):
    smoke.phase_serve(log, dense, requests=16)


def test_durable_phase(log, dense):
    smoke.phase_durable(log, dense, n=N, mutations=BATCH, batch=BATCH)


def test_in_format_phase(log):
    smoke.phase_in_format(log, jax.random.PRNGKey(7), n=N, batch=BATCH)


def test_failed_check_raises(log):
    with pytest.raises(smoke.CheckFailed):
        log.check("always_false", False)


def test_sharded_phase_on_four_host_devices():
    code = textwrap.dedent(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        assert len(jax.devices()) == 4, jax.devices()
        smoke.phase_sharded(smoke.Log(), jax.random.PRNGKey(0), n={N},
                            shards=4, inserts={2 * BATCH}, batch={BATCH})
        print("sharded phase ok")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert "sharded phase ok" in out.stdout
    assert "query_path: PASS shard_map" in out.stdout
    assert out.stdout.count("ids_equal_S1: PASS") == 7
    assert "compacted_on_distinct_devices: PASS" in out.stdout


def test_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.compile_cache_dir() == str(ROOT / ".jax_cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before  # JAX's own read
