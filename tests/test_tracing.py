"""The program's own measurement: device scopes in the query program and
host spans at its layer boundaries (``repro.tracing``).

* the five query stages name the HLO ops of ``segmented_query`` and of
  the ``shard_map`` program through their ``op_name`` metadata;
* a durable service behind the scheduler, traced with ``jax.profiler``,
  writes the expected spans with the expected nesting and threads: the
  fold's phases inside ``lsh.fold`` on the ingest lane, the WAL's sync on
  its committer thread, one ``seq`` across a mutation's spans;
* the counters that the spans feed equal the spans' own durations;
* answers are bit-identical with the profiler on and off.
"""

import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import grids
from repro import tracing
from repro.core import segments
from repro.core.index import DeviceLSHIndex, ShardedLSHIndex
from repro.distributed import index_sharding
from repro.serving.durability import DurableLSHService
from repro.serving.scheduler import ServingScheduler

KIND = "cp-e2lsh"
N_CORPUS, N_QUERIES, N_INS = 67, 6, 13


def _stages_in(hlo_text: str) -> set[str]:
    """The query stages named as a component of some op's scope path
    (a transform may wrap the name, as in ``vmap(rerank)``)."""
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        for comp in path.split("/"):
            for stage in segments.QUERY_STAGES:
                if re.fullmatch(rf"(\w*\()*{stage}\)*", comp):
                    found.add(stage)
    return found


def _mutated_index(cls, **kw):
    corpus, queries = grids.corpus_and_queries(N_CORPUS, N_QUERIES)
    idx = cls(grids.grid_family(KIND), bucket_cap=16, max_deltas=64,
              **kw).build(corpus)
    idx.insert(np.asarray(corpus[:N_INS]) + 0.5)
    idx.delete([3, 10])
    return idx, queries


@pytest.mark.parametrize("probes", [1, 4])
def test_stages_name_segmented_query_ops(probes):
    idx, queries = _mutated_index(DeviceLSHIndex)
    view = idx.store.view
    lowered = segments.segmented_query.lower(
        idx.family, view.all_arrays, jnp.asarray(idx._mults), queries,
        metric=idx.metric, topk=5, caps=view.all_caps, probes=probes)
    assert _stages_in(lowered.as_text(dialect="hlo", debug_info=True)) == set(
        segments.QUERY_STAGES)


def test_stages_name_shard_map_program_ops():
    idx, queries = _mutated_index(ShardedLSHIndex, shards=1)
    assert idx.query_path == "shard_map"
    view = idx.store.view
    lowered = index_sharding.shard_map_query.lower(
        idx.family, view.seg_arrays(0), view.delta_arrays,
        jnp.asarray(idx._mults), queries, metric=idx.metric, topk=5,
        cap=view.base.cap, delta_caps=view.delta_caps, mesh=idx.mesh,
        axis=idx.mesh_axis)
    text = lowered.as_text(dialect="hlo", debug_info=True)
    assert "shard_map" in text
    assert _stages_in(text) == set(segments.QUERY_STAGES)


# ---------------------------------------------------------------------------
# Host spans
# ---------------------------------------------------------------------------


def _host_spans(log_dir) -> list[dict]:
    """Every ``lsh.*`` span in the trace under ``log_dir``: name, host
    line (one per thread), start and end (ns), args."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("lsh."):
                    out.append({"name": e.name, "line": li,
                                "start": e.start_ns,
                                "end": e.start_ns + e.duration_ns,
                                "args": dict(e.stats)})
    return out


def _inside(child, parent) -> bool:
    return (child["line"] == parent["line"]
            and parent["start"] <= child["start"]
            and child["end"] <= parent["end"])


@pytest.fixture(scope="module")
def traced_churn(tmp_path_factory):
    """A durable service behind the scheduler with ``max_deltas=1``, so the
    second insert folds: deletes, inserts and queries, traced."""
    tmp = tmp_path_factory.mktemp("traced")
    corpus, queries = grids.corpus_and_queries(N_CORPUS, N_QUERIES)
    svc = DurableLSHService(grids.grid_family(KIND), str(tmp / "durable"),
                            metric="euclidean", bucket_cap=16, max_deltas=1,
                            snapshot_every=10_000).build(corpus)
    sched = ServingScheduler(svc, max_batch=8, deadline_ms=1)
    batch = np.asarray(corpus[:N_INS]) + 0.5
    # warm every program first, so the trace holds no compile
    for f in [sched.insert(batch), sched.delete([1, 2]),
              sched.insert(batch)]:
        f.result(timeout=300)
    for q in queries:
        sched.query(q, topk=5).result(timeout=300)
    folds = svc.stats.auto_compactions
    with jax.profiler.trace(str(tmp / "trace")):
        for f in [sched.delete([4, 5]), sched.insert(batch),
                  sched.insert(batch)]:
            f.result(timeout=300)
        for f in [sched.query(q, topk=5) for q in queries]:
            f.result(timeout=300)
    sched.close()
    svc.close()
    assert svc.stats.auto_compactions == folds + 1
    return _host_spans(tmp / "trace")


def test_fold_phases_nest_in_the_fold_on_the_ingest_lane(traced_churn):
    spans = traced_churn
    folds = [s for s in spans if s["name"] == "lsh.fold"]
    assert len(folds) == 1
    fold = folds[0]
    ingest = [s for s in spans if s["name"] == "lsh.ingest.insert"
              and _inside(fold, s)]
    assert len(ingest) == 1
    for stage in ("order", "gather", "sort", "tables"):
        inner = [s for s in spans if s["name"] == f"lsh.fold.{stage}"]
        assert inner and all(_inside(s, fold) for s in inner), stage
    # one identifier across the mutation's ingest, fold and WAL spans
    seq = ingest[0]["args"]["seq"]
    assert fold["args"]["seq"] == seq
    appends = [s for s in spans if s["name"] == "lsh.wal.append"
               and s["args"].get("seq") == seq]
    assert len(appends) == 1


def test_wal_sync_on_the_committer_thread(traced_churn):
    spans = traced_churn
    ingest_lines = {s["line"] for s in spans
                    if s["name"].startswith("lsh.ingest.")}
    appends = [s for s in spans if s["name"] == "lsh.wal.append"]
    syncs = [s for s in spans if s["name"] == "lsh.wal.sync"]
    assert len(appends) == 3 and len(syncs) == 3
    assert all(any(_inside(y, a) for a in appends) for y in syncs)
    assert not {s["line"] for s in syncs} & ingest_lines
    finishes = [s for s in spans if s["name"] == "lsh.wal.finish"]
    assert {s["line"] for s in finishes} <= ingest_lines
    assert sorted(s["args"]["seq"] for s in appends) == sorted(
        s["args"]["seq"] for s in spans
        if s["name"].startswith("lsh.ingest."))


def test_query_lane_spans(traced_churn):
    spans = traced_churn
    batches = [s for s in spans if s["name"] == "lsh.sched.batch"]
    calls = [s for s in spans if s["name"] == "lsh.query.call"]
    waits = [s for s in spans if s["name"] == "lsh.query.wait"]
    assert batches and len(calls) == len(batches) == len(waits)
    assert all(any(_inside(c, b) for b in batches) for c in calls)
    assert all(any(_inside(w, c) for c in calls) for w in waits)
    assert sum(b["args"]["n"] for b in batches) == N_QUERIES
    # never one span per request
    assert len(calls) <= N_QUERIES


# ---------------------------------------------------------------------------
# One timing per site
# ---------------------------------------------------------------------------


@pytest.fixture
def recorded(monkeypatch):
    """Every span opened, by name -> its measured seconds, in order."""
    seen: dict[str, list] = {}

    class Recording(tracing.span):
        __slots__ = ("_name",)

        def __init__(self, name, **args):
            super().__init__(name, **args)
            self._name = name

        def __exit__(self, *exc):
            out = super().__exit__(*exc)
            seen.setdefault(self._name, []).append(self.seconds)
            return out

    monkeypatch.setattr(tracing, "span", Recording)
    return seen


def test_counters_are_the_spans_durations(recorded, tmp_path):
    corpus, queries = grids.corpus_and_queries(N_CORPUS, N_QUERIES)
    svc = DurableLSHService(grids.grid_family(KIND), str(tmp_path),
                            metric="euclidean", bucket_cap=16, max_deltas=1,
                            snapshot_every=10_000).build(corpus)
    batch = np.asarray(corpus[:N_INS]) + 0.5
    svc.insert(batch)
    svc.delete([0, 3])
    svc.insert(batch)                                 # folds
    svc.query_arrays(queries, topk=5)
    svc.close()
    st = svc.stats
    assert st.auto_compactions == 1
    assert svc.index.auto_compact_s == sum(recorded["lsh.fold"])
    assert st.auto_compact_ms == svc.index.auto_compact_s * 1e3
    wal = 0.0
    for begin, finish in zip(recorded["lsh.wal.begin"],
                             recorded["lsh.wal.finish"]):
        wal += (begin + finish) * 1e3
    assert st.wal_appends == 3 and st.wal_ms == wal
    assert st.total_ms == recorded["lsh.query.call"][0] * 1e3
    inserts = recorded["lsh.index.insert"]
    assert st.insert_ms == pytest.approx(
        (inserts[0] * 1e3) + (inserts[1] - recorded["lsh.fold"][0]) * 1e3,
        rel=1e-12)


def test_seq_is_inherited_on_its_thread():
    with tracing.span("lsh.test.outer", seq=7) as outer:
        assert tracing.current_seq() == 7
        with tracing.span("lsh.test.inner", seq=None):
            assert tracing.current_seq() == 7
    assert tracing.current_seq() is None
    assert outer.seconds >= 0.0


def test_answers_bit_identical_with_profiler_on(tmp_path):
    idx, queries = _mutated_index(DeviceLSHIndex)
    off = [np.asarray(a) for a in idx.query_batch(queries, 5, probes=2)]
    with jax.profiler.trace(str(tmp_path)):
        on = [np.asarray(a) for a in idx.query_batch(queries, 5, probes=2)]
    for a, b in zip(off, on):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.uint8), b.view(np.uint8))


def test_compile_cache_keys_keep_the_scopes(monkeypatch):
    """Two programs that differ only in their scopes get different cache
    keys once the cache is on, so a cached executable names its ops by
    the scopes of the program that asked for it."""
    from jax._src import cache_key, compiler
    from repro import compile_cache

    def lowered(stage):
        def f(x):
            with jax.named_scope(stage):
                return x * 2.0
        return jax.jit(f).lower(jnp.ones(4)).compiler_ir()

    def key(stage):
        backend = jax.devices()[0].client
        return cache_key.get(lowered(stage), np.asarray(jax.devices()[:1]),
                             compiler.get_compile_options(1, 1),
                             backend)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    try:
        jax.config.update(flag, False)
        assert key("hash") == key("probe")
        compile_cache.enable_compile_cache()
        assert getattr(jax.config, flag)
        assert key("hash") != key("probe")
    finally:
        jax.config.update(flag, before)
