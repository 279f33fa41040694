"""The reduction of the program's own spans and device scopes
(``bench/spans.py``): on a hand-made trace whose answers are known, on
the committed chip trace of ``sift1m.batch-t8`` (which holds none, as a
program without spans would give), and on one fold cut from a traced
``sift1m.churn`` run on the chip (``trace_sift1m_churn_fold.json.gz``:
its ``lsh.*`` and ``bench.*`` spans and program executions, and 30 ms of
its ops with their stages)."""

from pathlib import Path

import pytest

from bench import spans, spec, trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6   # ns per ms
NEW_METRICS = [m["name"] for m in spec.load_benchmark()["per_layer"]
               if m["name"].startswith(("query.stage_ms.", "query.host_ms",
                                        "fold.stage_s.", "store.refresh"))]


def _hand_made():
    sq, other = "jit_segmented_query(1)", "jit_other(2)"
    ops = [("while.1", 10, 30, "norms"), ("fusion.2", 12, 20, "norms"),
           ("gather.3", 30, 38, "rerank"), ("sort.4", 38, 40, "select"),
           ("copy.5", 60, 62, "unscoped"), ("fusion.6", 62, 70, "hash"),
           ("x.7", 80, 90, "unscoped")]
    return {
        "devices": {"/device:TPU:0": {
            "ops": [[n, s * MS, e * MS] for n, s, e, _ in ops],
            "modules": [[sq, 10 * MS, 40 * MS], [sq, 60 * MS, 70 * MS],
                        [other, 80 * MS, 90 * MS]],
            "op_stage": [["other" if n == "x.7" else "segmented_query", st]
                         for n, _, _, st in ops]}},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.query_call", 5 * MS, 45 * MS],
                 ["bench.generate", 45 * MS, 58 * MS],
                 ["bench.query_call", 58 * MS, 95 * MS]],
        "spans": [  # line 1: the caller; line 2: the ingest lane
            ["lsh.query.call", 5 * MS, 44 * MS, 1, {"n": 4}],
            ["lsh.query.wait", 8 * MS, 40 * MS, 1, {}],
            ["lsh.ingest.insert", 42 * MS, 60 * MS, 2, {"seq": 3}],
            ["lsh.fold", 43 * MS, 59 * MS, 2, {"seq": 3}],
            ["lsh.fold.gather", 44 * MS, 50 * MS, 2, {"seq": 3}],
            ["lsh.yield", 46 * MS, 48 * MS, 2, {"seq": 3}],
            ["lsh.fold.sort", 50 * MS, 55 * MS, 2, {"seq": 3}]]}


def test_span_self_times_and_nesting():
    sp = spans.reduce(_hand_made())["spans"]
    assert sp["lsh.query.call"]["n"] == 1
    assert sp["lsh.query.call"]["total_s"] == pytest.approx(0.039)
    assert sp["lsh.query.call"]["self_s"] == pytest.approx(0.007)
    assert sp["lsh.query.wait"]["self_s"] == pytest.approx(0.032)
    assert sp["lsh.ingest.insert"]["self_s"] == pytest.approx(0.002)
    assert sp["lsh.fold"]["self_s"] == pytest.approx(0.005)
    assert sp["lsh.fold.gather"]["self_s"] == pytest.approx(0.004)
    within = sp["lsh.yield"]["within"]
    assert set(within) == {"lsh.ingest.insert", "lsh.fold",
                           "lsh.fold.gather"}
    assert within["lsh.fold"]["total_s"] == pytest.approx(0.002)
    assert "within" not in sp["lsh.query.call"] or not (
        sp["lsh.query.call"]["within"])


def test_scope_seconds_count_nested_ops_once():
    s = spans.reduce(_hand_made())
    sq = s["scope_s"]["segmented_query"]
    assert sq == pytest.approx({"norms": 0.020, "rerank": 0.008,
                                "select": 0.002, "hash": 0.008,
                                "unscoped": 0.002})
    assert s["scope_s"]["other"] == pytest.approx({"unscoped": 0.010})
    # the stages and the rest make up the program's device time
    t = trace.reduce(_hand_made())
    assert sum(sq.values()) == pytest.approx(
        t["program_s"]["segmented_query"])


def test_idle_goes_to_lsh_spans_before_bench_spans():
    events = _hand_made()
    idle = spans.reduce(events)["idle"]
    assert idle == pytest.approx({
        "other": 0.010, "lsh.query.call": 0.005, "lsh.query.wait": 0.002,
        "lsh.ingest.insert": 0.002, "lsh.fold": 0.005,
        "lsh.fold.gather": 0.004, "lsh.yield": 0.002, "lsh.fold.sort": 0.005,
        "bench.query_call": 0.015})
    t = trace.reduce(events)
    assert sum(idle.values()) == pytest.approx(t["window_s"] - t["busy_s"])


def test_readers_on_the_hand_made_trace(monkeypatch):
    s = spans.reduce(_hand_made())
    monkeypatch.setattr(spans, "of_run", lambda ctx: s)
    ctx = {"trace": trace.reduce(_hand_made())}
    read = {m: spec.metric_reader(m)(ctx) for m in NEW_METRICS}
    # two executions of segmented_query
    assert read["query.stage_ms.norms"] == pytest.approx(10.0)
    assert read["query.stage_ms.norms.churn"] == pytest.approx(10.0)
    assert read["query.stage_ms.hash"] == pytest.approx(4.0)
    assert read["query.stage_ms.probe"] == 0.0
    assert read["query.host_ms"] == pytest.approx(7.0)
    assert read["fold.stage_s.gather"] == pytest.approx(0.004)
    assert read["fold.stage_s.sort"] == pytest.approx(0.005)
    assert read["fold.stage_s.yield"] == pytest.approx(0.002)
    assert read["fold.stage_s.order"] == read["fold.stage_s.tables"] == 0.0
    assert read["store.refresh_ms"] is None


def test_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    """A traced run of a program with no spans or scopes (the parent of
    this change) gives every new metric None, and none raises."""
    events = trace.load(DATA / "trace_sift1m_batch_t8.json.gz")
    s = spans.reduce(events)
    assert s["spans"] == {} and s["scope_s"] == {}
    monkeypatch.setattr(spans, "of_run", lambda ctx: s)
    ctx = {"trace": trace.reduce(events)}
    assert {m: spec.metric_reader(m)(ctx) for m in NEW_METRICS} == {
        m: None for m in NEW_METRICS}
    monkeypatch.setattr(spans, "of_run", lambda ctx: None)
    assert all(spec.metric_reader(m)({}) is None for m in NEW_METRICS)


def test_committed_chip_trace_reduces_as_before():
    """What the existing metrics read from the committed trace is
    unchanged, and with no ``lsh.*`` span open the idle attribution is
    the ``bench.*`` one ``trace.reduce`` gives."""
    events = trace.load(DATA / "trace_sift1m_batch_t8.json.gz")
    t = trace.reduce(events)
    assert t["busy_s"] == 1.171834671
    assert t["program_s"] == {"segmented_query": 1.15225804}
    assert t["program_n"] == {"segmented_query": 5.0}
    assert t["window_s"] == pytest.approx(1.2)
    idle = spans.reduce(events)["idle"]
    assert idle == pytest.approx(dict(t["idle_gaps"]), rel=1e-9)


def test_churn_fold_phases_account_for_the_fold(monkeypatch):
    """On one fold of a traced ``sift1m.churn`` run on the chip (its
    extracted events, cut to the fold), the five ``fold.stage_s`` phases
    sum to within 10% of the ``lsh.fold`` span, and the idle time there
    goes to the program's spans."""
    events = trace.load(DATA / "trace_sift1m_churn_fold.json.gz")
    s = spans.reduce(events)
    fold = s["spans"]["lsh.fold"]
    assert fold["n"] == 1
    monkeypatch.setattr(spans, "of_run", lambda ctx: s)
    phases = {st: spec.metric_reader(f"fold.stage_s.{st}")({})
              for st in ("order", "gather", "sort", "tables", "yield")}
    assert all(v > 0 for v in phases.values())
    assert sum(phases.values()) == pytest.approx(fold["total_s"], rel=0.1)
    assert spec.metric_reader("store.refresh_ms")({}) > 0
    lsh = sum(v for k, v in s["idle"].items()
              if k.startswith(spans.PREFIX))
    assert lsh > 0.9 * sum(s["idle"].values())
    t = trace.reduce(events)
    assert sum(s["idle"].values()) == pytest.approx(
        t["window_s"] - t["busy_s"])


def test_stalls_are_the_generators_longest_turns():
    events = {"host": [["bench.window", 0, 100 * MS],
                       ["bench.wait_due", 1 * MS, 3 * MS],
                       ["bench.wait_due", 3 * MS, 5 * MS],
                       ["bench.submit", 5 * MS, 45 * MS],   # a stall
                       ["bench.wait_due", 45 * MS, 47 * MS],
                       ["bench.wait_due", 47 * MS, 49 * MS]],
              "spans": [["lsh.store.refresh", 4 * MS, 30 * MS, 2, {}],
                        ["lsh.query.wait", 40 * MS, 60 * MS, 1, {}]]}
    top = spans.stalls(events, 0, 100 * MS, top=2)
    assert [round(t[1], 6) for t in top] == [0.040, 0.002]
    assert top[0][0] == pytest.approx(0.005)
    assert top[0][2] == pytest.approx({"lsh.store.refresh": 0.025,
                                       "lsh.query.wait": 0.005})


def test_of_run_checks_the_window(monkeypatch, tmp_path):
    path = tmp_path / "sift1m.churn" / "trace" / "p" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(b"")
    monkeypatch.setattr(spans, "OUT_DIR", tmp_path)
    monkeypatch.setattr(spans, "_reduced",
                        lambda p, m: {"window_s": 20.0, "spans": {}})
    assert spans.of_run({"trace": {"window_s": 20.0}})["window_s"] == 20.0
    assert spans.of_run({"trace": {"window_s": 19.0}}) is None
    assert spans.of_run({"trace": None}) is None


# ---------------------------------------------------------------------------
# The xplane's event metadata
# ---------------------------------------------------------------------------


def _varint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def _ld(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _vi(field: int, v: int) -> bytes:
    return _varint(field << 3) + _varint(v)


def _plane(name: str, ops: list, ref_scope: bool = False) -> bytes:
    """An XPlane with stat metadata 1 = program_id, 2 = tf_op and each op
    (text, program id, scope) as event metadata; ``ref_scope`` stores the
    scope as a reference to a stat metadata named by it."""
    stat_meta = [(1, "program_id"), (2, "tf_op")]
    events = b""
    for k, (text, program, scope) in enumerate(ops):
        if ref_scope:
            sid = 100 + k
            stat_meta.append((sid, scope))
            tf = _ld(5, _vi(1, 2) + _vi(7, sid))
        else:
            tf = _ld(5, _vi(1, 2) + _ld(5, scope.encode()))
        meta = (_vi(1, k + 1) + _ld(2, text.encode())
                + _ld(5, _vi(1, 1) + _vi(3, program)) + tf)
        events += _ld(4, _vi(1, k + 1) + _ld(2, meta))
    stats = b"".join(_ld(5, _vi(1, i) + _ld(2, _vi(1, i)
                                           + _ld(2, n.encode())))
                     for i, n in stat_meta)
    return _ld(1, _ld(2, name.encode()) + events + stats)


def test_op_scopes_reads_device_event_metadata(tmp_path):
    pid = 18256953051883041274
    ops = [("%fusion.3 = f32[8]{0} fusion(...)", pid,
            "jit(segmented_query)/probe/vmap(jit(searchsorted))/gather:"),
           ("%copy.2 = f32[8]{0} copy(...)", 7, "buf:")]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_plane("/device:TPU:0", ops)
                     + _plane("/device:TPU:1", ops[:1], ref_scope=True)
                     + _plane("/host:CPU", [("lsh.x", 1, "rerank")]))
    got = spans.op_scopes(str(path))
    assert got == {(pid, ops[0][0]): ops[0][2], (7, ops[1][0]): "buf:"}


@pytest.mark.parametrize("scope,stage", [
    ("jit(segmented_query)/probe/vmap(jit(searchsorted))/gather:", "probe"),
    ("jit(segmented_query)/vmap(rerank)/dot_general", "rerank"),
    ("jit(shard_map_query)/shard_map/norms/while/body/dot_general", "norms"),
    ("jit(segmented_query)/hash/dot_general", "hash"),
    ("jit(segmented_query)/select/sort", "select"),
    ("jit(_scatter_rows_chunk)/scatter:", "unscoped"),
    ("family.projection.factors[0]:", "unscoped"),
    ("jit(rehash)/gather", "unscoped")])
def test_stage_of_a_scope_path(scope, stage):
    assert spans.stage_of(scope) == stage
