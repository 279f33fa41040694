"""The reduction from a trace to what the per-layer metrics read: on a
hand-made trace whose answers are known, and on a trace recorded on the
chip (a traced ``sift1m.batch-t8`` run, its extracted events committed
under ``bench/tests/data``)."""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
MS = 1e6   # ns per ms


def test_program_name():
    assert trace.program_name("jit_segmented_query(123)") == "segmented_query"
    assert trace.program_name("jit__sort_tables") == "_sort_tables"
    assert trace.program_name("fusion.3") == "fusion"


def test_reduce_hand_made():
    events = {
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10 * MS, 30 * MS],
                    ["sort.2", 25 * MS, 40 * MS],
                    ["fusion.1", 60 * MS, 70 * MS],
                    ["copy", 95 * MS, 130 * MS]],     # runs past the window
            "modules": [["jit_segmented_query(7)", 10 * MS, 40 * MS],
                        ["jit_segmented_query(7)", 60 * MS, 70 * MS],
                        ["jit_other", 95 * MS, 130 * MS]]}},
        "host": [["bench.window", 0, 100 * MS],
                 ["bench.query_call", 5 * MS, 45 * MS],
                 ["bench.generate", 45 * MS, 58 * MS],
                 ["bench.query_call", 58 * MS, 90 * MS]]}
    s = trace.reduce(events)
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.030 + 0.010 + 0.005)
    assert s["program_n"] == {"segmented_query": 2}
    assert s["program_s"]["segmented_query"] == pytest.approx(0.040)
    assert "other" not in s["program_s"]          # not inside the window
    ops = dict(s["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.030)
    assert ops["copy"] == pytest.approx(0.005)     # clipped to the window
    idle = dict(s["idle_gaps"])
    # idle 0-5 under the window only, 5-10 and 40-45 under query_call,
    # 45-58 generate, 58-60 and 70-90 query_call, 90-95 the window only
    assert idle["bench.query_call"] == pytest.approx(0.005 + 0.005 + 0.002
                                                     + 0.020)
    assert idle["bench.generate"] == pytest.approx(0.013)
    assert idle["other"] == pytest.approx(0.010)
    assert sum(idle.values()) == pytest.approx(s["window_s"] - s["busy_s"])


def test_reduce_needs_a_device():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "host": [["bench.window", 0, 1]]})


def test_reduce_chip_trace():
    path = DATA / "trace_sift1m_batch_t8.json.gz"
    s = trace.reduce(trace.load(path))
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    n = s["program_n"]["segmented_query"]
    assert n >= 1
    per_batch = s["program_s"]["segmented_query"] / n
    assert 0.01 < per_batch < 1.0          # a 1024-query T=8 batch
    assert s["device_ops"] and len(s["device_ops"]) <= 10
    assert all(name.startswith("bench.") or name == "other"
               for name, _ in s["idle_gaps"])
