"""The churn check's model of the store: the states the writer's log
publishes, and the reference's probe windows over segments with
tombstones."""

import numpy as np

from bench import reference
from bench.drivers import churn

N, B = 100, 10


def test_states_follow_deletes_inserts_and_folds():
    log = [("delete", 0, 1, B, False), ("insert", 1, 2, B, False),
           ("delete", 2, 3, B, False), ("insert", 3, 4, B, True),
           ("delete", 4, 5, B, False)]
    states = churn.store_states(log, N, B, B)
    got = [(s.segments, s.live_lo, s.live_hi, k) for s, k in states]
    assert got == [
        (((0, 100),), 0, 100, -1),
        (((0, 100),), 10, 100, 0),
        (((0, 100), (100, 110)), 10, 110, 1),
        (((0, 100), (100, 110)), 20, 110, 2),
        (((0, 100), (100, 110), (110, 120)), 20, 120, 3),   # delta first
        (((20, 120),), 20, 120, 3),                         # then the fold
        (((20, 120),), 30, 120, 4)]


def _index(keys):
    """A reference index over given keys (one table), no hashing."""
    idx = reference.Index.__new__(reference.Index)
    idx.fam = reference.HostFamily(
        factors=(), scale=1.0, offsets=np.zeros(1), num_codes=1,
        num_tables=1, width=1.0, mults=np.ones(1, np.uint32))
    idx.keys = np.asarray(keys, np.uint32)[:, None]
    n = idx.keys.shape[0]
    idx.whole = reference.Store(((0, n),), 0, n)
    idx._tables = {}
    return idx


def test_window_skips_tombstones_and_caps_each_segment():
    # items 0..9 share key 7 in the base, 10..13 in a delta
    idx = _index([7] * 10 + [7, 7, 7, 5])
    probe = np.array([[[7]]], np.uint32)
    store = reference.Store(((0, 10), (10, 14)), live_lo=3, live_hi=14)
    (ids,) = idx.window_ids(probe, 4, store)
    # the base's first 4 live (3..6), the delta's live members with key 7
    assert ids.tolist() == [3, 4, 5, 6, 10, 11, 12]
    (ids,) = idx.window_ids(probe, 4)
    assert ids.tolist() == [0, 1, 2, 3]
