"""The timed path broken underneath: every fault the cells can have turns
``correct`` false. (No cell spans chips, so there is no exchange between
chips to leave out.)"""

import numpy as np
import pytest

from bench import harness
from repro.core import segments
from repro.serving import lsh_service
from repro.serving.lsh_service import LSHService

SEED = 2**31 + 23
real_query = LSHService.query_arrays


def altered_answer(self, queries, topk=10, **kw):
    ids, scores, n = real_query(self, queries, topk, **kw)
    ids = ids.copy()
    ids[:, [0, 1]] = ids[:, [1, 0]]          # an answer altered at source
    return ids, scores, n


def half_batch(self, queries, topk=10, **kw):
    ids, scores, n = real_query(self, queries, topk, **kw)
    half = max(ids.shape[0] // 2, 1)         # the rest left out
    ids, scores, n = ids.copy(), scores.copy(), n.copy()
    ids[half:], scores[half:], n[half:] = -1, np.inf, 0
    return ids, scores, n


def altered_score(self, queries, topk=10, **kw):
    ids, scores, n = real_query(self, queries, topk, **kw)
    return ids, scores + np.float32(0.05), n


@pytest.mark.parametrize("cell", ["tiny-dense.batch-t8", "tiny-cp.batch-t1",
                                  "tiny-dense.served", "tiny-dense.churn"])
@pytest.mark.parametrize("fault", [altered_answer, half_batch,
                                   altered_score])
def test_broken_query_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    monkeypatch.setattr(lsh_service.LSHService, "query_arrays", fault)
    line = harness.run_cell(cell, SEED, 0.5, False, root=tiny_root,
                            require_tpu=False)
    assert not line["correct"]


@pytest.mark.parametrize("step", ["insert", "delete"])
def test_mutation_that_leaves_state_unchanged_is_not_correct(
        tiny_root, monkeypatch, step):
    if step == "insert":
        monkeypatch.setattr(LSHService, "insert",
                            lambda self, batch, batch_size=2048: self)
    else:
        monkeypatch.setattr(LSHService, "delete", lambda self, ids: len(ids))
    line = harness.run_cell("tiny-dense.churn", SEED, 1.0, False,
                            root=tiny_root, require_tpu=False)
    assert not line["correct"]


def _churn(tiny_root):
    return harness.run_cell("tiny-dense.churn", SEED, 1.0, False,
                            root=tiny_root, require_tpu=False)


def test_deltas_left_unprobed_is_not_correct(tiny_root, monkeypatch):
    """Queries read the base alone: inserts still in a delta are lost."""
    monkeypatch.setattr(segments.StoreView, "all_arrays",
                        property(lambda v: (v.seg_arrays(0),)))
    monkeypatch.setattr(segments.StoreView, "all_caps",
                        property(lambda v: (v.segments[0].cap,)))
    line = _churn(tiny_root)
    assert not line["correct"]
    assert line["checks"]["inserts_lost"]["value"] > 0


def test_tombstone_mask_dropped_is_not_correct(tiny_root, monkeypatch):
    """Queries read every slot as live: tombstoned items come back."""
    real = segments.StoreView.seg_arrays

    def every_slot_live(view, i):
        corpus, sorted_keys, perm, live, eff, win = real(view, i)
        live = live.at[:-1].set(True)
        if win is not None:
            win = segments._live_window_tables(perm, live)
        return corpus, sorted_keys, perm, live, eff, win

    monkeypatch.setattr(segments.StoreView, "seg_arrays", every_slot_live)
    line = _churn(tiny_root)
    assert not line["correct"]
    assert line["checks"]["deletes_found"]["value"] > 0
