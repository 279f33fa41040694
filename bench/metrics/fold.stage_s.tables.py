"""Seconds per compaction fold in its ``tables`` phase: the self time of the
``lsh.fold.tables`` spans inside ``lsh.fold`` spans, over the traced
window's folds (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.fold_stage_s(ctx, "tables")
