"""Seconds per compaction fold spent yielding the host to the query lane:
the ``lsh.yield`` spans inside ``lsh.fold`` spans, over the traced
window's folds (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.fold_stage_s(ctx, "yield")
