"""Milliseconds of one segment-store refresh on the live store (the
lookups a delete or an appended delta rebuilds): the mean
``lsh.store.refresh`` span (``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.span_ms(ctx, "lsh.store.refresh")
