"""Host milliseconds of one service query call beyond the wait for the
device (dispatch, and the copy of the answers to the host): the mean self
time of ``lsh.query.call`` less its ``lsh.query.wait`` child
(``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.span_ms(ctx, "lsh.query.call", "self_s")
