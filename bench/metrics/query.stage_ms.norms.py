"""Device time of the query program's ``norms`` stage (ms per execution
of ``segmented_query``): its ops' share of the program's executions in
the trace, by the ``jax.named_scope`` that names them
(``bench/spans.py``)."""

from bench import spans


def read(ctx):
    return spans.stage_ms(ctx, "norms")
