"""Host spans on the profiler's clock.

``span(name, **args)`` wraps ``jax.profiler.TraceAnnotation``: while a
profiler runs (``jax.profiler.trace``), the span lands on the host line of
its thread in the same trace, and on the same clock, as the device's
operations and program executions. Args are encoded as TraceMe encodes
them (``name#k=v,...#``), so the trace carries them as the event's stats.
With no profiler running a span costs one ``TraceAnnotation``
construction and a clock read at each end.

A span also measures itself: after the block, ``.seconds`` holds its wall
time, so a counter at the same site is fed from that one timing.

A span given ``seq=`` makes it its thread's current sequence number until
the span ends; spans opened inside it on that thread carry the same
``seq`` without being told (``current_seq`` hands it to another thread).
The serving plane's ingest lane numbers its operations this way, so one
mutation's ingest, WAL and fold spans share one identifier.

Spans sit at per-call, per-batch, per-chunk or per-phase granularity,
never per request and never inside jitted code. Names are
``lsh.<layer>[.<stage>]``.
"""

from __future__ import annotations

import contextvars
import time

import jax

_SEQ: contextvars.ContextVar[int | None] = contextvars.ContextVar(
    "repro_tracing_seq", default=None)


def current_seq() -> int | None:
    """The sequence number of the innermost open ``seq`` span on this
    thread, or None."""
    return _SEQ.get()


class span:
    """``with span("lsh.fold", seq=3) as s: ...`` then ``s.seconds``."""

    __slots__ = ("_annotation", "_seq", "_token", "_t0", "seconds")

    def __init__(self, name: str, **args):
        args = {k: v for k, v in args.items() if v is not None}
        self._seq = args.get("seq")
        if self._seq is None and _SEQ.get() is not None:
            args["seq"] = _SEQ.get()
        self._annotation = jax.profiler.TraceAnnotation(name, **args)
        self._token = None
        self.seconds: float | None = None

    def __enter__(self) -> "span":
        if self._seq is not None:
            self._token = _SEQ.set(self._seq)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self._token is not None:
            _SEQ.reset(self._token)
        return False
