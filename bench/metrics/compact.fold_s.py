"""Seconds of one automatic compaction fold (``ServiceStats``
``auto_compact_ms`` over ``auto_compactions``)."""


def read(ctx):
    if not ctx.get("folds"):
        return None
    return ctx["fold_ms"] / 1e3 / ctx["folds"]
