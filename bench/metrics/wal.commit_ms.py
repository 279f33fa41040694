"""Write-ahead log time of one mutation, fsync included
(``ServiceStats.wal_ms`` over ``wal_appends``)."""


def read(ctx):
    if not ctx.get("wal_appends"):
        return None
    return ctx["wal_ms"] / ctx["wal_appends"]
