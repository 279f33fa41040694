"""Device time of one query batch: the ``segmented_query`` program's
executions in the trace, over how many there were."""

PROGRAM = "segmented_query"


def read(ctx):
    t = ctx.get("trace")
    if not t or not t["program_n"].get(PROGRAM):
        return None
    return 1e3 * t["program_s"][PROGRAM] / t["program_n"][PROGRAM]
