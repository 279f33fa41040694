"""The query program's share of its roofline: the least time the chip
could take for a traced batch (``bench/work.py``: the larger of bytes
over memory bandwidth and FLOPs over the bf16 peak, counted from the
candidates each batch had, not from the implementation), on average, over
the program's mean device time per execution in the trace."""

from bench import work

PROGRAM = "segmented_query"


def read(ctx):
    t, batches = ctx.get("trace"), ctx.get("traced_batches")
    if (not t or not batches or not ctx.get("peaks")
            or not t["program_n"].get(PROGRAM)):
        return None
    least = sum(work.least_time(
        work.query_work(ctx["config"], rows, ctx["probes"], n_cand),
        ctx["peaks"])[0] for rows, n_cand in batches) / len(batches)
    return 100.0 * least / (t["program_s"][PROGRAM] / t["program_n"][PROGRAM])
