"""The device's idle share of the traced window: one minus the union of
its operations' intervals over the window's length."""

from bench import stats


def read(ctx):
    t = ctx.get("trace")
    if not t or t["window_s"] <= 0:
        return None
    return stats.idle_pct(t["busy_s"], t["window_s"])
