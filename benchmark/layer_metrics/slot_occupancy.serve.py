"""Live slots over slots, mean over the window's steps, read after each
``FrontEnd.step`` (``eng.S - eng.free_slots``)."""


def read(ctx):
    t0, t1 = ctx["counters"]["window"]
    live = [s[2] for s in ctx["counters"]["steps"] if t0 <= s[0] < t1]
    if not live:
        return None
    return 100.0 * sum(live) / (len(live) * ctx["counters"]["slots"])
