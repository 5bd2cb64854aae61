"""Tokens whose keys and values are live (prompt plus generated, over the
slots in use, as the clients saw them after each ``FrontEnd.step``) over
the tokens the engine's page pool can hold: mean over the window's steps.
What the traffic holds of the memory the pool takes."""


def read(ctx):
    t0, t1 = ctx["counters"]["window"]
    live = [sum(s[3]) for s in ctx["counters"]["steps"] if t0 <= s[0] < t1]
    if not live:
        return None
    return 100.0 * sum(live) / (len(live) * ctx["counters"]["kv_pool_tokens"])
