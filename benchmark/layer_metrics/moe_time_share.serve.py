"""Device time of every operation under the expert layers' scopes
(``moe_route``, ``moe_experts``, ``moe_combine``, ``moe_shared``: the
kind's ``scope_s``) over the traced stretch's busy time. Left out where
the scopes' times are not known."""


def read(ctx):
    from benchmark.work_latent_moe import EXPERT_SCOPES
    scope_s = ctx["counters"].get("scope_s")
    if ctx["trace"] is None or not scope_s:
        return None
    seconds = sum(scope_s.get(s, 0.0) for s in EXPERT_SCOPES)
    if seconds <= 0:
        return None
    ctx["notes"].append(
        f"moe_time_share.serve: {seconds:.4f} s of "
        f"{ctx['trace']['busy_s']:.4f} s busy; all scopes "
        f"{ {k: round(v, 4) for k, v in scope_s.items()} }")
    return 100.0 * seconds / ctx["trace"]["busy_s"]
