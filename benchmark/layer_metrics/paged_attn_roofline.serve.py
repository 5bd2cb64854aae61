"""``paged_append_attend``'s share of its roofline: per decode step and
layer it has to read each live slot's keys and values once (bytes from the
live contexts the clients saw at that step, ``work.paged_attend_work``);
the least time for that over the kernel's device time in the trace."""


def read(ctx):
    trace, traced = ctx["trace"], ctx["counters"].get("traced")
    if trace is None or traced is None:
        return None
    seconds, calls = ctx["trace_reduce"].family_time(trace,
                                                     "paged_append_attend")
    if calls == 0:
        return None
    ta, tb = traced
    least, bound = 0.0, "memory"
    for t, _, _, contexts in ctx["counters"]["steps"]:
        if ta <= t < tb and contexts:
            flops, nbytes = ctx["work"].paged_attend_work(ctx["model"],
                                                          contexts)
            per_call, bound = ctx["work"].least_seconds(flops, nbytes,
                                                        ctx["peaks"])
            least += per_call * ctx["model"]["n_layers"]
    ctx["notes"].append(
        f"paged_attn_roofline.serve: {calls} calls, "
        f"{seconds / calls * 1e3:.3f} ms a call, {bound}-bound")
    return 100.0 * least / seconds if least else None
