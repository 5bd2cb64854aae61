"""The whole serving step's share of the chip's peak: required FLOPs of
every prompt admitted and every token generated in the traced stretch (the
benchmark's count, ``work.py``) over the stretch times the peak."""


def read(ctx):
    trace, traced = ctx["trace"], ctx["counters"].get("traced")
    if trace is None or traced is None:
        return None
    work, model = ctx["work"], ctx["model"]
    ta, tb = traced
    flops = sum(work.prefill_flops(model, n)
                for t, n in ctx["counters"]["prefills"] if ta <= t < tb)
    flops += sum(work.decode_flops(model, n_prompt + j)
                 for t, n_prompt, j in ctx["counters"]["tokens"]
                 if ta <= t < tb and j > 0)
    if flops == 0:
        return None
    return 100.0 * flops / (trace["window_s"] * ctx["peaks"]["flops_bf16"]
                            * trace["n_devices"])
