"""The expert layers as a share of their roofline. A call's least time is
the larger of its FLOPs over the peak and of the bytes it has to move
over the bandwidth: the matrices of the experts its tokens TOUCHED
(``experts_touched`` and ``experts_touched_prefill`` of the traced
stretch's ``serve/step`` spans: distinct experts summed over the expert
layers, decode steps' and prompt chunks'), the shared expert and the
router once a call, the tokens in and out
(``work_latent_moe.expert_layer_least_seconds``; the sums over the
stretch bound the sum of the calls' own bounds from below). Divided by
the device time of EVERY operation that does part of an expert layer:
whatever ran under the program's scopes ``moe_route``, ``moe_experts``,
``moe_combine`` and ``moe_shared`` (the kind's ``scope_s``). Where the
scopes' times are not known the metric is left out, not guessed."""


def read(ctx):
    from benchmark import program_spans as ps
    from benchmark import work_latent_moe as work
    scope_s = ctx["counters"].get("scope_s")
    if ctx["trace"] is None or not scope_s:
        return None
    seconds = sum(scope_s.get(s, 0.0) for s in work.EXPERT_SCOPES)
    steps = [s.attrs for s in ps.in_stretch(ctx)
             if s.name == "serve/step" and "experts_touched" in s.attrs]
    if seconds <= 0 or not steps:
        return None
    model = ctx["model"]
    sparse = model["n_layers"] - model["leading_dense"]
    tokens = sparse * sum(a["prefill_tokens"] + a["decode_tokens"]
                          for a in steps)
    touched = sum(a["experts_touched"] + a["experts_touched_prefill"]
                  for a in steps)
    calls = sparse * sum((a["prefill_tokens"] > 0) + (a["decode_tokens"] > 0)
                         for a in steps)
    least = work.expert_layer_least_seconds(model, tokens, touched, calls,
                                            ctx["peaks"])
    ctx["notes"].append(
        f"moe_experts_roofline.serve: {calls} expert layers run, "
        f"{touched} experts touched ({touched / max(calls, 1):.1f} a "
        f"layer), {tokens // sparse} tokens; {seconds:.4f} s under "
        f"{ {s: round(scope_s.get(s, 0.0), 4) for s in work.EXPERT_SCOPES} }"
        f"; least "
        f"{least:.4f} s")
    return 100.0 * least / seconds
