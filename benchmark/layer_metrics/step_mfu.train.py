"""The whole training step's share of the chip's peak: required FLOPs per
token (``work.train_flops_per_token``: 6 per matrix parameter with the
output head, causal attention, recomputation not counted) times the tokens
of the traced steps, over the traced stretch times the peak."""


def read(ctx):
    trace, c = ctx["trace"], ctx["counters"]
    if trace is None or not c.get("traced_steps"):
        return None
    per_token = ctx["work"].train_flops_per_token(ctx["model"], c["seq_len"])
    flops = per_token * c["tokens_per_step"] * c["traced_steps"]
    return 100.0 * flops / (trace["window_s"] * ctx["peaks"]["flops_bf16"]
                            * trace["n_devices"])
