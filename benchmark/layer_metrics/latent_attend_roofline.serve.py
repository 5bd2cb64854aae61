"""The decode attend over latent pages as a share of its roofline.
Required: the live cached rows of every decoding step read ONCE at
``rank + rope`` numbers a row and layer (the row is key and value at
once), each slot's absorbed queries in and weighted sums out, against
the absorbed form's FLOPs (``work_latent_moe.attend_least_seconds``).
Live rows a step: ``latent_rows`` of the traced stretch's ``serve/step``
spans that decoded (the host's count at the END of the step, which runs
behind the device by the steps in flight: a lower bound). Divided by the
device time of EVERY operation whose name holds ``latent_attend``: the
attend and the write of the fresh row into its page."""


def read(ctx):
    from benchmark import program_spans as ps
    from benchmark import work_latent_moe as work
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = ctx["trace_reduce"].family_time(trace, "latent_attend")
    steps = [s.attrs for s in ps.in_stretch(ctx)
             if s.name == "serve/step" and s.attrs.get("decode_tokens")
             and "latent_rows" in s.attrs]
    if calls == 0 or not steps:
        return None
    layers = ctx["model"]["n_layers"]
    rows = layers * sum(a["latent_rows"] for a in steps)
    slots = layers * sum(a["decode_tokens"] for a in steps)
    least = work.attend_least_seconds(ctx["model"], rows, slots,
                                      layers * len(steps), ctx["peaks"])
    ctx["notes"].append(
        f"latent_attend_roofline.serve: {calls} kernel calls in "
        f"{seconds:.4f} s; {len(steps)} decoding steps of "
        f"{rows / layers / len(steps):.0f} live rows and "
        f"{slots / layers / len(steps):.1f} slots a step; least "
        f"{least:.4f} s")
    return 100.0 * least / seconds
