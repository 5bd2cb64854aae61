"""A retention model's whole serving step as a share of the chip's peak:
required FLOPs of every prompt token whose chunk was dispatched in the
traced stretch (the program's ``serve/prefill_chunk`` spans: ``tokens``
at positions from ``index`` chunks on) and of every token generated in it
(the clients' count), over the stretch times the peak. Two per matrix
parameter, the output head once a prompt (its last chunk) and once a
generated token, and retention in the cheaper of its two exact forms
(``work_retention.py``). A program without the span reads nothing."""


def read(ctx):
    from benchmark import program_spans as ps
    from benchmark import work_retention as work
    trace, traced = ctx["trace"], ctx["counters"].get("traced")
    if trace is None or traced is None:
        return None
    chunks = [s.attrs for s in ps.in_stretch(ctx)
              if s.name == "serve/prefill_chunk"]
    if not chunks:
        return None
    model, ta, tb = ctx["model"], *traced
    size = ctx["traffic"]["prefill_chunk"]
    generated = [n_prompt + j for t, n_prompt, j in ctx["counters"]["tokens"]
                 if ta <= t < tb and j > 0]
    prompt_tokens = sum(c["tokens"] for c in chunks)
    flops = work.token_flops(
        model, prompt_tokens + len(generated),
        sum(1 for c in chunks if c["last"]) + len(generated))
    flops += work.retention_flops(model, generated)
    for c in chunks:
        first = c["index"] * size
        flops += work.retention_flops(
            model, range(first + 1, first + c["tokens"] + 1))
    ctx["notes"].append(
        f"step_mfu.serve_retention: {len(chunks)} chunks of "
        f"{prompt_tokens} prompt tokens and {len(generated)} generated "
        f"tokens in the stretch")
    return 100.0 * flops / (trace["window_s"] * ctx["peaks"]["flops_bf16"]
                            * trace["n_devices"])
