"""A latent-attention, sparse-expert model's whole serving step as a
share of the chip's peak: required FLOPs of every prompt token whose
chunk was dispatched in the traced stretch (the program's
``serve/prefill_chunk`` spans: ``tokens`` at positions from ``index``
chunks on) and of every token generated in it (the clients' count), over
the stretch times the peak. Two per ACTIVE matrix parameter (attention's
projections, the dense layer, the router, 8 routed experts and the shared
one), the output head once a prompt (its last chunk) and once a
generated token, and attention in the cheaper of its two exact forms
(``work_latent_moe.py``). A program without the span reads nothing."""


def read(ctx):
    from benchmark import program_spans as ps
    from benchmark import work_latent_moe as work
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
    flops += work.decode_attention_flops(model, generated)
    for c in chunks:
        flops += work.chunk_attention_flops(model, c["index"] * size,
                                            c["tokens"])
    ctx["notes"].append(
        f"step_mfu.serve_latent_moe: {len(chunks)} chunks of "
        f"{prompt_tokens} prompt tokens and {len(generated)} generated "
        f"tokens in the stretch")
    return 100.0 * flops / (trace["window_s"] * ctx["peaks"]["flops_bf16"]
                            * trace["n_devices"])
