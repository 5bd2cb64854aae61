"""Pages the allocator holds over the pages of the pool, as the engine
counts them at the end of each ``serve/step`` of the traced stretch
(``pages_used`` / ``pages``); the mean. Page by page, beside
``kv_pool_fill.serve``'s token count: a slot with 49 live tokens holds a
whole page of 128."""


def read(ctx):
    from benchmark import program_spans as ps
    steps = [s.attrs for s in ps.in_stretch(ctx)
             if s.name == "serve/step" and "pages_used" in s.attrs]
    if not steps:
        return None
    mean = lambda key: sum(a[key] for a in steps) / len(steps)
    ctx["notes"].append(
        f"kv_pages_used.serve over {len(steps)} engine steps: mean "
        f"{mean('pages_used'):.2f} of {steps[0]['pages']} pages held, "
        f"{mean('live_tokens'):.1f} live tokens")
    return 100.0 * sum(a["pages_used"] / a["pages"] for a in steps) \
        / len(steps)
