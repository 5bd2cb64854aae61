"""95th percentile of submit -> first token in the client's hands, over the
same requests as the end-to-end median (those submitted in the window; a
request never answered counts as infinite). Entry point: FrontEnd."""


def read(ctx):
    from benchmark import harness
    samples = ctx["counters"].get("ttft_ms")
    if not samples:
        return None
    ctx["notes"].append(f"ttft_p95.serve over {len(samples)} requests")
    return harness.percentile(samples, 95)
