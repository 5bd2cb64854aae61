"""95th percentile of all gaps between consecutive tokens of one request
whose later token fell in the window, as the client sees them after each
``FrontEnd.step``. Entry point: FrontEnd. No end-to-end metric with a
bound, because in a closed loop over a lockstep decode the gaps sit on a
few levels (a step with no prefill, with one, with two) and this rank
falls on the edge between two of them: ONE step that the host delays by
70 ms moves it from 285 to 292 ms (PERF.md section 2)."""


def read(ctx):
    from benchmark import harness
    samples = ctx["counters"].get("gap_ms")
    if not samples:
        return None
    ctx["notes"].append(f"gap_p95.serve over {len(samples)} gaps")
    return harness.percentile(samples, 95)
