"""``retention_step``'s share of its memory roofline. Required bytes: for
each call (one layer of one decode step) each LIVE decoding slot's ``S``
and ``z`` read once and written once in float32 at the exact ``phi`` of
8,256 entries (a layout's padding is not required work), plus that slot's
q, k, v and o (``work_retention.step_bytes_per_slot``). Live decoding
slots a step: the mean of ``decode_tokens`` over the ``serve/step`` spans
of the traced stretch that decoded. Divided by the device time of EVERY
operation whose name holds ``retention_step``."""


def read(ctx):
    from benchmark import program_spans as ps
    from benchmark import work_retention as work
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = ctx["trace_reduce"].family_time(trace, "retention_step")
    live = [s.attrs["decode_tokens"] for s in ps.in_stretch(ctx)
            if s.name == "serve/step" and s.attrs.get("decode_tokens")]
    if calls == 0 or not live:
        return None
    mean_live = sum(live) / len(live)
    nbytes = calls * mean_live * work.step_bytes_per_slot(ctx["model"])
    least = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    ctx["notes"].append(
        f"retention_step_roofline.serve: {calls} calls, "
        f"{seconds / calls * 1e3:.3f} ms a call, {mean_live:.2f} live "
        f"slots a step over {len(live)} steps, memory-bound")
    return 100.0 * least / seconds
