"""The three flash-attention kernels' share of their roofline: the least
time the chip could take for their calls (per call the larger of
FLOPs / peak and bytes / bandwidth, from shapes, ``work.flash_kernel_work``)
over their summed device time in the trace."""


def read(ctx):
    trace, c = ctx["trace"], ctx["counters"]
    if trace is None:
        return None
    work = ctx["work"].flash_kernel_work(ctx["model"], c["batch"],
                                         c["seq_len"])
    least, measured = 0.0, 0.0
    for kernel, (flops, nbytes) in work.items():
        seconds, calls = ctx["trace_reduce"].family_time(trace, kernel)
        if calls == 0:
            continue
        per_call, bound = ctx["work"].least_seconds(flops, nbytes,
                                                    ctx["peaks"])
        ctx["notes"].append(
            f"flash_roofline.train: {kernel} {calls} calls, "
            f"{seconds / calls * 1e3:.3f} ms a call against "
            f"{per_call * 1e3:.3f} ms ({bound}-bound)")
        least += per_call * calls
        measured += seconds
    if measured == 0.0:
        return None
    return 100.0 * least / measured
