"""Device time of every operation whose name holds ``retention_`` (the
named kernels of the retention layers) over the traced stretch's busy
time. The chunked prefill's retention is plain ``jax.numpy`` under a
``jax.named_scope`` and is NOT in it: XLA's fusions carry no such name."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    seconds, calls = ctx["trace_reduce"].family_time(trace, "retention_")
    if calls == 0:
        return None
    ctx["notes"].append(
        f"retention_time_share.serve: {seconds:.4f} s in {calls} calls of "
        f"{trace['busy_s']:.4f} s busy")
    return 100.0 * seconds / trace["busy_s"]
