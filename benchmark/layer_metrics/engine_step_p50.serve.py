"""Median host time of one ``FrontEnd.step`` in the window (the
benchmark's own span around the call). A step harvests the dispatch before
last, so in steady state this is the device's time per dispatch."""
import statistics


def read(ctx):
    t0, t1 = ctx["counters"]["window"]
    durations = ctx["spans"].durations("bench/frontend_step", t0, t1)
    if not durations:
        return None
    ctx["notes"].append(f"engine_step_p50.serve over {len(durations)} steps")
    return statistics.median(durations) * 1e3
