"""Median of ``serve/queue`` (submit -> admission into a slot, front-end
queue included) over the requests admitted in the traced stretch. Near
nothing in a closed loop on as many slots as clients; the number the
open-loop cells are for."""
import statistics


def read(ctx):
    from benchmark import program_spans as ps
    waits = [ps.seconds(s) for s in ps.in_stretch(ctx, ending=True)
             if s.name == "serve/queue"]
    if not waits:
        return None
    ctx["notes"].append(f"queue_wait_p50.serve over {len(waits)} requests, "
                        f"longest {max(waits) * 1e3:.3f} ms")
    return statistics.median(waits) * 1e3
