"""What one ``FrontEnd.step`` costs the host: per ``serve/frontend_step``
span of the traced stretch, its duration less the ``serve/device_wait``
spans inside it (the one place the host blocks on the device); the median.
The floor of the step once the device is fast. The notes split the mean
step by self time: feed / admit (the host's side of an admission, without
its prefill enqueue) / dispatch (every enqueue) / replay (``serve/harvest``
less the wait) / the rest (``serve/step`` and ``serve/frontend_step``
themselves), and give the wait's share of the step. What the host waits
for elsewhere counts as its work here: an admission whose eager slot-state
updates run into the runtime's cap of 32 programs in flight stands inside
``serve/admit`` until the running decode program ends (PERF.md section 5)."""
import statistics


def read(ctx):
    from benchmark import program_spans as ps
    spans = ps.in_stretch(ctx)
    by_id = {s.id: s for s in spans}
    steps = [s for s in spans if s.name == ps.STEP]
    if not steps:
        return None
    step_of = {s.id: ps.under(s, by_id, ps.STEP) for s in spans}
    inside = [s for s in spans if step_of[s.id] is not None]
    wait = dict.fromkeys((s.id for s in steps), 0.0)
    for s in inside:
        if s.name == ps.WAIT:
            wait[step_of[s.id].id] += ps.seconds(s)
    own = ps.self_seconds(inside)
    per_step = lambda *names: sum(own[n] for n in names) / len(steps) * 1e3
    total = sum(ps.seconds(s) for s in steps)
    ctx["notes"].append(
        f"host_work_p50.serve over {len(steps)} steps of mean "
        f"{total / len(steps) * 1e3:.3f} ms; mean ms a step: feed "
        f"{per_step('serve/feed'):.3f}, admit {per_step('serve/admit'):.3f}, "
        f"dispatch {per_step('serve/dispatch'):.3f}, replay "
        f"{per_step('serve/harvest'):.3f}, the rest "
        f"{per_step('serve/step', ps.STEP):.3f}; serve/device_wait "
        f"{per_step(ps.WAIT):.3f} = {100.0 * own[ps.WAIT] / total:.2f}% "
        f"of the step")
    return statistics.median(ps.seconds(s) - wait[s.id] for s in steps) * 1e3
