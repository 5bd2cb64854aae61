"""The program's own spans, for the per-layer metrics that read them.

``harness.TracedStretch`` runs a ``jax.profiler`` session, and for as long
as one runs the program's spans are live (``paddle_tpu/observability/
trace.py``): each lands in the profiler's trace and in the program's ring,
on ``time.perf_counter``, which is also the clock of ``counters["traced"]``.
So a ``--trace 1`` run finds the spans of its traced stretch in the ring
with no switch of its own. A program whose spans a profiler session does
not switch on leaves the ring empty: ``in_stretch`` then returns nothing
and every reader ``None``.

The serving step, as the program splits it (``docs/observability.md``):
``serve/frontend_step`` > ``serve/feed``, ``serve/step`` >
``serve/admit`` > ``serve/dispatch`` (``kind="prefill"``);
``serve/dispatch`` (the decode enqueue); ``serve/harvest`` >
``serve/device_wait`` (the one place the host blocks on the device).
``serve/queue`` (submit -> admission into a slot) is recorded after the
fact, with no parent.
"""

import collections

Span = collections.namedtuple("Span", "name start end id parent attrs")

STEP = "serve/frontend_step"
WAIT = "serve/device_wait"


def in_stretch(ctx, ending=False):
    """The program's spans that lie inside the traced stretch (``ending``:
    that end inside it, wherever they began), oldest first; seconds on
    ``time.perf_counter``."""
    traced = ctx["counters"].get("traced")
    if traced is None:
        return []
    from paddle_tpu.observability import trace
    ta, tb = traced
    out = []
    for name, t0_ns, dur_ns, _tid, sid, parent, attrs in trace.events()[0]:
        start, end = t0_ns * 1e-9, (t0_ns + dur_ns) * 1e-9
        if end <= tb and (ta <= end if ending else ta <= start):
            out.append(Span(name, start, end, sid, parent, attrs or {}))
    return out


def seconds(span):
    return span.end - span.start


def under(span, by_id, name):
    """The nearest span of ``name`` around ``span``, or ``None``."""
    while span is not None and span.name != name:
        span = by_id.get(span.parent)
    return span


def self_seconds(spans):
    """Self time by name: each span's duration less its children's."""
    own = {s.id: seconds(s) for s in spans}
    for s in spans:
        if s.parent in own:
            own[s.parent] -= seconds(s)
    by_name = collections.Counter()
    for s in spans:
        by_name[s.name] += own[s.id]
    return by_name
