"""The readers of the program's own spans, on a tiny serving run on the CPU:
the benchmark's loop over ``FrontEnd`` under a ``jax.profiler`` session, as
``harness.TracedStretch`` runs one, with no other switch."""

import time

import jax
import pytest

from benchmark import harness, program_spans
from benchmark.kinds import serve_closed

SEED = 2**31 + 2025
READERS = ("host_work_p50.serve", "admit_host_p50.serve",
           "queue_wait_p50.serve", "kv_pages_used.serve")


def _read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


@pytest.fixture(autouse=True)
def _empty_ring():
    from paddle_tpu.observability import trace
    trace.disable()
    trace.clear(capacity=1 << 16)
    yield
    trace.clear()


def test_readers_on_a_tiny_run(tiny_serve_cell, tmp_path):
    _, eng, loop = serve_closed.setup(tiny_serve_cell, SEED, harness.Spans())
    for c in loop.clients:
        loop.submit(c)
    while loop.waiting_for_first_token():
        loop.pump()
    ctx = {"counters": {"traced": None}, "notes": []}
    assert program_spans.in_stretch(ctx) == []     # a --trace 0 run
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    ta = time.perf_counter()
    try:
        while len(loop.finished) < 6:
            loop.pump()
    finally:
        tb = time.perf_counter()
        jax.profiler.stop_trace()
    loop.pump()                                    # after the stretch: off
    ctx["counters"]["traced"] = (ta, tb)

    spans = program_spans.in_stretch(ctx)
    steps = [s for s in spans if s.name == program_spans.STEP]
    assert steps and all(ta <= s.start and s.end <= tb for s in spans)
    own = program_spans.self_seconds(spans)
    assert sum(own.values()) == pytest.approx(
        sum(program_spans.seconds(s) for s in spans if s.parent == 0))

    values = {name: _read(name, ctx) for name in READERS}
    # the step less its waits: positive, and under the step itself
    step_ms = sorted(program_spans.seconds(s) * 1e3 for s in steps)
    assert 0 < values["host_work_p50.serve"] < step_ms[-1]
    admits = [s for s in spans if s.name == "serve/admit"]
    assert len(admits) >= 6 - len(loop.clients) + 1
    assert min(program_spans.seconds(s) for s in admits) * 1e3 \
        <= values["admit_host_p50.serve"] \
        <= max(program_spans.seconds(s) for s in admits) * 1e3
    assert 0 < values["queue_wait_p50.serve"] < (tb - ta) * 1e3
    # every live slot holds a page; the pool has two a slot
    assert 100.0 / eng.P <= values["kv_pages_used.serve"] <= 100.0
    notes = "\n".join(ctx["notes"])
    for word in ("feed", "admit", "dispatch", "replay", "the rest",
                 "serve/device_wait", "by bucket", "pages held"):
        assert word in notes


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing(name):
    """No traced stretch, or a ring with no span of it (a program whose
    spans a profiler session does not switch on): ``None``, no raise."""
    from paddle_tpu.observability import trace
    assert _read(name, {"counters": {"traced": None}, "notes": []}) is None
    trace.enable()
    with trace.span("serve/frontend_step"):       # before the stretch
        with trace.span("serve/step", pages_used=1, pages=8, live_tokens=3):
            with trace.span("serve/admit", bucket=16):
                pass
    trace.complete("serve/queue", time.perf_counter() - 0.01)
    trace.disable()
    now = time.perf_counter()
    ctx = {"counters": {"traced": (now, now + 5.0)}, "notes": []}
    assert _read(name, ctx) is None and ctx["notes"] == []
