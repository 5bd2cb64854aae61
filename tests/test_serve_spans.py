"""The serving step's spans (docs/observability.md, "Trace span
catalogue"): a ``jax.profiler`` session switches them on and they land in
its trace; ``FrontEnd.step`` splits into feed / admit / dispatch / harvest
with the one blocking transfer as ``serve/device_wait``; ``serve/step``
carries what the traffic holds of the page pool."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.decode_engine import DecodeEngine
from paddle_tpu.inference.paged_engine import PagedDecodeEngine
from paddle_tpu.models import gpt
from paddle_tpu.observability import trace
from paddle_tpu.serving import FrontEnd


@pytest.fixture(scope="module")
def model():
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=512, d_model=32,
                        n_layers=2, n_heads=4, dtype=jnp.float32)
    return gpt.GPT(cfg, seed=0)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear(capacity=1 << 16)   # an earlier file may have shrunk it
    yield
    trace.disable()
    trace.clear()


def _engine(model, kind):
    if kind == "paged":
        return PagedDecodeEngine(model, n_pages=8, max_slots=2)
    return DecodeEngine(model, max_slots=2, max_len=128)


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 96, size=n).tolist() for n in lengths]


def _tree(events):
    """(spans by id, name of each span's parent)."""
    by_id = {e[4]: e for e in events}
    parent = {e[4]: by_id[e[5]][0] if e[5] in by_id else None
              for e in events}
    return by_id, parent


def test_profiler_session_switches_spans_on_and_off(model, tmp_path):
    fe = FrontEnd(_engine(model, "paged"))
    first, second, third = _prompts((5, 20, 9))
    fe.submit(first, max_new_tokens=3)
    fe.run()
    assert trace.events()[0] == [] and not trace.live()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert trace.live() and not trace.enabled()
        fe.submit(second, max_new_tokens=4)
        fe.run()
    finally:
        jax.profiler.stop_trace()
    assert not trace.live()
    in_ring = {e[0] for e in trace.events()[0]}
    assert {"serve/frontend_step", "serve/step", "serve/admit",
            "serve/dispatch", "serve/harvest", "serve/device_wait",
            "serve/queue", "serve/request"} <= in_ring
    n = len(trace.events()[0])
    fe.submit(third, max_new_tokens=3)
    fe.run()
    assert len(trace.events()[0]) == n          # off again

    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host, = [p for p in data.planes if p.name == "/host:CPU"]
    on_host = {ev.name for line in host.lines for ev in line.events
               if ev.name.startswith("serve/")}
    # live spans only: complete() intervals go to the ring alone
    assert on_host == {"serve/frontend_step", "serve/feed", "serve/step",
                       "serve/admit", "serve/dispatch", "serve/harvest",
                       "serve/device_wait"}


def test_serving_step_nesting_and_pool_attributes(model):
    eng = _engine(model, "paged")
    fe = FrontEnd(eng)
    trace.enable()
    reqs = [fe.submit(p, max_new_tokens=5)
            for p in _prompts((5, 140, 9, 30, 17))]
    held = []       # after each FrontEnd.step: (live slots, their tokens)
    while fe.busy:
        fe.step()
        live = [r for r in eng._slot_req if r is not None]
        held.append((len(live),
                     sum(len(r.prompt) + len(r.tokens) for r in live)))
    trace.disable()
    assert all(r.status == "done" for r in reqs)
    events = trace.events()[0]
    by_id, parent = _tree(events)
    named = lambda name: [e for e in events if e[0] == name]

    assert len(named("serve/frontend_step")) == len(held)
    assert {parent[e[4]] for e in named("serve/step")} \
        == {"serve/frontend_step"}
    admits = named("serve/admit")
    assert len(admits) == len(reqs)
    assert {parent[e[4]] for e in admits} == {"serve/step"}
    assert sorted(e[6]["prompt"] for e in admits) == [5, 9, 17, 30, 140]
    assert {e[6]["bucket"] for e in admits} == {16, 32, 256}
    assert all(e[6]["cached"] == 0 and e[6]["rid"] for e in admits)
    prefills = [e for e in named("serve/dispatch")
                if e[6]["kind"] == "prefill"]
    assert len(prefills) == len(reqs)
    assert {parent[e[4]] for e in prefills} == {"serve/admit"}
    # an admission is ONE device program: its span says so, and holds
    # exactly one dispatch, the prefill
    assert all(e[6]["programs"] == 1 for e in admits)
    assert sorted(e[5] for e in named("serve/dispatch")
                  if parent[e[4]] == "serve/admit") \
        == sorted(e[4] for e in admits) == sorted(e[5] for e in prefills)
    decodes = [e for e in named("serve/dispatch") if e[6]["kind"] == "paged"]
    assert decodes and {parent[e[4]] for e in decodes} == {"serve/step"}
    waits = named("serve/device_wait")
    assert len(waits) == len(named("serve/harvest"))
    assert {parent[e[4]] for e in waits} == {"serve/harvest"}
    for e in waits:            # the wait lies inside its harvest
        h = by_id[e[5]]
        assert h[1] <= e[1] and e[1] + e[2] <= h[1] + h[2]
        assert e[6]["kind"] == h[6]["kind"]
    # a retirement feeds the engine from inside the harvest's replay
    assert {parent[e[4]] for e in named("serve/feed")} \
        == {"serve/frontend_step", "serve/harvest"}
    assert sum(e[6]["admitted"] for e in named("serve/feed")) == len(reqs)
    assert named("serve/frontend_step")[0][6]["queued"] == len(reqs)

    steps = sorted(named("serve/step"), key=lambda e: e[1])
    assert len(steps) == len(held)
    for e, (n_live, tokens) in zip(steps, held):
        a = e[6]
        assert a["pages"] == 8 and 0 <= a["pages_used"] <= a["pages"]
        assert a["active"] == n_live and a["waiting"] >= 0
        # the cache holds every token but the newest of each live slot
        assert tokens - n_live <= a["live_tokens"] <= tokens
        assert a["pages_used"] >= -(-a["live_tokens"] // 128)
        # the pages the live tokens lie on, a slot at a time: at least
        # what the tokens would fill packed, at most one part-full page
        # a live slot more, and never more than the pool has in use
        assert -(-a["live_tokens"] // 128) <= a["live_pages"] \
            <= a["live_tokens"] // 128 + n_live
        assert a["live_pages"] <= a["pages_used"]
    assert any(a[6]["pages_used"] >= 3 for a in steps)   # 140 + 2 slots
    # all retired: what is left is the 140-token prompt's full page, kept
    # warm by the prefix cache
    assert steps[-1][6]["live_tokens"] == 0
    assert steps[-1][6]["live_pages"] == 0
    assert max(a[6]["live_pages"] for a in steps) >= 3
    assert steps[-1][6]["pages_used"] == eng.P - eng.free_pages <= 1


@pytest.mark.parametrize("kind", ["paged", "contiguous"])
def test_device_wait_splits_harvest_on_both_engines(model, kind):
    eng = _engine(model, kind)
    fe = FrontEnd(eng)
    trace.enable()
    for p in _prompts((6, 11, 4), seed=1):
        fe.submit(p, max_new_tokens=4)
    fe.run()
    trace.disable()
    events = trace.events()[0]
    _, parent = _tree(events)
    waits = [e for e in events if e[0] == "serve/device_wait"]
    harvests = [e for e in events if e[0] == "serve/harvest"]
    assert waits and len(waits) == len(harvests)
    assert {parent[e[4]] for e in waits} == {"serve/harvest"}
    assert sum(e[2] for e in waits) < sum(e[2] for e in harvests)
    steps = [e for e in events if e[0] == "serve/step"]
    assert steps and all(
        {"active", "tokens", "waiting"} <= set(e[6]) for e in steps)
    assert {parent[e[4]] for e in steps} == {"serve/frontend_step"}
