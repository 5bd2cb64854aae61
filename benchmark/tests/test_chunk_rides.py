"""``chunk_rides.serve``: the share of a stretch's prompt chunks whose
program carried decoding rows, read from the program's own spans."""

import time

import pytest

from benchmark import harness

NAME = "chunk_rides.serve"
CELLS = ["brumby-14b.serve-longgen16", "joyai-llm-flash.serve-doc32"]


def _read(ctx):
    return harness.load_module("layer_metrics", NAME).read(ctx)


@pytest.fixture(autouse=True)
def _empty_ring():
    from paddle_tpu.observability import trace
    trace.disable()
    trace.clear(capacity=1 << 12)
    yield
    trace.clear()


def _stretch(chunks):
    """Chunk spans with ``chunks``' attributes inside a traced stretch,
    and one before it; returns the reader's context."""
    from paddle_tpu.observability import trace
    trace.enable()
    with trace.span("serve/prefill_chunk", tokens=512, index=0, last=False,
                    rode=9):
        pass
    ta = time.perf_counter()
    for attrs in chunks:
        with trace.span("serve/step"):
            with trace.span("serve/prefill_chunk", **attrs):
                pass
    tb = time.perf_counter()
    trace.disable()
    return {"counters": {"traced": (ta, tb)}, "notes": []}


def test_share_of_chunks_that_carried_decoding_rows():
    ctx = _stretch([dict(tokens=512, index=i, last=False, rode=r)
                    for i, r in enumerate((3, 0, 31, 1))])
    assert _read(ctx) == pytest.approx(75.0)
    assert ctx["notes"] == [
        "chunk_rides.serve: 3 of 4 chunks carried decoding rows, 11.7 a "
        "chunk"]


def test_reads_nothing_from_a_program_without_the_attribute():
    """No traced stretch; no chunk in it; chunks whose span has no
    ``rode`` (what the parent commit records): ``None``, no raise."""
    assert _read({"counters": {"traced": None}, "notes": []}) is None
    assert _read(_stretch([])) is None
    ctx = _stretch([dict(tokens=512, index=0, last=True)])
    assert _read(ctx) is None and ctx["notes"] == []


def test_listed_for_the_chunked_cells():
    bench = harness.load_cell(CELLS[0])["bench"]
    metric, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert metric == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "workloads": CELLS}
    assert bench["per_layer"][-1] is metric
