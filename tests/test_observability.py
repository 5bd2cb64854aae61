"""Unified tracing + metrics pipeline (ISSUE 3): span nesting and ring
overflow, Chrome-trace/Perfetto schema, histogram percentile math vs
numpy, registry dedup, cross-rank export/merge, the statsz endpoint,
and the trace-merge CLI."""

import json
import os
import subprocess
import sys
import threading
import urllib.request

import numpy as np
import pytest

from paddle_tpu import stats
from paddle_tpu.observability import (span, complete, trace,
                                      merge_trace_files,
                                      merge_rank_traces, start_statsz,
                                      stop_statsz)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    trace.clear()
    yield
    trace.disable()
    trace.clear()
    stats.reset()


def _export_events(tmp_path, name="t.json"):
    path = trace.export(str(tmp_path / name))
    with open(path) as f:
        doc = json.load(f)
    return doc, [e for e in doc["traceEvents"] if e.get("ph") == "X"]


# -- spans -------------------------------------------------------------------

def test_span_nesting_parent_ids(tmp_path):
    trace.enable(str(tmp_path))
    with span("outer", kind="test"):
        with span("mid") as sp:
            sp.attrs["bytes"] = 42
            with span("leaf"):
                pass
        with span("mid2"):
            pass
    doc, evs = _export_events(tmp_path)
    by_name = {e["name"]: e for e in evs}
    assert set(by_name) == {"outer", "mid", "mid2", "leaf"}
    outer = by_name["outer"]["args"]["span_id"]
    assert by_name["mid"]["args"]["parent_id"] == outer
    assert by_name["mid2"]["args"]["parent_id"] == outer
    assert by_name["leaf"]["args"]["parent_id"] == \
        by_name["mid"]["args"]["span_id"]
    assert by_name["outer"]["args"]["parent_id"] == 0
    assert by_name["mid"]["args"]["bytes"] == 42
    # children nest inside the parent's interval (1us slack: exported
    # timestamps are wall-rebased floats with ~sub-us rounding)
    for child in ("mid", "leaf"):
        assert by_name[child]["ts"] >= by_name["outer"]["ts"] - 1
        assert (by_name[child]["ts"] + by_name[child]["dur"]
                <= by_name["outer"]["ts"] + by_name["outer"]["dur"] + 1)


def test_span_disabled_noop(tmp_path):
    calls = []

    def fn(v):
        with span("wrapped/fn", tag=1) as sp:
            sp.attrs["v"] = v           # an off span takes and drops it
            calls.append(v)
            return v * 2

    assert fn(3) == 6          # disabled: still runs, records nothing
    assert trace.events()[0] == []
    trace.enable(str(tmp_path))
    assert fn(4) == 8
    evs, dropped = trace.events()
    assert [e[0] for e in evs] == ["wrapped/fn"] and dropped == 0
    assert evs[0][6] == {"tag": 1, "v": 4}
    assert calls == [3, 4]


def test_off_span_allocates_nothing():
    """With neither the ring nor a profiler session on, every ``span()``
    is the one shared no-op object: nothing is allocated per call."""
    assert not trace.live()
    a, b = span("serve/step"), span("p2p/send", dst=3)
    assert a is b and not a.live
    with a as sp:
        assert sp is a
        sp.attrs["tokens"] = 7
    assert trace.events()[0] == []
    trace.enable()
    assert trace.live()
    c = span("serve/step")
    assert c is not a and c.live and c is not span("serve/step")


def test_async_begin_end_and_complete(tmp_path):
    """``complete`` records an interval after the fact, from any thread
    (``begin``/``end`` tokens are gone: nothing called them)."""
    trace.enable(str(tmp_path))
    import time
    t0 = time.perf_counter() - 0.25
    done = threading.Event()

    def other_thread():
        complete("late/interval", t0, tokens=3)
        done.set()

    threading.Thread(target=other_thread).start()
    assert done.wait(5)
    complete("fixed/interval", 1.0, 1.5, job=7)
    doc, evs = _export_events(tmp_path)
    by_name = {e["name"]: e for e in evs}
    assert by_name["late/interval"]["dur"] >= 0.2e6  # ~250ms in us
    assert by_name["late/interval"]["args"]["tokens"] == 3
    assert by_name["late/interval"]["args"]["parent_id"] == 0
    assert by_name["fixed/interval"]["dur"] == pytest.approx(0.5e6)
    assert by_name["fixed/interval"]["args"]["job"] == 7
    trace.disable()
    complete("off/interval", t0)
    assert "off/interval" not in [e[0] for e in trace.events()[0]]


def test_ring_buffer_overflow_keeps_newest(tmp_path):
    trace.enable(str(tmp_path), capacity=8)
    for i in range(20):
        with span(f"s{i}"):
            pass
    evs, dropped = trace.events()
    assert len(evs) == 8 and dropped == 12
    assert [e[0] for e in evs] == [f"s{i}" for i in range(12, 20)]
    doc, x = _export_events(tmp_path)
    assert doc["otherData"]["dropped"] == 12
    assert len(x) == 8


def test_perfetto_schema(tmp_path):
    trace.enable(str(tmp_path))
    with span("a", x=1):
        pass
    doc, evs = _export_events(tmp_path)
    assert isinstance(doc["traceEvents"], list)
    metas = [e for e in doc["traceEvents"] if e.get("ph") == "M"]
    assert any(m["name"] == "process_name" for m in metas)
    for e in evs:
        for key in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert key in e
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["ph"] == "X"
    # round-trips through json (Perfetto's minimum bar)
    json.dumps(doc)


def test_trace_file_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("PT_TRACE_DIR", str(tmp_path))
    monkeypatch.setenv("PT_PROCESS_ID", "3")
    assert trace.trace_file_from_env() == \
        str(tmp_path / "trace_rank3.json")
    monkeypatch.setenv("PT_TRACE_FILE", str(tmp_path / "me.json"))
    assert trace.trace_file_from_env() == str(tmp_path / "me.json")


# -- histograms ---------------------------------------------------------------

def test_histogram_percentiles_against_numpy():
    rs = np.random.RandomState(7)
    vals = rs.lognormal(mean=-5.0, sigma=1.5, size=4000)
    r = stats.StatRegistry()
    for v in vals:
        r.observe("lat_s", float(v))
    snap = r.snapshot()
    assert snap["lat_s.count"] == 4000
    assert snap["lat_s.sum"] == pytest.approx(vals.sum(), rel=1e-9)
    assert snap["lat_s.max"] == pytest.approx(vals.max())
    # log-bucketed with growth 2^(1/4): quantile estimates are within
    # half a bucket (~9%) of the exact value
    for q in (50, 90, 99):
        exact = np.percentile(vals, q)
        est = snap[f"lat_s.p{q}"]
        assert abs(est - exact) / exact < 0.12, (q, est, exact)
    assert "lat_s.p99" in r.table("lat_s.")


def test_histogram_edge_cases():
    r = stats.StatRegistry()
    r.observe("h", 0.0)          # underflow bucket
    r.observe("h", -1.0)         # negative → underflow, min tracked
    r.observe("h", 5.0)
    snap = r.snapshot("h.")
    assert snap["h.count"] == 3
    assert snap["h.max"] == 5.0
    assert snap["h.p99"] <= 5.0
    # single-sample histogram: every percentile is that sample
    r2 = stats.StatRegistry()
    r2.observe("one", 0.25)
    s2 = r2.snapshot()
    assert s2["one.p50"] == pytest.approx(0.25, rel=0.1)
    assert s2["one.p99"] == pytest.approx(0.25, rel=0.1)


# -- reset prefix fix ---------------------------------------------------------

def test_reset_prefix_matches_timer_and_histogram_derived_names():
    r = stats.StatRegistry()
    with r.timer("p2p/send"):
        pass
    r.observe("serve/ttft_s", 0.1)
    r.add("p2p/send_msgs")
    assert "p2p/send.total_s" in r.snapshot()
    r.reset("p2p/send.")             # derived-name prefix: clears timer
    snap = r.snapshot()
    assert "p2p/send.total_s" not in snap
    assert snap["p2p/send_msgs"] == 1   # counter prefix-distinct, kept
    r.reset("serve/ttft_s.p9")       # derived histogram name
    assert "serve/ttft_s.p50" not in r.snapshot()


# -- registry dedup -----------------------------------------------------------

def test_profiler_registry_is_stats_registry():
    from paddle_tpu import profiler
    from paddle_tpu.profiler import statistic
    assert profiler.stat_registry is stats.default_registry()
    assert statistic.StatRegistry is stats.StatRegistry
    profiler.stat_add("dedup/x", 2)
    assert stats.get("dedup/x") == 2
    assert stats.snapshot()["dedup/x"] == 2
    stats.add("dedup/x", 1)
    assert profiler.stat_get("dedup/x") == 3


# -- export / merge -----------------------------------------------------------

def test_export_merge_sums_counters_and_merges_histograms():
    a = stats.StatRegistry()
    b = stats.StatRegistry()
    for reg, scale in ((a, 1.0), (b, 2.0)):
        reg.add("steps", 5)
        reg.set_value("mfu", 0.3 * scale)
        with reg.timer("io"):
            pass
        for i in range(100):
            reg.observe("lat_s", scale * (i + 1) / 100.0)
    merged = stats.merge([a.export(rank=0), b.export(rank=1)])
    snap = merged.snapshot()
    assert snap["steps"] == 10
    assert snap["lat_s.count"] == 200
    assert snap["io.count"] == 2
    # gauges are rank-namespaced, not clobbered
    assert snap["rank0/mfu"] == pytest.approx(0.3)
    assert snap["rank1/mfu"] == pytest.approx(0.6)
    assert "mfu" not in snap
    # merged p50 sits between the two ranks' medians
    assert 0.5 < snap["lat_s.p50"] < 1.1
    # round-trips through json (statsz / sidecar files)
    stats.merge([json.loads(json.dumps(a.export(rank=0)))])


def test_snapshot_tag_rank(monkeypatch):
    monkeypatch.setenv("PT_PROCESS_ID", "2")
    r = stats.StatRegistry()
    r.add("c", 1)
    assert r.snapshot(tag_rank=True) == {"rank2/c": 1}


# -- statsz -------------------------------------------------------------------

def test_statsz_server_serves_live_snapshot():
    stats.add("statsz/hits", 3)
    stats.observe("statsz/lat_s", 0.5)
    srv = start_statsz(0)
    try:
        base = f"http://127.0.0.1:{srv.port}"
        with urllib.request.urlopen(base + "/statsz", timeout=5) as r:
            doc = json.load(r)
        assert doc["counters"]["statsz/hits"] == 3
        assert doc["histograms"]["statsz/lat_s"]["count"] == 1
        assert "rank" in doc
        with urllib.request.urlopen(base + "/statsz?flat=1",
                                    timeout=5) as r:
            flat = json.load(r)
        assert flat["statsz/hits"] == 3 and "statsz/lat_s.p50" in flat
        with urllib.request.urlopen(base + "/", timeout=5) as r:
            text = r.read().decode()
        assert "statsz/hits" in text
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=5)
    finally:
        stop_statsz()


def test_metricsz_prometheus_exposition_valid():
    """ISSUE 15 satellite: /metricsz serves Prometheus text exposition
    (0.0.4) of the live registry — every line a TYPE comment or a
    ``name[{labels}] value`` sample, counters suffixed _total,
    histograms as summaries with quantile samples."""
    import re
    from paddle_tpu.observability import StatszServer
    stats.add("promz/hits", 2)
    stats.set_value("promz/depth", 1.5)
    for v in (0.1, 0.2, 0.4):
        stats.observe("promz/lat_s", v)
    with stats.default_registry().timer("promz/phase"):
        pass
    srv = StatszServer(0, host="127.0.0.1")
    try:
        url = f"http://127.0.0.1:{srv.port}/metricsz"
        with urllib.request.urlopen(url, timeout=5) as r:
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
    finally:
        srv.stop()
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"\})?'
        r' (NaN|[+-]Inf|-?[0-9][0-9.e+-]*)$')
    meta = re.compile(r'^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* '
                      r'(counter|gauge|summary|histogram)$')
    lines = body.strip().splitlines()
    assert lines, "empty exposition"
    for ln in lines:
        assert sample.match(ln) or meta.match(ln), f"invalid line: {ln}"
    assert "# TYPE pt_promz_hits_total counter" in lines
    assert "pt_promz_hits_total 2.0" in lines
    assert "# TYPE pt_promz_depth gauge" in lines
    assert 'pt_promz_lat_s{quantile="0.5"}' in body
    assert "pt_promz_lat_s_count 3.0" in lines
    assert "pt_promz_phase_seconds_count 1.0" in lines
    # a declared TYPE precedes every sample of its metric
    typed = {ln.split()[2] for ln in lines if ln.startswith("# TYPE")}
    for ln in lines:
        if not ln.startswith("#"):
            name = ln.split("{")[0].split(" ")[0]
            base_ = re.sub(r"_(total|sum|count)$", "", name)
            assert name in typed or base_ in typed, ln


# -- trace merging ------------------------------------------------------------

def _fake_rank_trace(tmp_path, rank, names):
    evs = [{"name": "process_name", "ph": "M", "pid": rank, "tid": 0,
            "args": {"name": f"rank{rank}"}}]
    evs += [{"name": n, "ph": "X", "ts": 1.0 * i, "dur": 0.5,
             "pid": rank, "tid": 1, "args": {}}
            for i, n in enumerate(names)]
    p = tmp_path / f"trace_rank{rank}.json"
    p.write_text(json.dumps({"traceEvents": evs}))
    return str(p)


def test_merge_rank_traces_distinct_lanes(tmp_path):
    _fake_rank_trace(tmp_path, 0, ["a", "b"])
    _fake_rank_trace(tmp_path, 1, ["c"])
    out = merge_rank_traces(str(tmp_path))
    assert out.endswith("trace_merged.json")
    with open(out) as f:
        doc = json.load(f)
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in x} == {0, 1}
    metas = [e for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert len(metas) == 2
    (tmp_path / "sub").mkdir()
    assert merge_rank_traces(str(tmp_path / "sub")) is None


def test_trace_merge_cli(tmp_path):
    a = _fake_rank_trace(tmp_path, 0, ["x"])
    b = _fake_rank_trace(tmp_path, 1, ["y"])
    out = tmp_path / "merged.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
         "-o", str(out), a, b],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    doc = json.loads(out.read_text())
    assert {e["pid"] for e in doc["traceEvents"]
            if e["ph"] == "X"} == {0, 1}
    # dir mode
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "trace_merge.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert os.path.exists(tmp_path / "trace_merged.json")


def test_multiprocess_trace_merge_via_spawn(tmp_path):
    """Two spawned workers (the _mh_worker harness: PT_* env contract,
    CPU pinned at module import) each export a rank trace + a stats
    sidecar; the parent merges the traces into one timeline with
    distinct rank lanes and folds the stats exports into one view."""
    import _mh_worker
    import paddle_tpu.distributed as dist

    dist.spawn(_mh_worker.obs_worker, args=(str(tmp_path),), nprocs=2,
               join=True)
    out = merge_rank_traces(str(tmp_path))
    assert out is not None
    with open(out) as f:
        doc = json.load(f)
    x = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in x} == {0, 1}
    names = {e["name"] for e in x}
    assert {"mh/work", "mh/inner"} <= names
    # nested span survives per rank
    for rank in (0, 1):
        lane = {e["name"]: e for e in x if e["pid"] == rank}
        assert lane["mh/inner"]["args"]["parent_id"] == \
            lane["mh/work"]["args"]["span_id"]
    # launch-side stats aggregation from the worker sidecars
    exports = []
    for rank in (0, 1):
        with open(tmp_path / f"stats_{rank}.json") as f:
            exports.append(json.load(f))
    merged = stats.merge(exports)
    assert merged.snapshot()["mh/latency_s.count"] == 2
