"""Fleet-observability smoke (tools/ci.sh fleetobs, ISSUE 13): one
prefill + one decode replica — REAL processes through the
distributed/launch.py CLI — behind the role-aware router, with the
whole telemetry plane switched on (~1 min):

- every request's spans carry ONE trace context across router,
  prefill, wire, and decode; the stitched timeline
  (observability/merge.stitch_trace_files) shows all four on-device
  segments (queue-wait, prefill, kv-transfer, decode) for at least one
  request, and their durations SUM to the client-observed latency
  (the serve/route span) within 10%;
- the fleet /statsz serves the MERGED registry: its serve/ttft_s p99
  equals the FleetStats-merged histogram's p99;
- one injected stall (SIGSTOP the decode replica mid-request) raises
  EXACTLY one fleet/alert_stalled_replica naming the replica;
- the JSONL telemetry file grew.

Exit 0 + "FLEETOBS SMOKE OK" on success; any divergence asserts.
"""
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

# a multi-process CPU fleet smoke: one host is one process driving its
# chips, so neither this parent nor its workers may reach for one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PT_KV_WIRE"] = "fp32"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np  # noqa: E402

from paddle_tpu import stats  # noqa: E402
from paddle_tpu.observability import merge, trace  # noqa: E402
from paddle_tpu.serving import Router  # noqa: E402

WORKER = os.path.join(REPO, "tests", "_disagg_worker.py")


def _spawn(store_port, rid, role, launch_port, trace_file):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               FLEETOBS_TRACE_FILE=trace_file, PT_TRACE_FLUSH_S="0.5")
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{launch_port}",
         WORKER, str(store_port), rid, role],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def main():
    tdir = tempfile.mkdtemp(prefix="fleetobs_")
    trace.enable(os.path.join(tdir, "trace_router.json"))
    rs = np.random.RandomState(7)
    prompts = [[int(x) for x in rs.randint(0, 96, size=n)]
               for n in (40, 150, 90, 200, 60, 120)]
    budgets = [12, 16, 14, 12, 18, 14]

    router = Router(port=0, dead_after=20.0)
    procs = [_spawn(router.store.port, "pf0", "prefill", 8885,
                    os.path.join(tdir, "trace_pf0.json")),
             _spawn(router.store.port, "dc0", "decode", 8886,
                    os.path.join(tdir, "trace_dc0.json"))]
    try:
        router.wait_replicas(2, timeout=90)

        # -- phase A: the stitched-timeline workload --------------------
        t_client = {}
        ids = []
        for p, b in zip(prompts, budgets):
            q = router.submit(p, max_new_tokens=b)
            t_client[q] = time.perf_counter()
            ids.append(q)
        results = router.drain(timeout=180)
        for q in ids:
            t_client[q] = time.perf_counter() - t_client[q]
        assert all(results[q]["status"] == "done" for q in ids), results
        assert stats.get("serve/router_prefill_handoffs") > 0, \
            "no prefill->decode handoffs: the workload never crossed " \
            "the wire"
        print(f"  phase A: {len(ids)} requests served "
              f"prefill->wire->decode", flush=True)

        # -- fleet stats: merged /statsz + telemetry --------------------
        jsonl = os.path.join(tdir, "fleet.jsonl")
        fleet = router.enable_fleet_stats(
            refresh_s=0.25, stall_after_s=2.0, jsonl_path=jsonl)
        srv = fleet.serve_statsz(0, host="127.0.0.1")
        fleet.poll()
        merged = fleet.merged()
        hist = merged.histogram("serve/ttft_s")
        assert hist is not None and hist.count > 0, \
            "no decode-side TTFT samples reached the fleet merge"
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/statsz", timeout=5) as r:
            served = json.load(r)
        shist = served["histograms"].get("serve/ttft_s")
        assert shist is not None and shist["count"] == hist.count, \
            "fleet /statsz did not serve the merged TTFT histogram"
        from paddle_tpu.stats import _Histogram
        p99_srv = _Histogram.from_dict(shist).percentile(99)
        assert abs(p99_srv - hist.percentile(99)) < 1e-12
        # role-tagging: the prefill replica's samples live in their own
        # histogram, never in the fleet TTFT
        assert merged.histogram("serve/prefill_s") is not None, \
            "prefill replica exported no serve/prefill_s"
        print(f"  fleet /statsz: merged p99 TTFT "
              f"{p99_srv * 1e3:.1f}ms over {hist.count} samples",
              flush=True)

        # -- injected stall: SIGSTOP the decode replica mid-request -----
        victim_pid = router.directory.members()["dc0"]["pid"]
        tok0 = (router.directory.load("dc0") or {}).get("tokens", 0)
        rq = router.submit(prompts[1], max_new_tokens=64)
        # wait until the decode replica is busy AND has made token
        # progress on THIS request (a zero-progress busy stretch from
        # some unrelated hiccup must not pre-consume the alert edge)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            router.poll()
            load = router.directory.load("dc0") or {}
            if (load.get("busy_slots", 0) > 0
                    and load.get("tokens", 0) > tok0):
                break
            time.sleep(0.05)
        load = router.directory.load("dc0") or {}
        assert load.get("busy_slots", 0) > 0, \
            "decode replica never went busy"
        os.kill(victim_pid, signal.SIGSTOP)
        try:
            fired = []
            deadline = time.monotonic() + 12
            while time.monotonic() < deadline and not fired:
                fired = [a for a in fleet.poll()
                         if a == "stalled_replica"]
                time.sleep(0.2)
        finally:
            os.kill(victim_pid, signal.SIGCONT)
        assert fired, "anomaly watch never flagged the SIGSTOP'd " \
            "replica within the window"
        n_alerts = int(stats.get("fleet/alert_stalled_replica"))
        assert n_alerts == 1, \
            f"expected exactly one stall alert, got {n_alerts}"
        named = [a["msg"] for a in fleet.alerts
                 if a["kind"] == "stalled_replica"]
        assert named and "dc0" in named[0], named
        print(f"  stall: one alert, names the replica ({named[0][:60]}"
              f"...)", flush=True)
        results = router.drain(timeout=180)
        assert results[rq]["status"] == "done", results[rq]
        assert os.path.exists(jsonl) and os.path.getsize(jsonl) > 0, \
            "fleet JSONL telemetry never appended"
    finally:
        router.shutdown()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
        router.close()

    # -- stitch: one timeline, four segments, 10% latency sum ----------
    trace.export()
    trace.disable()
    paths = [os.path.join(tdir, f"trace_{n}.json")
             for n in ("router", "pf0", "dc0")]
    for p in paths:
        assert os.path.exists(p), f"missing trace file {p}"
    out, summary = merge.stitch_trace_files(
        paths, os.path.join(tdir, "trace_stitched.json"))
    need = ("queue-wait", "prefill", "kv-transfer", "decode")
    full = {rid: info for rid, info in summary.items()
            if all(s in info["segments"] for s in need)
            and info["client_us"]}
    assert full, f"no request stitched with all four segments: " \
        f"{ {r: sorted(i['segments']) for r, i in summary.items()} }"
    ok_sum = []
    for rid, info in full.items():
        seg_sum = sum(dur for name, (_, dur) in info["segments"].items()
                      if name in need)
        rel = abs(seg_sum - info["client_us"]) / info["client_us"]
        # the residual is the stream segment (decode end -> router
        # pickup) plus clock-rebase error
        if rel <= 0.10:
            ok_sum.append((rid, seg_sum, info["client_us"], rel))
    assert ok_sum, \
        "no stitched request's segment sum landed within 10% of its " \
        "client-observed latency: " + str(
            {r: (sum(d for n, (_, d) in i["segments"].items()
                     if n in need), i["client_us"])
             for r, i in full.items()})
    rid, seg_sum, client, rel = ok_sum[0]
    # cross-process: the stitched request's spans span >= 3 lanes
    assert len(full[rid]["pids"]) >= 3, full[rid]
    print(f"  stitch: {len(full)}/{len(summary)} requests carry all "
          f"four segments; {rid} sums {seg_sum / 1e3:.1f}ms vs client "
          f"{client / 1e3:.1f}ms ({100 * rel:.1f}% off) across "
          f"{len(full[rid]['pids'])} process lanes -> {out}",
          flush=True)
    print("FLEETOBS SMOKE OK", flush=True)


if __name__ == "__main__":
    main()
