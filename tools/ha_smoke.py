"""Control-plane HA smoke (tools/ci.sh ha, ISSUE 17): SIGKILL the
router process mid-traffic — REAL processes end to end — and prove
the failover contract in about a minute on CPU:

- a successor router generation (same request journal, same endpoint
  file) recovers the intake via journal replay and re-places every
  outstanding request (``recovered`` > 0 enforced by construction:
  the kill lands while the journal holds submits without results);
- the replicas reconnect through the endpoint file, re-announce, and
  republish retained results to the new generation's store;
- ZERO request-id loss: the successor's result set is exactly the
  full workload, every stream ``done`` — and byte-identical to an
  undisturbed control fleet (greedy decode, same weights), run first.

Exit 0 + "HA SMOKE OK" on success; any divergence asserts. The
fuller (slower) acceptance matrix — SIGSTOP partitions, disagg
store-chaos — lives in tests/test_router_failover.py (-m slow).
"""
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time

# a multi-process CPU fleet smoke: one host is one process driving its
# chips, so neither this parent nor its workers may reach for one
os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

from paddle_tpu.serving.router import read_endpoint_file  # noqa: E402

ROUTER_WORKER = os.path.join(REPO, "tests", "_router_worker.py")
SERVE_WORKER = os.path.join(REPO, "tests", "_serve_worker.py")

WORKLOAD = 10
SEED = 3


def _free_port():
    """An unused launch-master port (fixed ladders collide with
    orphans from earlier failed runs)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn_router(ep, journal, res, extra=()):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, ROUTER_WORKER, "--endpoint-file", ep,
         "--journal", journal, "--results", res,
         "--workload", str(WORKLOAD), "--seed", str(SEED), *extra],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        start_new_session=True)


def _spawn_replica(store_port, rid, ep):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               PT_ROUTER_ENDPOINT_FILE=ep)
    # own process group so cleanup can reach the serve-worker
    # grandchildren, not just the launch parent
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{_free_port()}",
         SERVE_WORKER, str(store_port), rid],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
        start_new_session=True)


def _wait_file(path, timeout, what):
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        assert time.monotonic() < deadline, \
            f"{what} {path} absent after {timeout}s"
        time.sleep(0.05)


def _journal_counts(path):
    s = r = 0
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                if '"kind": "submit"' in line:
                    s += 1
                elif '"kind": "result"' in line:
                    r += 1
    except OSError:
        pass
    return s, r


def _kill_group(p):
    try:
        os.killpg(p.pid, signal.SIGKILL)
    except (OSError, ProcessLookupError):
        try:
            p.kill()
        except OSError:
            pass


def _reap(procs, timeout=40):
    for p in procs:
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            _kill_group(p)
            p.wait(timeout=10)


def _run(tag, tmp, kill_mid_traffic):
    ep = os.path.join(tmp, f"{tag}.ep")
    journal = os.path.join(tmp, f"{tag}.jsonl")
    res = os.path.join(tmp, f"{tag}.results.json")
    gen1 = _spawn_router(ep, journal, res,
                         extra=["--interval-ms", "30"])
    procs, gen2 = [], None
    try:
        _wait_file(ep, 60, "endpoint file")
        port = read_endpoint_file(ep)["port"]
        procs = [_spawn_replica(port, f"{tag}-r0", ep),
                 _spawn_replica(port, f"{tag}-r1", ep)]
        if kill_mid_traffic:
            deadline = time.monotonic() + 90
            while True:
                s, r = _journal_counts(journal)
                if s >= WORKLOAD // 2 and s > r:
                    break
                assert time.monotonic() < deadline, \
                    "router never reached mid-traffic"
                assert gen1.poll() is None, "router died on its own"
                time.sleep(0.02)
            os.kill(gen1.pid, signal.SIGKILL)
            gen1.wait(timeout=10)
            print(f"  killed gen-1 router at "
                  f"{_journal_counts(journal)[0]}/{WORKLOAD} submits",
                  flush=True)
            gen2 = _spawn_router(ep, journal, res)
        _wait_file(res, 180, "results file")
        with open(res, encoding="utf-8") as f:
            out = json.load(f)
        _reap(([gen2] if gen2 else [gen1]) + procs)
        return out
    except BaseException:
        for p in [gen1, *procs] + ([gen2] if gen2 else []):
            if p.poll() is None:
                _kill_group(p)
        raise


def main():
    t0 = time.monotonic()
    all_ids = {f"rq-{i:06d}" for i in range(1, WORKLOAD + 1)}
    with tempfile.TemporaryDirectory(prefix="pt-ha-smoke-") as tmp:
        control = _run("ctrl", tmp, kill_mid_traffic=False)
        assert set(control["results"]) == all_ids
        print(f"  control: {WORKLOAD} streams, one generation",
              flush=True)
        out = _run("ha", tmp, kill_mid_traffic=True)
        assert out["generation"] == 2, out["generation"]
        assert out["recovered"] >= 1, \
            "journal replay recovered nothing"
        assert set(out["results"]) == all_ids, \
            sorted(all_ids - set(out["results"]))
        assert all(v["status"] == "done"
                   for v in out["results"].values())
        diverged = [q for q in sorted(all_ids)
                    if out["results"][q]["tokens"]
                    != control["results"][q]["tokens"]]
        assert not diverged, f"streams diverged: {diverged}"
        print(f"  failover: gen-2 recovered {out['recovered']} "
              f"outstanding, {WORKLOAD}/{WORKLOAD} ids, "
              f"byte-identical", flush=True)
    print(f"HA SMOKE OK ({time.monotonic() - t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
