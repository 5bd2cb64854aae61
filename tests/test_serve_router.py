"""Multi-replica serving router (paddle_tpu/serving/router.py, ISSUE
10): real replica processes spawned through the distributed/launch.py
CLI, TCPStore membership, least-outstanding placement, and —
the acceptance case — killing one replica under fault injection loses
no queued request (request-id accounting proves redistribution)."""

import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from _fleetobs import assert_flushed_trace_stitches
from paddle_tpu import native, stats
from paddle_tpu.serving import Router

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_serve_worker.py")

pytestmark = pytest.mark.skipif(not native.is_available(),
                                reason="native TCPStore unavailable")


def _spawn_replica(store_port: int, rid: str, launch_port: int,
                   extra_env=None):
    """One replica process via the launch CLI (one launch per replica,
    nproc_per_node=1, so a fault-injected kill of one replica cannot
    take its peers' launcher down with it)."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.update(extra_env or {})
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{launch_port}",
         WORKER, str(store_port), rid],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def _cleanup(router, procs):
    router.shutdown()
    for p in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10)
    router.close()


def test_router_round_trip_two_replicas():
    """Requests spread over two real replica processes come back
    complete and correct; placement is least-outstanding (both
    replicas serve some share). ``dead_after`` is generous here: a
    loaded CI host can stall an idle replica's heartbeat for seconds,
    and a false death would legitimately shift all work to one replica
    (that behavior is the NEXT test's job)."""
    router = Router(port=0, dead_after=15.0)   # ephemeral store port
    procs = [_spawn_replica(router.store.port, f"rep{i}", 8875 + i)
             for i in range(2)]
    try:
        router.wait_replicas(2, timeout=90)
        rs = np.random.RandomState(0)
        ids = [router.submit(list(rs.randint(0, 96, size=7)),
                             max_new_tokens=6) for _ in range(8)]
        # an INFEASIBLE request (prompt beyond the replica engines'
        # cache) must come back as a rejected RESULT — an uncaught
        # raise would kill the replica and the router would cascade the
        # poison payload through the whole fleet (regression)
        bad = router.submit([3] * 140, max_new_tokens=16)
        results = router.drain(timeout=120)
        assert sorted(results) == sorted(ids + [bad])
        assert results[bad]["status"] == "rejected-invalid"
        assert "exceed cache length" in results[bad]["error"]
        assert all(results[q]["status"] == "done"
                   and len(results[q]["tokens"]) == 6 for q in ids)
        served_by = {results[q]["replica"] for q in ids}
        assert served_by == {"rep0", "rep1"}, served_by
        assert len(router.replicas()) == 2   # nobody died of it
    finally:
        _cleanup(router, procs)


def test_replica_death_redistributes_queued_work(tmp_path):
    """Acceptance: SIGKILL one replica with requests outstanding —
    every submitted request id still completes (redistributed to the
    survivor), counted on serve/router_redistributed. The victim runs
    TRACED with a fast periodic flush (ISSUE 13): its last flushed
    spans must survive the SIGKILL and still stitch by request id."""
    stats.reset("serve/router")
    victim_trace = str(tmp_path / "trace_rep0.json")
    router = Router(port=0, dead_after=2.5)
    procs = [_spawn_replica(
                 router.store.port, f"rep{i}", 8885 + i,
                 extra_env=({"FLEETOBS_TRACE_FILE": victim_trace,
                             "PT_TRACE_FLUSH_S": "0.2"}
                            if i == 0 else None))
             for i in range(2)]
    try:
        router.wait_replicas(2, timeout=90)
        rs = np.random.RandomState(1)
        victim = "rep0"
        ids = []

        def victim_open():
            return [q for q, r in router._assigned.items()
                    if r == victim and q not in router.results]

        def victim_flushed_a_request():
            try:
                return bool(assert_flushed_trace_stitches(victim_trace,
                                                          ids))
            except (AssertionError, OSError, ValueError):
                return False        # no file, or no span of ours, yet

        # The kill lands on the first sign that the victim admitted a
        # request of this run (its flushed trace stitches one) while it
        # still holds unfinished ones: it is kept fed until then, as
        # bench_fleet_churn defers its kill. A replica with a warm
        # compile cache serves a fixed batch in well under a second,
        # and a kill that loses nothing proves nothing.
        deadline = time.monotonic() + 90
        while not (victim_open() and victim_flushed_a_request()):
            assert time.monotonic() < deadline, \
                "the victim never showed an admitted request"
            while len(victim_open()) < 4:
                ids.append(router.submit(list(rs.randint(0, 96, size=9)),
                                         max_new_tokens=64))
            router.poll()
        victim_reqs = victim_open()
        pid = router.directory.members()[victim]["pid"]
        os.kill(pid, signal.SIGKILL)
        results = router.drain(timeout=120)
        # request-id accounting: nothing lost, first result wins
        assert sorted(results) == sorted(ids)
        assert all(r["status"] == "done"
                   for r in results.values()), results
        assert stats.get("serve/router_redistributed") > 0
        # whatever the victim hadn't finished was re-served by rep1
        # (the counter may exceed it if host load false-positived rep1
        # dead for a moment too — at-least-once makes that harmless)
        redone = [q for q in victim_reqs
                  if results[q]["replica"] == "rep1"]
        assert len(redone) <= stats.get("serve/router_redistributed")
    finally:
        _cleanup(router, procs)
    # the SIGKILLed replica left a complete (atomically flushed) trace
    # whose request-tagged spans still stitch
    assert_flushed_trace_stitches(victim_trace, ids)


def test_least_outstanding_placement_deterministic():
    """Placement policy in isolation (no replica processes): with two
    alive replicas and no completions, submissions alternate; results
    landing rebalance toward the drained replica."""
    from paddle_tpu.serving.router import _publish

    store = native.TCPStore("127.0.0.1", 0, is_master=True)
    try:
        router = Router(store=store)
        router.directory.announce("a", {})
        router.directory.announce("b", {})
        router.directory.alive = lambda rid, dead_after=0: True
        ids = [router.submit([1, 2, 3], max_new_tokens=2)
               for _ in range(4)]
        assert [router._assigned[q] for q in ids] == ["a", "b", "a", "b"]
        # 'a' drains both its requests -> next two land on 'a' first
        for q in ids[::2]:
            _publish(store, "a", q, {"id": q, "tokens": [],
                                     "status": "done", "error": None,
                                     "replica": "a"})
        router.poll()
        more = [router.submit([1, 2, 3], max_new_tokens=2)
                for _ in range(2)]
        assert [router._assigned[q] for q in more] == ["a", "a"]
    finally:
        store.close()


def test_membership_alive_judges_progress():
    """ReplicaDirectory liveness: progress-based, observer-clocked."""
    from paddle_tpu.distributed.membership import ReplicaDirectory
    store = native.TCPStore("127.0.0.1", 0, is_master=True)
    try:
        d_rep = ReplicaDirectory(store)
        d_obs = ReplicaDirectory(store)
        assert d_obs.members() == {}
        assert not d_obs.alive("ghost", dead_after=0.1)
        d_rep.announce("r0", {"slots": 2})
        assert d_obs.members() == {"r0": {"slots": 2}}
        assert d_obs.alive("r0", dead_after=0.2)
        time.sleep(0.05)
        d_rep.heartbeat("r0")
        assert d_obs.alive("r0", dead_after=0.2)   # progressed
        time.sleep(0.3)
        assert not d_obs.alive("r0", dead_after=0.2)  # stalled
        d_rep.heartbeat("r0")
        assert d_obs.alive("r0", dead_after=0.2)   # resurrected
    finally:
        store.close()
