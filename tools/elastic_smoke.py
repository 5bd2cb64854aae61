"""Elastic-fleet smoke (tools/ci.sh elastic, ISSUE 14; ~90s):

Phase 1 — serving autoscale + heal: the FleetController spawns a
2-replica decode fleet (floor=2) through the real launch CLI, Poisson
load flows through the router, and one replica is SIGKILLed
mid-traffic. Asserts: the controller replaces it (fleet converges back
to the floor), EVERY submitted request id completes (zero loss,
at-least-once), the replacement actually serves (goodput recovers),
and the post-load idle stretch triggers one graceful scale-down drain
(replica exits ``drained``, rc 0).

Phase 2 — preemption-tolerant training: a 4-worker static launch under
PT_ELASTIC_RESHAPE=1; two workers die once epoch 1 commits. Asserts:
the launcher reshapes the group 4→2 exporting the new world size, the
trainer re-plans its mesh and restore_resharded-resumes from the
newest VERIFIED epoch (epochs continue, never restart from 0), and
the job finishes all epochs at world 2.

Exit 0 + "ELASTIC SMOKE OK" on success; any divergence asserts.
"""
import json
import os
import signal
import subprocess
import sys
import time

# a multi-process CPU fleet smoke: one host is one process driving its
# chips, so neither this parent nor its workers may reach for one
os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np  # noqa: E402

from paddle_tpu import stats  # noqa: E402
from paddle_tpu.fleet import (FleetController, TierSpec,  # noqa: E402
                              TargetOccupancyPolicy, launch_spawn)
from paddle_tpu.serving import Router, loadgen  # noqa: E402

SERVE_WORKER = os.path.join(REPO, "tests", "_serve_worker.py")
TRAIN_WORKER = os.path.join(REPO, "tests", "_elastic_train_worker.py")


def phase_serving():
    stats.reset("fleet/controller")
    stats.reset("serve/router")
    router = Router(port=0, dead_after=3.0)
    ctl = FleetController(
        router,
        launch_spawn(SERVE_WORKER, router.store.port, pass_role=False),
        tiers=[TierSpec("both", min_replicas=2, max_replicas=3,
                        policy=TargetOccupancyPolicy(
                            down_sustain_s=4.0))],
        cooldown_s=1.0, drain_grace_s=15.0)
    try:
        ctl.step()                       # heal empty fleet up to floor
        rids = router.wait_replicas(2, timeout=120)
        print(f"  phase 1: controller spawned the floor fleet {rids}",
              flush=True)

        rs = np.random.RandomState(11)
        trace = loadgen.poisson_trace(28, qps=3.0, seed=7, vocab=96,
                                      prompt_len=(6, 24),
                                      new_tokens=(6, 16))
        ids, arrivals = [], iter(trace)
        nxt = next(arrivals)
        t0 = time.monotonic()
        victim = rids[0]
        victim_pid = router.directory.members()[victim]["pid"]
        killed = [False]

        def tick():
            nonlocal nxt
            while nxt is not None and \
                    time.monotonic() - t0 >= nxt.t:
                ids.append(router.submit(
                    nxt.prompt, max_new_tokens=nxt.max_new_tokens))
                nxt = next(arrivals, None)
            if not killed[0] and len(ids) >= 8:
                killed[0] = True
                os.kill(victim_pid, signal.SIGKILL)
                print(f"  phase 1: SIGKILLed {victim} "
                      f"(pid {victim_pid}) mid-traffic", flush=True)

        ctl.pump(14.0, interval_s=0.15, extra=tick)
        while nxt is not None:           # drain any un-submitted tail
            ids.append(router.submit(nxt.prompt,
                                     max_new_tokens=nxt.max_new_tokens))
            nxt = next(arrivals, None)
        results = router.drain(timeout=120)

        # zero request-id loss: every submitted id completed
        missing = sorted(set(ids) - set(results))
        assert not missing, f"lost request ids: {missing}"
        assert all(results[q]["status"] == "done" for q in ids), \
            {q: results[q] for q in ids
             if results[q]["status"] != "done"}
        # the controller replaced the victim: >= 3 spawns (2 floor +
        # >= 1 heal) and the fleet is back at the floor
        n_up = int(stats.get("fleet/controller_scale_ups"))
        assert n_up >= 3, f"controller never healed (scale_ups={n_up})"
        alive = router.wait_replicas(2, timeout=60)
        assert victim not in alive, alive
        print(f"  phase 1: {len(ids)} requests, zero loss through the "
              f"kill; fleet converged to {alive}", flush=True)

        # goodput recovery: a post-heal wave is served by the healed
        # fleet, INCLUDING the replacement replica
        wave2 = [router.submit(list(rs.randint(0, 96, size=10)),
                               max_new_tokens=8) for _ in range(10)]
        results = router.drain(timeout=120)
        assert all(results[q]["status"] == "done" for q in wave2)
        served_by = {results[q]["replica"] for q in wave2}
        replacement = [r for r in alive if r not in rids]
        assert replacement and any(r in served_by for r in replacement), \
            f"replacement {replacement} never served: {served_by}"
        print(f"  phase 1: post-heal wave served by {sorted(served_by)}"
              f" (goodput recovered)", flush=True)

        # graceful retirement: drop the ceiling to 1 — the controller
        # drains the emptier replica, which finishes, publishes
        # 'drained', and exits on its own
        ctl.tiers[0].min_replicas = 1
        ctl.tiers[0].max_replicas = 1
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and \
                not stats.get("fleet/controller_drains_completed"):
            ctl.pump(0.5, interval_s=0.25)
        n_drained = int(stats.get("fleet/controller_drains_completed"))
        assert n_drained >= 1, "ceiling drop never drained a replica"
        assert int(stats.get("fleet/controller_kills")) == 0, \
            "graceful drain escalated to SIGKILL"
        print(f"  phase 1: ceiling drop drained {n_drained} replica(s) "
              f"gracefully (no kill)", flush=True)
    finally:
        router.shutdown()
        ctl.shutdown()
        router.close()


def phase_training(workdir):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu",
               PT_ELASTIC_RESHAPE="1", ET_DIE_RANKS="2,3",
               ET_DIE_WORLD="4", ET_DIE_AFTER_EPOCH="1",
               ET_DIE_SIGNAL="kill")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "4", "--max_restarts", "2",
         "--master", f"127.0.0.1:{7941 + os.getpid() % 500}",
         TRAIN_WORKER, workdir, "6"],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, (r.returncode, r.stderr[-3000:])
    assert "reshaping local group 4->2" in r.stderr, r.stderr[-2000:]
    assert "reshaped 4->2 devices" in r.stderr, r.stderr[-2000:]
    log = [json.loads(line) for line in
           open(os.path.join(workdir, "loss_log.jsonl"))]
    v1 = [e for e in log if e["world"] == 4]
    v2 = [e for e in log if e["world"] == 2]
    assert v1 and v2, log
    # resumed from the newest VERIFIED epoch: epochs continue
    assert v2[0]["epoch"] <= v1[-1]["epoch"] + 1, (v1[-1], v2[0])
    assert max(e["epoch"] for e in log) == 5, log
    # the resumed trajectory continues the optimum, not from scratch
    assert v2[0]["loss"] <= log[0]["loss"] + 0.05, (v2[0], log[0])
    print(f"  phase 2: SIGKILL-preempted 4->2 reshape resumed at "
          f"epoch {v2[0]['epoch']} (loss {v2[0]['loss']:.4f}), "
          f"finished all 6 epochs at world 2", flush=True)


def main():
    import tempfile
    t0 = time.perf_counter()
    phase_serving()
    phase_training(tempfile.mkdtemp(prefix="elastic_smoke_"))
    print(f"ELASTIC SMOKE OK ({time.perf_counter() - t0:.0f}s)",
          flush=True)


if __name__ == "__main__":
    main()
