"""Live-reshard + drain-migration smoke (tools/ci.sh reshard,
ISSUE 16; ~2 min):

Phase A — in-HBM training reshape: an ElasticTrainer on 4 virtual CPU
devices requests a cooperative 4->2 reshape mid-run. The in-HBM
redistribute path (PT_RESHARD_INPLACE=1) must produce the SAME loss
trajectory as the checkpoint round trip it replaces
(PT_RESHARD_INPLACE=0 control), observe ``fleet/reshard_inplace_s``,
and take zero fallbacks.

Phase B — drain-with-migration serving: a router + two real replica
processes under Poisson load; one replica is marked draining
mid-decode. Its in-flight requests must MIGRATE to the survivor
(``serve/router_migrated`` > 0), the drain must complete in seconds
(bounded by migration, not the longest request), every request id must
complete, and every token stream must be byte-identical to a no-drain
control fleet run of the same trace.

Exit 0 + "RESHARD SMOKE OK" on success; any divergence asserts.
"""
import os
import subprocess
import sys
import time

# a multi-process CPU fleet smoke: one host is one process driving its
# chips, so neither this parent nor its workers may reach for one
os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from paddle_tpu import stats  # noqa: E402
from paddle_tpu.serving import Router, loadgen  # noqa: E402

WORKER = os.path.join(REPO, "tests", "_serve_worker.py")


def phase_train(workdir):
    import jax.numpy as jnp
    from paddle_tpu import optimizer as optim
    from paddle_tpu.distributed import mesh as mesh_lib
    from paddle_tpu.fleet import ElasticTrainer, plan_topology
    from paddle_tpu.fleet.elastic_train import synthetic_data
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=16, d_model=32,
                        n_layers=2, n_heads=2, dtype=jnp.float32)

    def run(tag):
        mesh_lib.set_topology(None)
        trainer = ElasticTrainer(
            gpt.GPT(cfg, seed=0), optim.SGD(learning_rate=0.05),
            os.path.join(workdir, tag), n_epochs=4,
            mesh=plan_topology(gpt.GPT(cfg, seed=0), n_devices=4),
            data_fn=synthetic_data(cfg.vocab_size, 12,
                                   cfg.max_seq_len))
        trainer.on_epoch = (
            lambda rec: trainer.request_reshape(2)
            if rec["epoch"] == 1 else None)
        try:
            return trainer.run()
        finally:
            mesh_lib.set_topology(None)

    stats.reset("fleet/")
    t0 = time.perf_counter()
    recs = run("inplace")
    snap = stats.snapshot("fleet/")
    assert [r["devices"] for r in recs] == [4, 4, 2, 2], recs
    assert stats.get("fleet/reshard_fallbacks") == 0, \
        "in-HBM reshard fell back on a healthy run"
    inplace_s = snap.get("fleet/reshard_inplace_s.sum", 0.0)
    assert snap.get("fleet/reshard_inplace_s.count", 0) >= 1
    print(f"  phase A: in-HBM 4->2 reshard in {inplace_s:.3f}s, "
          f"zero fallbacks ({time.perf_counter() - t0:.0f}s)",
          flush=True)

    os.environ["PT_RESHARD_INPLACE"] = "0"
    try:
        control = run("ckpt")
    finally:
        del os.environ["PT_RESHARD_INPLACE"]
    for a, b in zip(recs, control):
        assert abs(a["loss"] - b["loss"]) < 1e-6, \
            f"in-HBM trajectory diverged from checkpoint path: {a} {b}"
    print("  phase A: loss trajectory identical to the checkpoint-path "
          "control (bit-parity oracle holds)", flush=True)


def _spawn(store_port, rid, launch_port):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{launch_port}",
         WORKER, str(store_port), rid],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def _run_fleet(trace, drain_at=None):
    """One 2-replica fleet run of ``trace``; optionally mark rep0
    draining ``drain_at`` seconds in. Returns (results by submit
    order, drain latency seconds or None, router_migrated count)."""
    stats.reset("serve/")
    base = 9100 + (os.getpid() + (0 if drain_at is None else 50)) % 400
    router = Router(port=0, dead_after=20.0)
    procs = [_spawn(router.store.port, f"rep{i}", base + i)
             for i in range(2)]
    try:
        router.wait_replicas(2, timeout=120)
        ids = []
        t0 = time.monotonic()
        drained = [None]

        def _drain_now():
            td = time.monotonic()
            router.mark_draining("rep0")
            while router.directory.state("rep0") != "drained":
                router.poll()
                time.sleep(0.02)
            drained[0] = time.monotonic() - td
            print(f"  phase B: rep0 drained in {drained[0]:.2f}s "
                  f"mid-traffic", flush=True)

        for a in trace:
            while time.monotonic() - t0 < a.t:
                if drain_at is not None and \
                        time.monotonic() - t0 >= drain_at:
                    drain_at = None
                    _drain_now()
                router.poll()
                time.sleep(0.01)
            ids.append(router.submit(a.prompt,
                                     max_new_tokens=a.max_new_tokens))
        if drain_at is not None:
            _drain_now()
        results = router.drain(timeout=120)
        drained_in = drained[0]
        migrated = int(stats.get("serve/router_migrated"))
        return [results[q] for q in ids], drained_in, migrated
    finally:
        router.shutdown()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
        router.close()


def phase_serve():
    trace = loadgen.poisson_trace(10, qps=4.0, seed=7, vocab=96,
                                  prompt_len=(6, 24),
                                  new_tokens=(24, 48))
    control, _none, _m = _run_fleet(trace)
    assert all(r["status"] == "done" for r in control), control
    drained, drain_s, migrated = _run_fleet(trace, drain_at=1.0)
    assert all(r["status"] == "done" for r in drained), \
        [r for r in drained if r["status"] != "done"]    # zero id loss
    assert migrated > 0, \
        "drain never migrated an in-flight request mid-decode"
    assert drain_s is not None and drain_s < 30.0, drain_s
    # byte-identical streams: migration must not fork any stream
    for i, (a, b) in enumerate(zip(control, drained)):
        assert a["tokens"] == b["tokens"], \
            (i, a["tokens"], b["tokens"])
    print(f"  phase B: {len(drained)} requests, {migrated} migrated "
          f"mid-decode, all streams byte-identical to the no-drain "
          f"control", flush=True)


def main():
    import tempfile
    t0 = time.perf_counter()
    phase_train(tempfile.mkdtemp(prefix="reshard_smoke_"))
    phase_serve()
    print(f"RESHARD SMOKE OK ({time.perf_counter() - t0:.0f}s)",
          flush=True)


if __name__ == "__main__":
    main()
