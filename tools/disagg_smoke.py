"""Disaggregated-serving smoke (tools/ci.sh disagg, ISSUE 12): one
prefill + one decode replica — REAL processes through the
distributed/launch.py CLI — behind the role-aware router on CPU,
proving end to end (~1 min):

- a fixed-seed workload routed prefill→wire→decode returns streams
  BIT-IDENTICAL to single-replica serving (PT_KV_WIRE=fp32 for the
  identity phase; every decode phase ran on the decode replica and
  every handoff was counted);
- the KV wire actually moved bytes (replica-side counters ride the
  heartbeat load gauges, so the router process can assert them);
- a repeated-system-prompt workload hits the FLEET prefix directory:
  the decode replica's `serve/fleet_prefix_hit_tokens` goes nonzero
  (pages published by one admission served another replica's prefill)
  and the router skips the prefill tier once coverage is complete
  (serve/router_prefill_skipped).

Exit 0 + "DISAGG SMOKE OK" on success; any divergence asserts.
"""
import os
import subprocess
import sys
import time

# a multi-process CPU fleet smoke: one host is one process driving its
# chips, so neither this parent nor its workers may reach for one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PT_KV_WIRE"] = "fp32"      # the bit-identity contract
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import numpy as np  # noqa: E402

from paddle_tpu import stats  # noqa: E402
from paddle_tpu.inference.paged_engine import PagedDecodeEngine  # noqa: E402
from paddle_tpu.serving import FrontEnd, Router  # noqa: E402

WORKER = os.path.join(REPO, "tests", "_disagg_worker.py")


def _spawn(store_port, rid, role, launch_port):
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "1",
         "--master", f"127.0.0.1:{launch_port}",
         WORKER, str(store_port), rid, role],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE)


def main():
    import _disagg_worker
    rs = np.random.RandomState(0)
    sysprompt = [int(x) for x in rs.randint(0, 96, size=260)]
    uniques = [[int(x) for x in rs.randint(0, 96, size=n)]
               for n in (9, 40, 140)]
    # repeated-system-prompt tail: same 2 warm pages + unique suffixes
    warm = [sysprompt + [int(x) for x in rs.randint(0, 96, size=6)]
            for _ in range(4)]
    prompts = uniques + [sysprompt] + warm
    budgets = [5, 6, 7, 4, 4, 4, 4, 4]
    n_cold = len(uniques) + 1

    # single-replica oracle (identical model builder as the workers)
    eng = PagedDecodeEngine(_disagg_worker.build_model(), n_pages=48,
                            max_slots=2, page_size=128)
    fe = FrontEnd(eng)
    oracle = [fe.submit(p, max_new_tokens=b)
              for p, b in zip(prompts, budgets)]
    fe.run()
    want = [r.tokens for r in oracle]
    print(f"  oracle: {len(want)} streams on one replica", flush=True)

    router = Router(port=0, dead_after=15.0)
    procs = [_spawn(router.store.port, "pf0", "prefill", 8865),
             _spawn(router.store.port, "dc0", "decode", 8866),
             _spawn(router.store.port, "dc1", "decode", 8867)]
    try:
        router.wait_replicas(3, timeout=90)
        # phase 1 (cold): every prompt goes prefill->wire->decode;
        # the sysprompt's pages get published to the fleet directory
        t0 = time.perf_counter()
        ids = [router.submit(p, max_new_tokens=b)
               for p, b in zip(prompts[:n_cold], budgets[:n_cold])]
        results = router.drain(timeout=180)
        # phase 2 (warm): the directory now covers the system prompt's
        # full pages — the router skips the prefill tier. A FRESH
        # decode replica joins first (most free pages → placement
        # prefers it): it has no local cache, so serving the warm
        # requests forces a fleet fetch — the cross-replica hit the
        # smoke exists to prove
        procs.append(_spawn(router.store.port, "dc2", "decode", 8868))
        router.wait_replicas(4, timeout=90)
        ids2 = [router.submit(p, max_new_tokens=b)
                for p, b in zip(prompts[n_cold:], budgets[n_cold:])]
        results = router.drain(timeout=180)
        wall = time.perf_counter() - t0
        all_ids = ids + ids2
        assert sorted(results) == sorted(all_ids)
        got = [results[q]["tokens"] for q in all_ids]
        assert got == want, "disaggregated streams diverged from " \
            "single-replica serving on the fp32 wire"
        assert all(results[q]["status"] == "done" for q in all_ids)
        assert {results[q]["replica"] for q in all_ids} <= \
            {"dc0", "dc1", "dc2"}
        print(f"  bit-identity: {len(all_ids)} streams equal through "
              f"prefill->wire->decode ({wall:.1f}s)", flush=True)

        handoffs = stats.get("serve/router_prefill_handoffs")
        skipped = stats.get("serve/router_prefill_skipped")
        assert handoffs > 0, "no prefill->decode handoffs happened"
        assert skipped > 0, "fleet coverage never skipped the " \
            "prefill tier"
        # replica-side counters ride the heartbeat load gauges: the
        # prefill replica moved wire bytes, and SOME decode replica
        # fetched fleet pages
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            pf = router.directory.load("pf0") or {}
            hits = max((router.directory.load(r) or {}).get(
                "fleet_hit_tokens", 0)
                for r in ("dc0", "dc1", "dc2"))
            if hits and pf.get("kv_transfer_bytes_wire"):
                break
            time.sleep(0.2)
        assert pf.get("kv_transfer_bytes_wire", 0) > 0, pf
        assert hits > 0, \
            "repeated-system-prompt workload never hit the fleet " \
            "prefix directory"
        print(f"  fleet: hit_tokens={hits} on a decode replica, "
              f"router handoffs={int(handoffs)}, "
              f"prefill skipped={int(skipped)}", flush=True)
    finally:
        router.shutdown()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=10)
        router.close()
    print("DISAGG SMOKE OK", flush=True)


if __name__ == "__main__":
    main()
