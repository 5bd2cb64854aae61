"""Pipelined-serving smoke (tools/ci.sh serve, ISSUE 4): run a
pipelined decode UNDER FAULT INJECTION on CPU and prove, end to end,

- byte-identical survivor streams at in-flight depth 1 vs 3 on the
  plain, chunked and speculative paths (contiguous engine) and the
  paged engine, plain and speculative;
- a nan-poisoned request is evicted alone, at harvest, on every path;
- a queued deadline_s=0 request is evicted without touching peers;
- the pipeline actually pipelines (serve/host_gap_s samples recorded,
  serve/inflight returns to 0) and the serve/ stats surface is live.

Exit code 0 + "SERVE SMOKE OK" on success; any divergence asserts.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from paddle_tpu import stats  # noqa: E402
from paddle_tpu.models import gpt  # noqa: E402
from paddle_tpu.inference import make_engine  # noqa: E402
from paddle_tpu.inference.decode_engine import DecodeEngine  # noqa: E402
from paddle_tpu.testing import faults  # noqa: E402


def _model():
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=256, d_model=32,
                        n_layers=2, n_heads=4, dtype=jnp.float32)
    return gpt.GPT(cfg, seed=0)


def _serve(make_engine, depth):
    """One faulted serving episode; returns the survivors' streams."""
    faults.clear()
    stats.reset("serve/")
    eng = make_engine(depth)
    rs = np.random.RandomState(0)
    ok = [list(rs.randint(0, 96, size=n)) for n in (5, 17)]
    poisoned = list(rs.randint(0, 96, size=7))
    r_ok = [eng.submit(p, max_new_tokens=8) for p in ok]
    r_poi = eng.submit(poisoned, max_new_tokens=8)   # slot 2
    r_dead = eng.submit([1, 2, 3], max_new_tokens=8, deadline_s=0.0)
    eng.step()
    with faults.inject("engine.poison_logits", "nan", slot=2, count=1):
        eng.step()
    eng.run()
    assert r_poi.failed and r_poi.error == "non-finite logits", \
        "poisoned request not evicted"
    assert r_dead.failed and "deadline" in r_dead.error
    assert all(r.done and not r.failed for r in r_ok)
    assert stats.get("serve/nonfinite_evictions") == 1
    # queued expiry lands on the queue-reject counter (distinct from
    # mid-decode serve/deadline_evictions — no device work was wasted)
    assert stats.get("serve/queue_deadline_rejects") == 1
    assert stats.get("serve/deadline_evictions") == 0
    assert stats.get("serve/inflight") == 0
    if depth > 1:
        assert stats.snapshot("serve/").get(
            "serve/host_gap_s.count", 0) >= 1, "pipeline never measured"
    return [list(r.tokens) for r in r_ok]


def main():
    model = _model()
    cases = {
        "plain": lambda d: DecodeEngine(
            model, max_slots=3, max_len=128, inflight=d),
        "chunked": lambda d: DecodeEngine(
            model, max_slots=3, max_len=128, steps_per_call=4,
            inflight=d),
        "speculative": lambda d: DecodeEngine(
            model, max_slots=3, max_len=128, speculative_k=3,
            steps_per_call=2, inflight=d),
        # the serving default (factory → paged)
        "paged": lambda d: make_engine(
            model, n_pages=24, max_slots=3, steps_per_call=2,
            inflight=d),
        "paged_spec": lambda d: make_engine(
            model, n_pages=24, max_slots=3, steps_per_call=2,
            speculative_k=3, inflight=d),
    }
    for name, make in cases.items():
        base = _serve(make, 1)
        piped = _serve(make, 3)
        assert piped == base, \
            f"{name}: depth-3 streams diverged from depth-1"
        print(f"  {name}: depth1 == depth3 "
              f"({sum(len(s) for s in base)} survivor tokens)",
              flush=True)
    print(stats.table("serve/"))
    print("SERVE SMOKE OK", flush=True)


if __name__ == "__main__":
    main()
