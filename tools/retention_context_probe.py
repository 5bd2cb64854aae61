#!/usr/bin/env python3
"""The decode step of a retention model against the context: the time of
one engine step with every slot live, once with prompts of ``--short``
tokens in every slot and once with prompts of ``--long`` (ISSUE 29, step
7e: equal, which is the architecture's point; a paged softmax cache grows
with the context). Needs a TPU; run through ``chiprun``:

    python3 tools/retention_context_probe.py --short 1024 --long 12288

The model is the benchmark's ``brumby-14b`` configuration with the
benchmark's weights. Prints one JSON line.
"""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def step_ms(eng, rng, vocab, n_prompt, steps):
    """Median ms of ``steps`` engine steps with all slots decoding
    prompts of ``n_prompt`` tokens."""
    # the slots are prefilled one chunk a step, the oldest first: the
    # first one decodes all the while, so its budget covers that too
    chunks = -(-n_prompt // eng.prefill_chunk)
    reqs = [eng.submit(rng.integers(0, vocab, n_prompt).tolist(),
                       max_new_tokens=steps + 60 + eng.S * (chunks + 1))
            for _ in range(eng.S)]
    while not all(r.tokens for r in reqs):
        eng.step()
    for _ in range(8):
        eng.step()
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        eng.step()
        times.append(time.perf_counter() - t0)
    assert all(not r.done for r in reqs), "a request ended inside the probe"
    eng.run()
    return statistics.median(times) * 1e3, max(times) * 1e3


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--short", type=int, default=1024)
    ap.add_argument("--long", type=int, default=12288)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seed", type=int, default=2147650099)
    args = ap.parse_args(argv)
    import numpy as np
    from benchmark import harness
    from benchmark.program_retention import build_model
    from benchmark.reference import power_retention
    from paddle_tpu import inference
    cell = harness.load_cell("brumby-14b.serve-longgen16")
    jax, _ = harness.require_tpu(1)
    harness.enable_compile_cache()
    model_cfg = cell["model"]
    weights = power_retention.make_weights(model_cfg, args.seed)
    eng = inference.make_engine(
        build_model(model_cfg, weights),
        max_slots=cell["traffic"]["max_slots"],
        prefill_chunk=cell["traffic"]["prefill_chunk"])
    rng = np.random.default_rng(args.seed)
    out = {"slots": eng.S, "steps": args.steps}
    for name, n in (("warm", 600), ("short", args.short),
                    ("long", args.long), ("short_again", args.short)):
        med, longest = step_ms(eng, rng, model_cfg["vocab_size"], n,
                               args.steps if name != "warm" else 20)
        out[name] = {"prompt_tokens": n, "step_ms_median": med,
                     "step_ms_longest": longest}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
