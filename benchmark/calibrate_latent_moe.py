#!/usr/bin/env python3
"""``calibrate.py``'s readings for a cell of kind ``serve_latent_moe``
(that file knows the two kinds it was written with and is not edited):

    python3 benchmark/calibrate_latent_moe.py --workload <cell> \
        --seeds 1,2,3,... [--controls 3] [--seconds 20]

For every seed: every number the kind compares (``logit_gap``, the
gaps' mean, ``routing_mismatch``, ``router_mismatch``), read from what
the engine served and reported in the run, against the plain reference
over the checked requests, and ``correct`` under the cell's committed
limits; and the ``altered`` reading: each served token of the checked
requests replaced, one at a time, by the token half the vocabulary
away, and the gap that token would read (the smallest, the first
percentile, the median). For the first ``--controls`` seeds also the
controls, each one precision below what the configuration states: the
reference in the program's place with its matrix products in float8 and
in int8, with its router in bfloat16, and with what the latent cache
keeps rounded to float8; over the ``--control-requests`` shortest of the
checked requests whose routing the engine reported (a control is a
second pass of the reference a request),
each with its ``correct`` under the committed limits, which has to come
out false. One process reads all seeds. One JSON line per seed goes to
standard output and to ``chiprun_out/calibrate/<cell>.jsonl``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, weights as weights_lib   # noqa: E402

CONTROLS = (("control_fp8", {"mode": "fp8"}),
            ("control_int8", {"mode": "int8"}),
            ("control_router_bf16", {"router": "bfloat16"}),
            ("control_cache_fp8", {"cache": "float8"}))


NUMBERS = ("logit_gap", "logit_gap_mean", "routing_mismatch",
           "router_mismatch", "unfinished")


def _numbers(serve, checked, limits):
    """Every number, and ``correct`` under the cell's limits."""
    out = {name: value for name, value, _ in serve.compare(
        checked, dict.fromkeys(NUMBERS, 0))}
    out["correct"] = all(value <= limit for _, value, limit
                         in serve.compare(checked, limits))
    return out


def _altered(cell, samples, memo):
    """The gap each served token would read were it the token half the
    vocabulary away, from the reference's rows already made."""
    import jax.numpy as jnp
    import numpy as np
    vocab = cell["model"]["vocab_size"]
    gaps = []
    for s in samples:
        rows = memo[id(s)][0]
        other = (jnp.asarray(s["tokens"], jnp.int32) + vocab // 2) % vocab
        gaps.extend(np.asarray(jnp.max(rows, -1) - jnp.take_along_axis(
            rows, other[:, None], axis=-1)[:, 0]))
    return {"smallest": float(np.min(gaps)),
            "p01": float(np.percentile(gaps, 1)),
            "median": float(np.median(gaps))}


def serve_seed(cell, seed, with_control, seconds, control_requests=3):
    from benchmark.kinds import serve_latent_moe as serve
    weights, eng, loop, tap = serve.setup(cell, seed, harness.Spans())
    for c in loop.clients:
        loop.submit(c)
    while loop.waiting_for_first_token():
        loop.pump()
    t0 = time.perf_counter()
    while loop.pump() < t0 + seconds:
        pass
    done = [f for f in loop.finished if f["t_done"] > t0]
    samples = serve.checked_samples(
        done, cell["traffic"]["checked_requests"], seed, tap)
    failed, limits = loop.failed, cell["limits"]
    weights_lib.free((eng.state, eng.kp, eng.vp))
    del eng, loop, tap
    memo = {}
    checked = serve.reference_gaps(cell, weights, samples, memo=memo)
    gaps = sorted((g for s in checked for g in s["gaps"]), reverse=True)
    out = {"seed": seed, "finished": len(done), "failed": failed,
           "checked_prompt_tokens": sum(len(s["prompt"]) for s in samples),
           "checked_tokens": sum(len(s["tokens"]) for s in samples),
           "routings": sum(c["routings"] for c in checked),
           "program": _numbers(serve, checked, limits),
           "program_top_gaps": gaps[:5],
           "program_nonzero_share": sum(1 for g in gaps if g > 0)
           / max(len(gaps), 1),
           "altered": _altered(cell, samples, memo)}
    if with_control:
        few = sorted((s for s in samples if "routing" in s),
                     key=lambda s: len(s["prompt"]))[:control_requests]
        out["control_tokens"] = sum(len(s["prompt"]) + len(s["tokens"])
                                    for s in few)
        out["program_on_control_requests"] = _numbers(
            serve, serve.reference_gaps(cell, weights, few, memo=memo),
            limits)
        for name, kw in CONTROLS:
            t = time.perf_counter()
            control = serve.reference_gaps(cell, weights, few, memo=memo,
                                           **kw)
            out[name] = _numbers(serve, control, limits)
            out[name + "_seconds"] = time.perf_counter() - t
    weights_lib.free(weights)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-requests", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell["traffic"]["kind"] != "serve_latent_moe":
        raise SystemExit("calibrate_latent_moe: a serve_latent_moe cell; "
                         "calibrate.py and calibrate_retention.py read "
                         "the other kinds")
    harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            line = serve_seed(cell, seed, i < args.controls, args.seconds,
                              args.control_requests)
            line["seconds"] = time.perf_counter() - t0
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
