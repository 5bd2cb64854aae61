#!/usr/bin/env python3
"""``calibrate.py``'s readings for a cell of kind ``serve_retention`` (that
file knows the two kinds it was written with and is not edited):

    python3 benchmark/calibrate_retention.py --workload <cell> \
        --seeds 1,2,3,... [--controls 3] [--seconds 20]

For every seed: the program's widest ``logit_gap`` against the plain
reference over the sampled requests. For the first ``--controls`` seeds
also the controls over the same requests: the reference in the program's
place with its matrix products in float8 and in int8, and with its state
rounded to bfloat16 after every token (the precision below the float32
that the configuration states for the state). One process reads all
seeds. One JSON line per seed goes to standard output and to
``chiprun_out/calibrate/<cell>.jsonl``.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import correct, harness, weights as weights_lib   # noqa: E402


def _flat(numbers):
    return {k: v[0] for k, v in numbers.items()}


def serve_seed(cell, seed, with_control, seconds):
    from benchmark.kinds import serve_retention as serve
    weights, eng, loop = serve.setup(cell, seed, harness.Spans())
    for c in loop.clients:
        loop.submit(c)
    while loop.waiting_for_first_token():
        loop.pump()
    t0 = time.perf_counter()
    while loop.pump() < t0 + seconds:
        pass
    done = [f for f in loop.finished if f["t_done"] > t0]
    samples = serve.pick_samples(done, cell["traffic"]["checked_requests"],
                                 seed)
    failed = loop.failed
    weights_lib.free((eng.state, eng.kp, eng.vp))
    del eng, loop
    checked = serve.reference_gaps(cell, weights, samples)
    gaps = sorted((g for s in checked for g in s["gaps"]), reverse=True)
    out = {"seed": seed, "finished": len(done), "failed": failed,
           "sampled_prompt_tokens": sum(len(s["prompt"]) for s in samples),
           "sampled_tokens": sum(len(s["tokens"]) for s in samples),
           "program": _flat(correct.serve_numbers(checked)),
           "program_top_gaps": gaps[:5],
           "program_nonzero_share": sum(1 for g in gaps if g > 0)
           / len(gaps)}
    if with_control:
        for name, kw in (("control_fp8", {"mode": "fp8"}),
                         ("control_int8", {"mode": "int8"}),
                         ("control_state_bf16", {"state": "bfloat16"})):
            t = time.perf_counter()
            control = serve.reference_gaps(cell, weights, samples, **kw)
            out[name] = _flat(correct.serve_numbers(control))
            cg = sorted((g for s in control for g in s["gaps"]),
                        reverse=True)
            out[name + "_top_gaps"] = cg[:5]
            out[name + "_seconds"] = time.perf_counter() - t
    weights_lib.free(weights)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if cell["traffic"]["kind"] != "serve_retention":
        raise SystemExit("calibrate_retention: a serve_retention cell; "
                         "calibrate.py reads the other kinds")
    harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            line = serve_seed(cell, seed, i < args.controls, args.seconds)
            line["seconds"] = time.perf_counter() - t0
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
