#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers its limits are
set from (PERF.md keeps the readings; ``limits/<cell>.json`` the limits):

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3,... \
        [--controls 3] [--seconds 20]

For every seed it reads the program against the plain reference (the lower
reading is the largest of these). For the first ``--controls`` seeds it
also reads the controls — the reference put in the program's place with its
matrix products in float8 and in int8 — and, for a training cell, the planted fault
"half of the batch left out" (the upper readings are the smallest of
these). One process reads all seeds, so the programs compile once. The
benchmark's own runs never come here.

One JSON line per seed goes to standard output and to
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


def _leaf_gaps(program, reference):
    """Every leaf's gap, by the measure of ``correct._worst_leaf_gap``."""
    import statistics
    out = {}
    for key in ("grad_norm", "change_norm"):
        ref = reference[key]
        floor = statistics.median(ref.values())
        out[key] = {k: (program[key][k] - ref[k]) / max(ref[k], floor)
                    for k in ref}
    return out


def train_seed(cell, seed, with_control, leaves_dir=None):
    from benchmark.kinds import train
    job = cell["traffic"]
    state = train.setup(cell, seed)
    batches = train.make_batches(seed, job["checked_steps"], job["batch"],
                                 job["seq_len"], cell["model"]["vocab_size"])
    train.drive_checked_steps(state, batches)
    program = train.program_readings(state)
    weights = state["weights"]
    weights_lib.free((state["params"], state["opt_state"]))
    state.clear()
    reference = train.reference_readings(cell, weights, batches)
    numbers = correct.train_numbers(program, reference)
    out = {"seed": seed, "program": _flat(numbers),
           "worst_leaves": {k: v[1] for k, v in numbers.items() if v[1]},
           "loss": {"program": program["loss"],
                    "reference": reference["loss"]}}
    if with_control:
        control = train.reference_readings(cell, weights, batches,
                                           mode="fp8")
        out["control_fp8"] = _flat(correct.train_numbers(control, reference))
        control8 = train.reference_readings(cell, weights, batches,
                                            mode="int8")
        out["control_int8"] = _flat(
            correct.train_numbers(control8, reference))
        half = train.reference_readings(cell, weights, batches,
                                        rows=job["batch"] // 2)
        out["fault_half_batch"] = _flat(
            correct.train_numbers(half, reference))
        if leaves_dir:
            with open(os.path.join(leaves_dir, f"{cell['workload']}."
                                   f"{seed}.leaves.json"), "w") as f:
                json.dump({"program": _leaf_gaps(program, reference),
                           "control_fp8": _leaf_gaps(control, reference),
                           "reference_grad_norm": reference["grad_norm"]},
                          f)
    weights_lib.free(weights)
    return out


def serve_seed(cell, seed, with_control, seconds):
    from benchmark.kinds import serve_closed as serve
    spans = harness.Spans()
    weights, eng, loop = serve.setup(cell, seed, spans)
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
    weights_lib.free((eng.kp, eng.vp, getattr(eng, "_stacked", None)))
    del eng, loop
    checked = serve.reference_gaps(cell, weights, samples)
    out = {"seed": seed, "finished": len(done), "failed": failed,
           "sampled_tokens": sum(len(s["tokens"]) for s in samples),
           "program": _flat(correct.serve_numbers(checked))}
    gaps = sorted((g for s in checked for g in s["gaps"]), reverse=True)
    out["program_top_gaps"] = gaps[:5]
    out["program_nonzero_share"] = sum(1 for g in gaps if g > 0) / len(gaps)
    if with_control:
        control = serve.reference_gaps(cell, weights, samples, mode="fp8")
        out["control_fp8"] = _flat(correct.serve_numbers(control))
        cg = sorted((g for s in control for g in s["gaps"]), reverse=True)
        out["control_top_gaps"] = cg[:5]
        out["control_nonzero_share"] = sum(1 for g in cg if g > 0) / len(cg)
        control8 = serve.reference_gaps(cell, weights, samples, mode="int8")
        out["control_int8"] = _flat(correct.serve_numbers(control8))
    weights_lib.free(weights)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--leaves", action="store_true",
                    help="training: also write every leaf's signed gap")
    ap.add_argument("--override", default="",
                    help="traffic keys to change, e.g. batch=8,seq_len=2048 "
                         "(a second witness at another size)")
    ap.add_argument("--no-kernels", action="store_true",
                    help="a second witness: the program's XLA paths, with "
                         "its flag use_pallas_kernels off")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    if args.no_kernels:
        from paddle_tpu import flags
        flags.set_flags({"use_pallas_kernels": False})
        args.override += ",kernels=off"
    for item in filter(None, args.override.split(",")):
        key, value = item.split("=")
        if key == "kernels":
            continue
        cell["traffic"][key] = type(cell["traffic"][key])(value)
    jax, _ = harness.require_tpu(cell["chips"])
    harness.enable_compile_cache()
    out_dir = os.path.join(harness.ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    kind = cell["traffic"]["kind"]
    with open(os.path.join(out_dir, args.workload + ".jsonl"), "a") as f:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            if kind == "train":
                line = train_seed(cell, seed, i < args.controls,
                                  out_dir if args.leaves else None)
                line["override"] = args.override
            elif kind == "serve_closed":
                line = serve_seed(cell, seed, i < args.controls,
                                  args.seconds)
            else:
                raise SystemExit(f"calibrate: no readings for kind {kind!r}")
            line["seconds"] = time.perf_counter() - t0
            text = json.dumps(line)
            print(text, flush=True)
            f.write(text + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
