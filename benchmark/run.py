#!/usr/bin/env python3
"""The benchmark's one command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once. It refuses to start without a TPU (no CPU
fallback, no result line), makes weights and inputs from ``--seed``, warms
only that cell's shapes, measures for ``--seconds``, checks what the timed
path produced against the plain reference, and prints ONE JSON object as
the last line of standard output. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics (a short
stretch of the run is profiled) and a ``breakdown``.

Everything that belongs to one cell is found by name from BENCHMARK.json:
``configs/<config>.json``, ``traffic/<traffic>.json`` (whose ``kind`` names
``kinds/<kind>.py``), ``limits/<cell>.json`` and, for each per-layer metric,
``layer_metrics/<metric>.py``. See README.md beside this file.
"""

import time

_T0 = time.perf_counter()

import argparse      # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness   # noqa: E402


def per_layer_metrics(cell, result, spans, device_kind):
    from benchmark import peaks, trace_reduce, work
    ctx = {
        "cell": cell, "model": cell["model"], "traffic": cell["traffic"],
        "peaks": peaks.peaks_for(device_kind), "trace": result["trace"],
        "counters": result["counters"], "spans": spans, "work": work,
        "trace_reduce": trace_reduce, "notes": [],
    }
    out = {}
    for metric in harness.metrics_of(cell, "per_layer"):
        reader = harness.load_module("layer_metrics", metric["name"])
        value = reader.read(ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    for note in ctx["notes"]:
        harness.say(note)
    return out


def execute(cell, seed, seconds, trace, jax, devices, t0=_T0):
    """A whole run but for the look for a chip (the tests come in here)."""
    spans = harness.Spans()
    kind = harness.load_module("kinds", cell["traffic"]["kind"])
    result = kind.run({"cell": cell, "seed": seed, "seconds": seconds,
                       "trace": trace, "spans": spans, "jax": jax,
                       "devices": devices, "t0": t0})
    device = result["device"]
    if trace:
        metrics = per_layer_metrics(cell, result, spans, device["kind"])
        reduced = result["trace"]
        device = dict(device, busy_s=reduced["busy_s"],
                      window_s=reduced["window_s"])
        from benchmark import trace_reduce
        breakdown = trace_reduce.breakdown(reduced)
    else:
        units = {m["name"]: m["unit"]
                 for m in harness.metrics_of(cell, "end_to_end")}
        metrics = {name: {"value": value, "unit": units[name]}
                   for name, value in result["end_to_end"].items()
                   if name in units}
        breakdown = None
    harness.emit(result, metrics, device, breakdown)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    jax, devices = harness.require_tpu(cell["chips"])
    harness.say(f"jax {jax.__version__}, {len(devices)} x "
                f"{devices[0].device_kind}; compile cache at "
                f"{harness.enable_compile_cache()}")
    execute(cell, args.seed, args.seconds, bool(args.trace), jax, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
