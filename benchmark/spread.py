#!/usr/bin/env python3
"""How widely a cell's runs spread, and the bound that follows from it.

    python3 benchmark/spread.py --workload <cell> --runs 6 --seconds 30 --seed0 <n>
    python3 benchmark/spread.py --rule

The first form runs ``benchmark/run.py --trace 0`` ``--runs`` times, each a
process of its own with the next seed, and prints each run's end-to-end
metrics, each metric's median, its spread in its unit and as a share of the
median, and that share against the metric's ``bound`` in BENCHMARK.json. A
run that is not ``correct`` is printed as such and counted, never dropped.
Run it on the chip before handing in a PR: a side whose spread passes the
bound cannot be told from its parent, and the check comes back
``unresolved``. ``--out <file>`` also writes the set as one entry for
``spreads.json``. Each run's own output goes to standard error, so that
standard output holds the report alone. This process never touches JAX:
the chip is the child's.

The second form reads ``benchmark/spreads.json`` (every set the bounds rest
on) and prints, for each bounded metric, the widest spread on record and
the bound the rule gives: the smallest step of ``LADDER`` of which that
spread is at most half, and never less than the bound in BENCHMARK.json.

``spread()`` is this repo's reading of the driver's own sentence (ledger,
PR 31, ``reason``): "A spread leaves out the run farthest from its median
where that narrows it", of the largest less the smallest of one side's
runs. The driver's code is not in the repo. ``quartile_spread()`` is the
other measure a ``benchmark`` PR's bounds are judged by: the distance
between the quartiles as ``statistics.quantiles(values, n=4)`` gives them;
``middle_half()`` is the same with the farthest run left out, which that
check holds to half the bound. The rule reads ``spread()``, the widest of
the three.
"""

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPREADS = os.path.join(HERE, "spreads.json")
# up to the largest bound the benchmark's contract allows (0.1); the steps
# past 0.05 came with PR 32's first check, which read chat16's TTFT medians
# too wide for 5% (PERF.md section 2)
LADDER = (0.01, 0.015, 0.02, 0.025, 0.03, 0.04, 0.05, 0.06, 0.075, 0.1)
RUN_TIMEOUT_S = 1500        # the first run of a cell in a checkout compiles


def spread(values) -> float:
    """Largest less smallest, without the one value farthest from the
    median where that narrows it. Fewer than three values: nothing is
    left out (one of two would leave no spread at all)."""
    v = sorted(float(x) for x in values)
    if not v:
        raise ValueError("spread() of no values")
    if len(v) < 3:
        return v[-1] - v[0]
    mid = statistics.median(v)
    rest = list(v)
    rest.remove(max(v, key=lambda x: abs(x - mid)))
    return min(v[-1] - v[0], rest[-1] - rest[0])


def quartile_spread(values) -> float:
    q1, _, q3 = statistics.quantiles([float(x) for x in values], n=4)
    return q3 - q1


def middle_half(values) -> float:
    """The distance between the quartiles of what is left when the one
    value farthest from the median is taken out, or of all the values
    where that is narrower: how the check of a ``benchmark`` PR words its
    measure of tightness ("the middle half of 6 runs ..., of each side's
    runs the one farthest from its median left out": PR 32's first check,
    PERF.md section 2). It holds the MEAN of its two sets' readings to
    half the bound, and the bound to eight times the wider
    ``quartile_spread`` of all the runs. Never wider than ``spread()``."""
    v = sorted(float(x) for x in values)
    if len(v) < 4:
        return quartile_spread(v) if len(v) > 1 else 0.0
    mid = statistics.median(v)
    rest = list(v)
    rest.remove(max(v, key=lambda x: abs(x - mid)))
    return min(quartile_spread(v), quartile_spread(rest))


def bound_for(widest_share: float, parent_bound: float) -> float:
    """The rule: the smallest step of the ladder that is at least twice the
    widest spread (as a share of the median) and not under the parent's
    bound; the top step, or the parent's bound, where none is."""
    for step in LADDER:
        if step >= parent_bound and 2 * widest_share <= step:
            return step
    return max(LADDER[-1], parent_bound)


def parse_result_line(stdout: str):
    """The run's result (its last line of standard output) as
    ``{"correct": bool, "values": {metric: value}}``, or ``None`` where
    the run printed none."""
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    if not lines:
        return None
    try:
        line = json.loads(lines[-1])
        return {"correct": bool(line["correct"]),
                "values": {name: float(m["value"])
                           for name, m in line["metrics"].items()}}
    except (ValueError, KeyError, TypeError):
        return None


def set_shares(entry: dict) -> dict:
    """``{metric: spread as a share of the median}`` of one entry of
    ``spreads.json``: worked out from its runs, or as the ledger or a
    check's refusal gave it."""
    if "runs" not in entry:
        return {name: s["spread"] / s["median"]
                for name, s in entry["spreads"].items()}
    by_metric = {}
    for run in entry["runs"]:
        for name, value in run["values"].items():
            by_metric.setdefault(name, []).append(value)
    return {name: spread(vals) / statistics.median(vals)
            for name, vals in by_metric.items()}


def widest_shares(sets, seconds) -> dict:
    """``{metric: (share, where)}``: the widest spread over the sets
    measured at ``seconds`` (sets at another length, and those that ran
    in an ``experiment``'s environment, are for the record)."""
    widest = {}
    for entry in sets:
        if entry["seconds"] != seconds or "experiment" in entry:
            continue
        where = (entry.get("name")
                 or f"{entry['from']}, PR {entry['pr']}, {entry['side']}")
        for name, share in set_shares(entry).items():
            if share > widest.get(name, (-1.0, ""))[0]:
                widest[name] = (share, f"{entry['cell']} ({where})")
    return widest


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def print_rule() -> int:
    bench = _bench()
    with open(SPREADS) as f:
        widest = widest_shares(json.load(f)["sets"], bench["run_seconds"])
    for metric in bench["end_to_end"]:
        name, bound = metric["name"], metric["bound"]
        if name not in widest:
            print(f"{name}: no set on record; bound {bound:.3g}")
            continue
        share, where = widest[name]
        print(f"{name}: widest spread {share:.4%} in {where}; the rule "
              f"gives {bound_for(share, bound):.3g}, BENCHMARK.json has "
              f"{bound:.3g}")
    return 0


def report(runs, bounds, units, out=sys.stdout) -> None:
    """Each metric of a set: the runs, the median, both spreads, and the
    first against the bound."""
    sound = [r for r in runs if r["values"]]
    for name in sorted({n for r in sound for n in r["values"]}):
        vals = [r["values"][name] for r in sound if name in r["values"]]
        mid = statistics.median(vals)
        s = spread(vals)
        line = (f"{name}: runs {' '.join(f'{v:.6g}' for v in vals)}; "
                f"median {mid:.6g} {units.get(name, '')}; spread {s:.6g} = "
                f"{s / mid:.4%} of the median")
        if len(vals) >= 2:
            line += (f" (quartiles {quartile_spread(vals) / mid:.4%}, "
                     f"middle half {middle_half(vals) / mid:.4%})")
        if name in bounds:
            of_bound = s / mid / bounds[name]
            line += f"; {of_bound:.2f} of the bound {bounds[name]:.3g}"
            if of_bound > 0.5 and name != "setup_s":
                line += ": OVER HALF, a later check may not resolve"
        print(line, file=out)
    bad = [r["seed"] for r in runs if not r["correct"]]
    print(f"{len(runs)} runs, {len(bad)} not correct"
          + (f": seeds {bad}" if bad else ""), file=out)


def run_set(workload, n_runs, seconds, seed0):
    runs = []
    for seed in range(seed0, seed0 + n_runs):
        cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
               workload, "--seed", str(seed), "--seconds", f"{seconds:g}",
               "--trace", "0"]
        try:
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  text=True, timeout=RUN_TIMEOUT_S)
            sys.stderr.write(proc.stdout)       # the run's own log, kept
            parsed = parse_result_line(proc.stdout)
        except subprocess.TimeoutExpired:
            parsed = None
        run = {"seed": seed, **(parsed or {"correct": False, "values": {}})}
        runs.append(run)
        print(f"seed {seed}: "
              + ("no result line" if parsed is None else
                 ("correct" if run["correct"] else "NOT CORRECT") + " "
                 + " ".join(f"{k}={v:.6g}" for k, v in run["values"].items())),
              flush=True)
    return runs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rule", action="store_true")
    ap.add_argument("--workload")
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int)
    ap.add_argument("--out", help="write the set here, as an entry for "
                                  "spreads.json")
    ap.add_argument("--commit", help="recorded in --out as given")
    args = ap.parse_args(argv)
    if args.rule:
        return print_rule()
    if args.workload is None or args.seed0 is None:
        ap.error("--workload and --seed0 are required without --rule")
    bench = _bench()
    if args.workload not in {w["name"] for w in bench["workloads"]}:
        ap.error(f"no workload {args.workload!r} in BENCHMARK.json")
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    runs = run_set(args.workload, args.runs, seconds, args.seed0)
    report(runs,
           {m["name"]: m["bound"] for m in bench["end_to_end"]},
           {m["name"]: m["unit"] for m in bench["end_to_end"]})
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"from": "chip", "commit": args.commit,
                       "cell": args.workload, "seconds": seconds,
                       "runs": runs}, f, indent=1)
    return 0 if all(r["correct"] for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
