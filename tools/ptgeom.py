#!/usr/bin/env python
"""ptgeom CLI — static TPU kernel-geometry verification (ISSUE 20).

    python tools/ptgeom.py                       # sweep + table + gate
    python tools/ptgeom.py --geoms r06           # one ladder rung
    python tools/ptgeom.py --kernels paged_fused,paged_plain
    python tools/ptgeom.py --extra my_kernels.py # off-tree registry
    python tools/ptgeom.py --write-baseline

Drives every registered Pallas kernel wrapper (``ptgeom_cases()`` hooks
in ``paddle_tpu/ops/pallas/``) under ``jax.eval_shape`` at the bench
model ladder x the autotune key space, harvests one
:class:`~paddle_tpu.analysis.kernelmodel.KernelSpec` per launch, and
runs the PT006–PT009 geometry rules over them through the ptlint
engine — same suppressions, same baseline machinery, different facts.

Unlike ptlint this needs jax importable (tracing, never executing:
CPU-only CI shards run it fine). Exit status: 0 clean, 1 on
non-baselined findings, 2 on usage errors or cases that failed to
harvest (a kernel whose trace crashes was NOT verified — that must not
read as green).

Env: ``PTGEOM_GEOMS`` presets ``--geoms``; ``PT_VMEM_BUDGET_MB`` sets
the PT006 budget (see docs/static-analysis.md).
"""

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DEFAULT_BASELINE = os.path.join(ROOT, "tools", "ptgeom_baseline.json")


def _load_extra(path: str):
    """Import an off-tree registry module (must define
    ``ptgeom_cases()``); its launch sites join the project like any
    on-tree file."""
    name = "_ptgeom_extra_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _table(specs, km):
    budget = km.vmem_budget_bytes()
    worst = {}
    for s in specs:
        est = km.vmem_estimate(s)
        key = (s.kernel, f"{s.path}:{s.line}")
        if key not in worst or est > worst[key][0]:
            worst[key] = (est, s.geometry, s.config, s.grid,
                          len(s.aliases))
    rows = [("kernel", "site", "worst vmem", "of budget", "geometry",
             "config", "grid", "aliases")]
    for (kern, site), (est, g, c, grid, na) in sorted(worst.items()):
        rows.append((kern, site, f"{est / 2**20:.2f} MiB",
                     f"{est / budget * 100:5.1f}%", g, c,
                     "x".join(map(str, grid)), str(na)))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    for i, r in enumerate(rows):
        print("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            print("  ".join("-" * w for w in widths))
    print(f"budget: {budget / 2**20:.2f} MiB usable "
          f"(PT_VMEM_BUDGET_MB={os.environ.get('PT_VMEM_BUDGET_MB', '16')}"
          f" minus reserve), double-buffer factor {km.DOUBLE_BUFFER}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="ptgeom",
        description="static VMEM/tiling/aliasing verification of every "
                    "registered Pallas launch")
    ap.add_argument("--geoms", default=os.environ.get("PTGEOM_GEOMS"),
                    help="comma-set of ladder geometries "
                         "(tiny,350m,r06); default: all")
    ap.add_argument("--kernels", default=None,
                    help="comma-set of kernel names to sweep "
                         "(default: every registered kernel)")
    ap.add_argument("--extra", action="append", default=[],
                    help="extra registry module (a .py file defining "
                         "ptgeom_cases()); repeatable")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON (default tools/"
                         "ptgeom_baseline.json)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="record the current findings as the baseline")
    ap.add_argument("--error-on-new", action="store_true",
                    help="exit 1 on non-baselined findings (default)")
    ap.add_argument("--no-error", action="store_true",
                    help="report only; always exit 0")
    ap.add_argument("--stats", action="store_true",
                    help="print findings-per-rule totals")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids (e.g. PT006,PT009)")
    ap.add_argument("--no-table", action="store_true",
                    help="skip the per-kernel VMEM/tiling table")
    ap.add_argument("--format", choices=("text", "json"),
                    default="text")
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import (baseline, engine, kernelmodel,
                                     rules_tpu)

    geoms = None
    if args.geoms:
        geoms = tuple(g.strip() for g in args.geoms.split(",")
                      if g.strip())
        unknown = set(geoms) - set(kernelmodel.LADDER)
        if unknown:
            print(f"ptgeom: unknown geometries {sorted(unknown)} "
                  f"(have {sorted(kernelmodel.LADDER)})",
                  file=sys.stderr)
            return 2
    kernels = None
    if args.kernels:
        kernels = {k.strip() for k in args.kernels.split(",")
                   if k.strip()}
    extra_modules = [_load_extra(p) for p in args.extra]

    cases = kernelmodel.iter_cases(kernels, geoms, extra_modules)
    if not cases:
        print("ptgeom: no cases matched the filters", file=sys.stderr)
        return 2
    specs, errors = kernelmodel.sweep(cases, root=ROOT)
    for case, err in errors:
        print(f"ptgeom: harvest failed for {case.kernel} "
              f"[{case.geometry}/{case.config}]: {err}",
              file=sys.stderr)

    project = engine.load_project(
        sorted({s.abspath for s in specs}), root=ROOT)
    project.geom_specs = specs
    rules = rules_tpu.geom_rules()
    if args.rules:
        keep = {r.strip().upper() for r in args.rules.split(",")}
        rules = [r for r in rules if r.id in keep]
        if not rules:
            print(f"ptgeom: no such rules {sorted(keep)}",
                  file=sys.stderr)
            return 2
    findings = engine.run(project, rules)

    if args.write_baseline:
        if errors:
            print("ptgeom: refusing to write a baseline from a sweep "
                  "with harvest errors", file=sys.stderr)
            return 2
        baseline.write(args.baseline, findings)
        print(f"ptgeom: wrote {len(findings)} finding(s) to "
              f"{os.path.relpath(args.baseline, ROOT)}")
        return 0

    known_map = baseline.load(args.baseline)
    new, known = baseline.partition(findings, known_map)

    if args.format == "json":
        print(json.dumps(
            {"new": [vars(f) for f in new],
             "baselined": [vars(f) for f in known],
             "specs": [
                 {"name": s.name(), "site": f"{s.path}:{s.line}",
                  "vmem_bytes": kernelmodel.vmem_estimate(s)}
                 for s in specs]}, indent=2))
    else:
        if not args.no_table:
            _table(specs, kernelmodel)
        for f in new:
            print(f.format())
        if known:
            print(f"ptgeom: {len(known)} baselined finding(s) "
                  f"suppressed (see "
                  f"{os.path.relpath(args.baseline, ROOT)})")

    if args.stats:
        per_rule = {}
        for f in findings:
            per_rule[f.rule] = per_rule.get(f.rule, 0) + 1
        print("ptgeom stats (baselined included):")
        for rule in sorted(set(list(per_rule) +
                               [r.id for r in rules])):
            print(f"  {rule}: {per_rule.get(rule, 0)}")
        print(f"  specs: {len(specs)}  total: {len(findings)}  "
              f"new: {len(new)}  baselined: {len(known)}")

    if new:
        print(f"ptgeom: {len(new)} new finding(s)", file=sys.stderr)
        return 0 if args.no_error else 1
    if errors and not args.no_error:
        # an unharvestable case means that geometry was NOT verified —
        # a green exit would pass CI on exactly the kernels whose
        # tracing is broken
        print(f"ptgeom: {len(errors)} case(s) could not be harvested",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
