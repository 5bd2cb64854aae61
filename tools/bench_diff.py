#!/usr/bin/env python
"""bench_diff — machine-checked BENCH/MULTICHIP snapshot comparison
(ISSUE 15 regression sentinel).

    python tools/bench_diff.py BASELINE.json NEW.json [--rtol 0.10]
    python tools/bench_diff.py --selftest SNAPSHOT.json

Until now every recapture verdict ("within ~1.5x of contiguous?", "did
the fused kernel help?") was an eyeball diff of two JSON blobs; r05's
RESOURCE_EXHAUSTED silently dropped the bert/resnet/ppyoloe rows and
nothing flagged it. This tool compares two snapshots row by row:

- **direction-aware**: tok/s-like rows regress DOWN, ms/latency-like
  rows regress UP; config echoes (batch, seq, dispatch counts, ...)
  are informational and never fail the diff.
- **noise-aware**: per-row relative tolerance — a global ``--rtol``
  floor (default 10%) widened per row family by the built-in noise
  table (serving p99 tails swing harder than steady-state tok/s).
- **missing rows fail**: a numeric baseline row that vanished (or came
  back as ``<row>_error``) is a regression — exactly the r05 failure
  mode. New rows are reported, never failed.
- **schema-checked**: mismatched headline metrics or provenance schema
  versions exit 2 (the diff would be meaningless), not 1.
- prints the **paged-vs-contiguous ratio** against the ROADMAP item 1
  flip criterion (paged within 1.5x of contiguous) whenever both rows
  are present in the NEW snapshot.

Exit status: 0 clean (improvements/new rows included), 1 regression(s)
— each named —, 2 schema mismatch or unreadable input. ``--selftest``
proves the sentinel alive: self-diff must be clean AND a synthetic 20%
tok/s regression must be caught by name (wired as ``tools/ci.sh
benchdiff`` in the default gate).

Accepts both snapshot shapes: the driver wrapper ``{"parsed": {...}}``
and bench.py's raw result line ``{"metric": ...,
"extra": {...}}``.
"""

import argparse
import copy
import json
import sys

# (substring, rtol) — first match wins; rows matching no entry use the
# --rtol floor. Tails and churn measurements are intrinsically noisier
# than steady-state throughput (PR 9/14 smoke de-flaking history).
NOISE_TABLE = (
    ("p99", 0.25),
    ("p50", 0.20),
    ("churn", 0.25),
    ("goodput", 0.20),
    ("loss_delta", None),   # parity deltas compare vs thresholds, not
    ("_frac", 0.25),        # each other; fractions swing with load
)

# direction classification: +1 = higher is better, -1 = lower is
# better, 0 = informational (config echo / identity — never a failure).
# _INFO wins first: it exists only for rows a generic fragment below
# would otherwise misclassify (autotune sweep timings carry _ms).
_INFO = ("schema", "vs_baseline", "provenance", "skipped",
         "loss_delta", "autotune", "cache_hit",
         "scan_layers", "captured_unix", "republished")
_HIGHER = ("tokens_per_sec", "tok_s", "goodput", "mfu", "hw_util",
           "tokens_per_step", "agreement", "cosine", "hit_rate",
           "hit_tokens", "roofline_frac", "vs_roofline",
           "overlap_frac", "compression_ratio", "wire_ratio",
           "completed", "ips")
_LOWER = ("_ms", "ttft", "tpot", "latency", "_tax_frac", "exposed_s",
          "peak_mb", "rejects", "evictions", "spawn_timeouts",
          "host_gap", "recovery_s", "overhead_frac")
# checked BEFORE _HIGHER: rows whose name embeds a higher-is-better
# fragment but measure a cost (the drain bench's goodput_dip_frac
# contains "goodput" yet a bigger dip is a worse drain; the kernel
# launch accounting — launches_per_token / launches_per_step —
# regresses UP, ISSUE 19)
_LOWER_FIRST = ("goodput_dip", "fallbacks", "migrate_failed",
                "launches_per_")


def direction(row: str) -> int:
    low = row.lower()
    for frag in _INFO:
        if frag in low:
            return 0
    for frag in _LOWER_FIRST:
        if frag in low:
            return -1
    for frag in _HIGHER:
        if frag in low:
            return 1
    for frag in _LOWER:
        if frag in low:
            return -1
    return 0   # unclassified: report drift, never fail on it


def row_rtol(row: str, floor: float) -> float:
    low = row.lower()
    for frag, tol in NOISE_TABLE:
        if frag in low:
            return floor if tol is None else max(floor, tol)
    return floor


def load_bench(path: str) -> dict:
    """The bench result dict from either snapshot shape. Raises
    ValueError on files that hold neither."""
    with open(path, "r", encoding="utf-8") as fh:
        doc = json.load(fh)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if not isinstance(doc, dict) or "metric" not in doc:
        raise ValueError(f"{path}: neither a driver snapshot "
                         f"({{'parsed': ...}}) nor a bench result line "
                         f"({{'metric': ...}})")
    return doc


def flatten_rows(result: dict) -> dict:
    """``{row_name: value}`` over the headline metric + extra, nested
    dicts dotted (``flash_autotune.blocks.0``). Numeric leaves become
    rows; string leaves keep only the ``*_error`` / ``*_skipped``
    markers (they testify a row DIED — the r05 signature)."""
    rows = {}
    if isinstance(result.get("value"), (int, float)):
        rows[str(result.get("metric", "metric"))] = float(result["value"])

    def walk(prefix, v):
        if isinstance(v, bool):
            rows[prefix] = float(v)
        elif isinstance(v, (int, float)):
            rows[prefix] = float(v)
        elif isinstance(v, dict):
            for k, sub in v.items():
                walk(f"{prefix}.{k}" if prefix else str(k), sub)
        elif isinstance(v, (list, tuple)):
            for i, sub in enumerate(v):
                walk(f"{prefix}.{i}", sub)
        elif isinstance(v, str) and (prefix.endswith("_error")
                                     or prefix.endswith("_skipped")):
            rows[prefix] = v

    walk("", {k: v for k, v in result.get("extra", {}).items()
              if k != "provenance"})
    return rows


def schema_check(base: dict, new: dict):
    """None when comparable, else the human reason they are not."""
    if base.get("metric") != new.get("metric"):
        return (f"headline metric mismatch: {base.get('metric')!r} vs "
                f"{new.get('metric')!r}")
    bs = (base.get("provenance") or base.get("extra", {})
          .get("provenance") or {}).get("schema_version")
    ns = (new.get("provenance") or new.get("extra", {})
          .get("provenance") or {}).get("schema_version")
    if bs is not None and ns is not None and bs != ns:
        return f"provenance schema_version mismatch: {bs} vs {ns}"
    if base.get("unit") and new.get("unit") \
            and base["unit"] != new["unit"]:
        return (f"headline unit mismatch: {base['unit']!r} vs "
                f"{new['unit']!r}")
    return None


def _death_marker(row: str, nrows: dict):
    """The ``<section>_error`` / ``<section>_skipped`` string covering a
    vanished ``row``, if any: bench.py marks a dead SECTION (e.g.
    ``decode_engine_error``) while the rows it killed carry longer
    names (``decode_engine_tokens_per_sec``) — so match markers whose
    stem prefixes the row, not the reverse."""
    for r, v in nrows.items():
        if not isinstance(v, str):
            continue
        stem = r.rsplit("_", 1)[0]   # strip _error / _skipped
        if row.startswith(stem):
            return v
    return None


def compare(base: dict, new: dict, rtol: float = 0.10,
            atol: float = 1e-6) -> dict:
    """Row-by-row verdicts: ``regressions`` / ``improvements`` /
    ``within_noise`` / ``missing`` / ``added`` / ``info_drift``, each a
    list of (row, detail) tuples. ``atol`` floors the comparison for
    (near-)zero baselines: an exactly-0.0 row (overlap's pinned
    exposed_s) drifting by micro-units must not read as an infinite
    relative regression."""
    brows, nrows = flatten_rows(base), flatten_rows(new)
    out = {k: [] for k in ("regressions", "improvements",
                           "within_noise", "missing", "added",
                           "info_drift")}
    for row in sorted(brows):
        bv = brows[row]
        if isinstance(bv, str):   # baseline row was already dead
            continue
        d = direction(row)
        if row not in nrows:
            err = _death_marker(row, nrows)
            if d == 0:
                out["missing"].append((row, "informational row gone"))
            else:
                detail = f"row vanished (baseline {bv:g})"
                if isinstance(err, str):
                    detail = f"row died: {err[:80]}"
                out["regressions"].append((row, detail))
            continue
        nv = nrows[row]
        if isinstance(nv, str):
            out["regressions"].append((row, f"row died: {nv[:80]}"))
            continue
        if abs(nv - bv) <= atol:
            rel = 0.0   # absolute floor: 0.0 -> 1e-7 is not a signal
        elif bv == 0:
            rel = (1.0 if nv > 0 else -1.0) * float("inf")
        else:
            rel = (nv - bv) / abs(bv)
        tol = row_rtol(row, rtol)
        detail = f"{bv:g} -> {nv:g} ({rel:+.1%}, tol {tol:.0%})"
        if d == 0:
            if rel:
                out["info_drift"].append((row, detail))
            continue
        worse = -rel * d
        if worse > tol:
            out["regressions"].append((row, detail))
        elif -worse > tol:
            out["improvements"].append((row, detail))
        else:
            out["within_noise"].append((row, detail))
    for row in sorted(set(nrows) - set(brows)):
        if isinstance(nrows[row], str):
            continue
        out["added"].append((row, f"{nrows[row]:g}"))
    return out


def paged_flip_report(new: dict, criterion: float = 1.5):
    """ROADMAP item 1: contiguous/paged tok/s ratio vs the flip
    criterion. Returns the printed lines (empty when rows absent)."""
    rows = flatten_rows(new)
    contig = rows.get("decode_engine_tokens_per_sec")
    paged = rows.get("decode_engine_paged_tokens_per_sec")
    if not isinstance(contig, float) or not isinstance(paged, float) \
            or paged <= 0:
        return []
    ratio = contig / paged
    verdict = ("PASS — flip paged to the default serving path"
               if ratio <= criterion else
               f"not yet — paged must close {ratio / criterion:.2f}x")
    return [f"paged flip criterion: contiguous {contig:g} tok/s / "
            f"paged {paged:g} tok/s = {ratio:.2f}x "
            f"(criterion <= {criterion}x): {verdict}"]


def _print_report(verdicts, show_all=False):
    order = ("regressions", "missing", "improvements", "added",
             "info_drift", "within_noise")
    for kind in order:
        items = verdicts[kind]
        if not items or (not show_all and kind == "within_noise"):
            if kind == "within_noise" and items:
                print(f"within noise: {len(items)} row(s)")
            continue
        print(f"{kind.replace('_', ' ')} ({len(items)}):")
        for row, detail in items:
            print(f"  {row}: {detail}")


def selftest(path: str, rtol: float) -> int:
    """The sentinel's own aliveness check: (a) self-diff is clean, (b)
    a synthetic 20% regression on every tok/s row is caught by name."""
    base = load_bench(path)
    clean = compare(base, base, rtol)
    if clean["regressions"] or clean["missing"]:
        print("selftest FAIL: self-diff not clean", file=sys.stderr)
        _print_report(clean)
        return 1
    wounded = copy.deepcopy(base)
    hit = []

    def maim(d, prefix=""):
        for k, v in list(d.items()):
            name = f"{prefix}.{k}" if prefix else k
            if isinstance(v, dict):
                maim(v, name)
            elif isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and direction(k) == 1 and "tokens_per_sec" in k:
                d[k] = v * 0.8
                hit.append(name)

    maim(wounded.get("extra", {}))
    if isinstance(wounded.get("value"), (int, float)) \
            and "tokens_per_sec" in str(wounded.get("metric", "")):
        wounded["value"] = wounded["value"] * 0.8
        hit.append(str(wounded["metric"]))
    if not hit:
        print(f"selftest SKIP: {path} carries no tok/s rows to maim "
              f"(headline-only snapshot) — self-diff was clean")
        return 0
    v = compare(base, wounded, rtol)
    caught = {row for row, _ in v["regressions"]}
    missed = [h for h in hit if not any(h in c or c in h
                                        for c in caught)]
    if missed:
        print(f"selftest FAIL: 20% regression in {missed} not caught",
              file=sys.stderr)
        return 1
    print(f"selftest OK: self-diff clean; synthetic 20% tok/s "
          f"regression caught on {len(caught)} row(s) "
          f"(e.g. {sorted(caught)[0]})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff",
        description="machine-checked BENCH snapshot comparison")
    ap.add_argument("baseline", help="baseline snapshot JSON")
    ap.add_argument("new", nargs="?", default=None,
                    help="new snapshot JSON (omit with --selftest)")
    ap.add_argument("--rtol", type=float, default=0.10,
                    help="relative-tolerance floor per row "
                         "(default 0.10; noise table may widen)")
    ap.add_argument("--atol", type=float, default=1e-6,
                    help="absolute-drift floor: |new-base| at or below "
                         "this is within noise regardless of ratio "
                         "(protects exactly-zero baselines)")
    ap.add_argument("--flip-criterion", type=float, default=1.5,
                    help="paged-vs-contiguous flip threshold")
    ap.add_argument("--all", action="store_true",
                    help="print within-noise rows too")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable verdicts on stdout")
    ap.add_argument("--selftest", action="store_true",
                    help="self-diff + synthetic-regression aliveness "
                         "check on BASELINE")
    args = ap.parse_args(argv)

    try:
        base = load_bench(args.baseline)
        if args.selftest:
            return selftest(args.baseline, args.rtol)
        if args.new is None:
            ap.error("NEW snapshot required (or --selftest)")
        new = load_bench(args.new)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"bench_diff: {e}", file=sys.stderr)
        return 2

    reason = schema_check(base, new)
    if reason:
        print(f"bench_diff: snapshots not comparable: {reason}",
              file=sys.stderr)
        return 2

    verdicts = compare(base, new, args.rtol, args.atol)
    if args.json:
        print(json.dumps({k: [list(t) for t in v]
                          for k, v in verdicts.items()}, indent=1))
    else:
        _print_report(verdicts, show_all=args.all)
        for line in paged_flip_report(new, args.flip_criterion):
            print(line)
    n_reg = len(verdicts["regressions"])
    if n_reg:
        print(f"bench_diff: {n_reg} regression(s)", file=sys.stderr)
        return 1
    print(f"bench_diff: clean ({len(verdicts['within_noise'])} within "
          f"noise, {len(verdicts['improvements'])} improved, "
          f"{len(verdicts['added'])} new)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
