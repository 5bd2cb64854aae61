"""The comparison that decides ``correct``. Every number compared has a
limit of its own in ``limits/<cell>.json``, set from readings on the chip
(PERF.md gives them); a number with no limit there is not compared.

Training: what the program read over its first steps against what the
plain reference reads over the same steps.

- ``loss_gap_step<i>``: |program - reference| / |reference|.
- ``grad_norm_gap``: the worst leaf's gap between the norm of the first
  gradient as the program's optimizer got it and the reference's norm,
  against the reference's norm of that leaf or of the median leaf,
  whichever is larger (some gradients are all but zero).
- ``change_norm_gap``: the same for the norm of (parameters after the
  checked steps - parameters at the start). Leaves whose reference
  gradient is under a thousandth of the median leaf's are left out: under
  Adam they move by round-off alone (the key bias under softmax).

Serving: ``logit_gap`` is the widest gap, over every served token of the
sampled requests, by which that token's reference logit lies below the
reference's best at its position. ``unfinished`` counts sampled requests
that did not deliver exactly the tokens they were asked for.
"""

import statistics

NEGLIGIBLE_GRADIENT = 1e-3      # of the median leaf's gradient norm


def _worst_leaf_gap(program: dict, reference: dict, leaves) -> tuple:
    leaves = list(leaves)
    floor = statistics.median(reference[k] for k in leaves)
    worst, worst_leaf = 0.0, None
    for k in leaves:
        if k not in program:
            return float("inf"), k
        gap = abs(program[k] - reference[k]) / max(reference[k], floor, 1e-30)
        if not gap <= worst:          # also catches NaN
            worst, worst_leaf = gap, k
    return worst, worst_leaf


def train_numbers(program: dict, reference: dict) -> dict:
    """Every number of a training cell, with the leaf that set it."""
    out = {}
    for i, (a, b) in enumerate(zip(program["loss"], reference["loss"]), 1):
        out[f"loss_gap_step{i}"] = (abs(a - b) / abs(b), None)
    grads = reference["grad_norm"]
    out["grad_norm_gap"] = _worst_leaf_gap(program["grad_norm"], grads, grads)
    median_grad = statistics.median(grads.values())
    moved = [k for k in reference["change_norm"]
             if grads[k] >= NEGLIGIBLE_GRADIENT * median_grad]
    out["change_norm_gap"] = _worst_leaf_gap(
        program["change_norm"], reference["change_norm"], moved)
    return out


def _checks(numbers: dict, limits: dict) -> list:
    checks = []
    for name, limit in limits.items():
        if name.startswith("_"):
            continue
        value = numbers[name][0] if name in numbers else float("inf")
        if value != value:            # NaN never passes
            value = float("inf")
        checks.append((name, float(value), float(limit)))
    return checks


def compare_train(program: dict, reference: dict, limits: dict) -> list:
    return _checks(train_numbers(program, reference), limits)


def serve_numbers(samples: list) -> dict:
    """``samples``: one dict per sampled request with ``gaps`` (the
    reference's best logit minus the served token's, per served token) and
    ``complete`` (it delivered exactly what was asked)."""
    gaps = [g for s in samples for g in s["gaps"]]
    return {
        "logit_gap": (max(gaps) if gaps else float("inf"), None),
        "unfinished": (float(sum(1 for s in samples if not s["complete"])),
                       None),
    }


def compare_serve(samples: list, limits: dict) -> list:
    return _checks(serve_numbers(samples), limits)
