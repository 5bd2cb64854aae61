"""PT006–PT009 — static TPU kernel-geometry contracts (ISSUE 20).

These rules consume the :class:`~paddle_tpu.analysis.kernelmodel.
KernelSpec` rows that ``tools/ptgeom.py`` harvests (attached to the
project as ``project.geom_specs``) and ride the existing ptlint
engine: inline ``# ptlint: disable=PT00x -- rationale`` suppressions at
the ``pl.pallas_call`` launch site, content-anchored baseline
fingerprints, the same CLI exit-code contract. With no harvested specs
(a plain ``ptlint`` run) every rule is a no-op, so the jax-free lint
gate is unchanged.

- **PT006** VMEM budget: Σ in/out block bytes × the double-buffer
  pipelining factor + VMEM scratch must fit ``PT_VMEM_BUDGET_MB``
  (default 16 MB) minus a compiler reserve. One finding per launch
  site naming the worst (config, geometry) pair.
- **PT007** tiling alignment: a CHOSEN tile (block dim strictly inside
  the array dim) must keep the trailing dim a multiple of 128 lanes
  and the second-minor a multiple of the dtype sublane (8 f32 /
  16 bf16 / 32 int8) — a misaligned block pads on chip, inflating
  both VMEM residency and HBM bytes, or is refused by the Pallas TPU
  lowering outright (a 1-row block of a many-row array is).
- **PT008** aliasing contracts: a whole-array ``ANY``-space pool that
  matches an output must be input_output_aliased (else the kernel pays
  a full HBM pool copy per launch), and an aliased pair whose block
  shapes or index maps diverge is a corruption hazard.
- **PT009** grid-cost sanity: per-grid-step HBM bytes implied by the
  block index maps vs the minimal traffic — flags a kernel whose
  blocking re-fetches an operand ≥2x per launch (the revisit window
  the pipeline could have held is smaller than the operand's reuse
  distance), with the analytic roofline cost from ``devprof`` when
  available.
"""

import types
from typing import Dict, Iterable, List, Tuple

from paddle_tpu.analysis import kernelmodel
from paddle_tpu.analysis.engine import Rule

_MIB = 1 << 20

# PT009 ignores re-reads whose EXTRA per-launch traffic is below this —
# re-streaming a few KiB of scales is noise, re-streaming weight slabs
# is the finding
PT009_MIN_EXTRA_BYTES = 1 * _MIB


def _node(line: int):
    return types.SimpleNamespace(lineno=line, col_offset=0)


def _site_groups(ctx, project):
    """Harvested specs for this file, grouped per (line, family)."""
    groups: Dict[Tuple[int, str], List] = {}
    for spec in getattr(project, "geom_specs", ()) or ():
        if spec.path != ctx.relpath:
            continue
        groups.setdefault((spec.line, spec.name()), []).append(spec)
    return sorted(groups.items())


class VmemBudgetRule(Rule):
    """PT006 — static VMEM residency vs PT_VMEM_BUDGET_MB."""

    def __init__(self):
        super().__init__(
            id="PT006", severity="error",
            description="pallas launch whose blocked operands + scratch "
                        "exceed the static VMEM budget")

    def check(self, ctx, project) -> Iterable:
        budget = kernelmodel.vmem_budget_bytes()
        for (line, name), specs in _site_groups(ctx, project):
            worst = max(specs, key=kernelmodel.vmem_estimate)
            est = kernelmodel.vmem_estimate(worst)
            if est <= budget:
                continue
            yield self.finding(
                ctx, _node(line),
                f"{name}: estimated VMEM {est / _MIB:.2f} MiB exceeds "
                f"budget {budget / _MIB:.2f} MiB "
                f"({est / max(budget, 1):.1f}x) — worst at geometry "
                f"'{worst.geometry}' config '{worst.config}' "
                f"(grid {worst.grid}; 2x-buffered blocks + scratch)",
                symbol=name)


class TilingAlignmentRule(Rule):
    """PT007 — chosen tiles must respect (sublane, 128-lane) multiples."""

    def __init__(self):
        super().__init__(
            id="PT007", severity="warning",
            description="blocked operand tiled off the (sublane, 128) "
                        "grid — the block silently pads on chip")

    def check(self, ctx, project) -> Iterable:
        for (line, name), specs in _site_groups(ctx, project):
            viols = []
            seen = set()
            for spec in specs:
                for op in list(spec.inputs) + list(spec.outputs):
                    if op.space != "vmem" or op.block is None:
                        continue
                    bs, shp = op.block, op.shape
                    checks = []
                    if bs and 0 < bs[-1] < shp[-1] and bs[-1] % 128:
                        checks.append((len(bs) - 1, bs[-1], 128,
                                       "lane"))
                    # a 1-row block of a many-row array is NOT exempt:
                    # the Pallas TPU lowering refuses it outright (PR 21:
                    # block (1, 2048) of an (L, 2048) array, "last two
                    # dimensions ... divisible by 8 and 128 ... or equal
                    # to the respective dimensions of the overall
                    # array"). Row-streaming rides a (1, 1, n) block of
                    # an (L, 1, n) array, whose last two dims ARE the
                    # array's.
                    if len(bs) >= 2 and 0 < bs[-2] < shp[-2]:
                        sub = kernelmodel.sublane(op.dtype)
                        if bs[-2] % sub:
                            checks.append((len(bs) - 2, bs[-2], sub,
                                           "sublane"))
                    for dim, got, want, kind in checks:
                        key = (op.role, op.index, dim, got)
                        if key in seen:
                            continue
                        seen.add(key)
                        viols.append(
                            f"{op.role}[{op.index}] block {bs} dim "
                            f"{dim} = {got} is not a multiple of "
                            f"{want} ({kind}, dtype {op.dtype}, "
                            f"geometry '{spec.geometry}' config "
                            f"'{spec.config}')")
            if viols:
                yield self.finding(
                    ctx, _node(line),
                    f"{name}: misaligned tile pads on chip — "
                    + "; ".join(viols[:3])
                    + (f" (+{len(viols) - 3} more)"
                       if len(viols) > 3 else ""),
                    symbol=name)


class AliasContractRule(Rule):
    """PT008 — ANY pools must alias; aliased pairs must agree."""

    def __init__(self):
        super().__init__(
            id="PT008", severity="error",
            description="in-place pool not input_output_aliased, or an "
                        "aliased pair with diverging geometry")

    def _spec_violations(self, spec) -> List[str]:
        out: List[str] = []
        aliased_in = set(spec.aliases)
        by_index = {op.index: op for op in spec.inputs}
        any_outs = [o for o in spec.outputs if o.space == "any"]
        alias_tgt = set(spec.aliases.values())
        for op in spec.inputs:
            if op.space != "any" or op.index in aliased_in:
                continue
            for o in any_outs:
                if o.index in alias_tgt:
                    continue
                if o.shape == op.shape and o.dtype == op.dtype:
                    out.append(
                        f"ANY-space pool in[{op.index}] "
                        f"{op.shape}:{op.dtype} matches out[{o.index}] "
                        f"but is not input_output_aliased — the launch "
                        f"pays a full HBM pool copy")
                    break
        for gi, oi in sorted(spec.aliases.items()):
            inp = by_index.get(gi)
            outp = spec.outputs[oi] if 0 <= oi < len(spec.outputs) \
                else None
            if inp is None or outp is None:
                out.append(f"alias {gi}->{oi} names a missing operand")
                continue
            if inp.shape != outp.shape or inp.dtype != outp.dtype:
                out.append(
                    f"alias {gi}->{oi} shape/dtype mismatch: "
                    f"{inp.shape}:{inp.dtype} vs "
                    f"{outp.shape}:{outp.dtype}")
                continue
            if inp.block != outp.block:
                out.append(
                    f"alias {gi}->{oi} block mismatch: {inp.block} vs "
                    f"{outp.block} — in-place writes land in the wrong "
                    f"window")
                continue
            if inp.map_id is not None and inp.map_id == outp.map_id:
                continue
            if inp.deps is None or outp.deps is None:
                continue  # data-dependent maps: cannot probe statically
            for pt, idx in inp.probes.items():
                oidx = outp.probes.get(pt)
                if oidx is not None and oidx != idx:
                    out.append(
                        f"alias {gi}->{oi} index maps diverge at grid "
                        f"{pt}: in->{idx} vs out->{oidx} — aliased "
                        f"write corrupts a block the input never "
                        f"presented")
                    break
        return out

    def check(self, ctx, project) -> Iterable:
        for (line, name), specs in _site_groups(ctx, project):
            msgs = []
            for spec in specs:
                for v in self._spec_violations(spec):
                    if v not in msgs:
                        msgs.append(v)
            if msgs:
                yield self.finding(
                    ctx, _node(line),
                    f"{name}: " + "; ".join(msgs[:3])
                    + (f" (+{len(msgs) - 3} more)"
                       if len(msgs) > 3 else ""),
                    symbol=name)


def _reread(spec, op):
    """(factor, fetches, distinct) — how many block fetches the
    row-major grid traversal implies vs the distinct blocks touched."""
    grid = spec.grid
    if not grid or op.block is None or op.deps is None:
        return None
    gp = 1
    for g in grid:
        gp *= int(g)
    deps = set(op.deps)
    distinct = 1
    for d in deps:
        distinct *= int(grid[d])
    run = 1
    for d in reversed(range(len(grid))):
        if d in deps:
            break
        run *= int(grid[d])
    fetches = gp // max(run, 1)
    return fetches / max(distinct, 1), fetches, distinct


def _roofline_suffix(extra_bytes: int) -> str:
    try:
        from paddle_tpu.observability import devprof
        secs = devprof.hbm_seconds(extra_bytes)
    except Exception:
        return ""
    if not secs:
        return ""
    return f" (~{secs * 1e6:.0f} us/launch at roofline HBM peak)"


class GridCostRule(Rule):
    """PT009 — blocking that re-reads an operand >=2x per launch."""

    def __init__(self):
        super().__init__(
            id="PT009", severity="warning",
            description="grid traversal re-fetches a blocked operand "
                        ">=2x per launch vs minimal HBM traffic")

    def check(self, ctx, project) -> Iterable:
        for (line, name), specs in _site_groups(ctx, project):
            worst = None
            for spec in specs:
                for op in spec.inputs:
                    if op.space != "vmem":
                        continue
                    rr = _reread(spec, op)
                    if rr is None:
                        continue
                    factor, fetches, distinct = rr
                    extra = (fetches - distinct) * op.block_bytes()
                    if factor < 2 or extra < PT009_MIN_EXTRA_BYTES:
                        continue
                    if worst is None or extra > worst[0]:
                        worst = (extra, factor, fetches, distinct, op,
                                 spec)
            if worst is None:
                continue
            extra, factor, fetches, distinct, op, spec = worst
            yield self.finding(
                ctx, _node(line),
                f"{name}: in[{op.index}] block {op.block_shape()} is "
                f"fetched {fetches}x per launch but only {distinct} "
                f"distinct blocks exist ({factor:.0f}x re-read, "
                f"+{extra / _MIB:.1f} MiB HBM over minimal at geometry "
                f"'{spec.geometry}' config '{spec.config}')"
                + _roofline_suffix(extra),
                symbol=name)


def geom_rules() -> List[Rule]:
    return [VmemBudgetRule(), TilingAlignmentRule(),
            AliasContractRule(), GridCostRule()]
