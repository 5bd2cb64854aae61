"""Trace-time Pallas kernel-geometry harvester (ISSUE 20).

``harvest`` intercepts ``pl.pallas_call`` while a kernel wrapper runs
under ``jax.eval_shape`` and records one :class:`KernelSpec` per launch
site: block shapes + dtypes from the in/out BlockSpecs, ``ANY``/SMEM
memory spaces, the grid, scalar-prefetch operand count, ``pltpu.VMEM``
scratch shapes, and the ``input_output_aliases`` pairs. Nothing
executes — ``eval_shape`` only abstract-evaluates, so the sweep runs on
a CPU-only CI shard in seconds, for geometries (r06-scale pools) whose
buffers could never be allocated on the host.

The module itself imports NOTHING outside the stdlib at module level:
``rules_tpu`` (and through it ptlint's jax-free bootstrap) can import
the spec model and the VMEM arithmetic without jax. Everything that
needs jax — the interception shim, the geometry registry sweep — pulls
it in lazily.

Geometry registry: each kernel module under ``ops/pallas/`` exposes a
``ptgeom_cases()`` hook returning :class:`GeomCase` rows — the bench
model ladder (tiny → r06-scale, :data:`LADDER`) crossed with that
kernel's autotune candidate space. ``tools/ptgeom.py`` sweeps them and
drives the PT006–PT009 rules in ``rules_tpu``.
"""

import contextlib
import dataclasses
import functools
import importlib
import inspect
import os
import sys
from typing import (Any, Callable, Dict, List, Optional, Sequence,
                    Tuple)

__all__ = [
    "OperandSpec", "ScratchSpec", "KernelSpec", "GeomCase", "LADDER",
    "KERNEL_MODULES", "itemsize", "sublane", "sds", "harvest",
    "iter_cases", "sweep", "vmem_estimate", "vmem_budget_bytes",
    "budget_reason", "DOUBLE_BUFFER", "VMEM_RESERVE_BYTES",
]

# bytes per element by canonical dtype name — kept local so the rule
# layer never needs numpy/jax to price a block
_ITEMSIZE = {
    "bool": 1, "int8": 1, "uint8": 1, "float8_e4m3fn": 1,
    "float8_e5m2": 1, "float8_e4m3b11fnuz": 1,
    "int16": 2, "uint16": 2, "float16": 2, "bfloat16": 2,
    "int32": 4, "uint32": 4, "float32": 4,
    "int64": 8, "uint64": 8, "float64": 8, "complex64": 8,
}

# Mosaic pipelines blocked operands through VMEM double-buffered (fetch
# block i+1 while computing on block i) — each blocked operand costs
# TWO block-sized windows of residency
DOUBLE_BUFFER = 2

# VMEM the budget model holds back for the compiler's own spills /
# semaphores on a ~16 MiB core
VMEM_RESERVE_BYTES = 512 * 1024


def itemsize(dtype) -> int:
    """Bytes per element for a dtype given as name string, np.dtype,
    or scalar type — without importing numpy when the name is known."""
    name = getattr(dtype, "name", None)
    if name is None:
        name = getattr(dtype, "__name__", None) or str(dtype)
    name = name.strip()
    if name in _ITEMSIZE:
        return _ITEMSIZE[name]
    try:  # exotic dtypes: fall back to numpy if it is importable
        import numpy as _np
        return int(_np.dtype(dtype).itemsize)
    except Exception:
        return 4


def sublane(dtype) -> int:
    """Minimum second-minor tile multiple for a dtype on TPU:
    (8, 128) f32, (16, 128) bf16/f16, (32, 128) int8/fp8."""
    return {1: 32, 2: 16}.get(itemsize(dtype), 8)


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


@dataclasses.dataclass
class OperandSpec:
    """One blocked (or whole-array) operand of a pallas launch."""

    role: str                      # "in" | "out"
    index: int                     # global operand index (counts prefetch)
    shape: Tuple[int, ...]
    dtype: str
    block: Optional[Tuple[int, ...]]   # None = whole array
    space: str                     # "vmem" | "any" | "smem" | "sem"
    # grid dims the index map depends on; None = data-dependent map
    # (e.g. reads a scalar-prefetch ref) that static probing cannot see
    deps: Optional[Tuple[int, ...]] = None
    # probed map outputs {grid_point: block_index} for alias comparison
    probes: Dict[Tuple[int, ...], Tuple[int, ...]] = \
        dataclasses.field(default_factory=dict)
    map_id: Optional[int] = None   # id() of the index_map callable

    def block_shape(self) -> Tuple[int, ...]:
        return self.block if self.block is not None else self.shape

    def block_bytes(self) -> int:
        return _prod(self.block_shape()) * itemsize(self.dtype)


@dataclasses.dataclass
class ScratchSpec:
    shape: Tuple[int, ...]
    dtype: str
    space: str = "vmem"

    def nbytes(self) -> int:
        return _prod(self.shape) * itemsize(self.dtype)


@dataclasses.dataclass
class KernelSpec:
    """Everything PT006–PT009 need about one pallas launch site."""

    body: str                      # kernel function name
    path: str                      # repo-relative launch site (posix)
    abspath: str
    line: int
    grid: Tuple[int, ...]
    num_scalar_prefetch: int
    inputs: List[OperandSpec]
    outputs: List[OperandSpec]
    scratch: List[ScratchSpec]
    aliases: Dict[int, int]        # global input index -> output index
    kernel: str = ""               # registry family (GeomCase.kernel)
    geometry: str = ""
    config: str = ""

    def name(self) -> str:
        return self.kernel or self.body


def vmem_budget_bytes() -> int:
    """PT006 budget: ``PT_VMEM_BUDGET_MB`` (default 16, the per-core
    VMEM size) minus a fixed compiler reserve."""
    try:
        mb = float(os.environ.get("PT_VMEM_BUDGET_MB", "16") or "16")
    except ValueError:
        mb = 16.0
    return max(0, int(mb * (1 << 20)) - VMEM_RESERVE_BYTES)


def vmem_estimate(spec: KernelSpec) -> int:
    """Static VMEM residency model: Σ block bytes × double-buffer
    factor over VMEM-pipelined in/out operands, plus VMEM scratch.
    ANY/SMEM operands don't occupy VMEM block windows; an aliased
    input shares its output's buffer and is not double-counted."""
    total = 0
    aliased_in = set(spec.aliases)
    for op in list(spec.inputs) + list(spec.outputs):
        if op.space != "vmem":
            continue
        if op.role == "in" and op.index in aliased_in:
            continue
        factor = DOUBLE_BUFFER if (op.block is not None and spec.grid) \
            else 1
        total += factor * op.block_bytes()
    for sc in spec.scratch:
        if sc.space == "vmem":
            total += sc.nbytes()
    return total


# ---------------------------------------------------------------------------
# interception shim
# ---------------------------------------------------------------------------

def _launch_site() -> Tuple[str, int]:
    """First stack frame outside this module and outside jax: the
    kernel wrapper's ``pl.pallas_call`` expression."""
    here = os.path.abspath(__file__)
    f = sys._getframe(1)
    while f is not None:
        fn = f.f_code.co_filename
        posix = os.path.abspath(fn).replace(os.sep, "/")
        if (posix != here.replace(os.sep, "/")
                and "/jax/" not in posix and "/jax_" not in posix
                and not fn.startswith("<")):
            return os.path.abspath(fn), f.f_lineno
        f = f.f_back
    return here, 0


def _space_of(ms) -> str:
    s = str(ms).lower() if ms is not None else ""
    if "smem" in s:
        return "smem"
    if "any" in s:
        return "any"
    if "sem" in s:
        return "sem"
    return "vmem"


def _dtype_name(dt) -> str:
    name = getattr(dt, "name", None)
    if name:
        return str(name)
    name = getattr(dt, "__name__", None)
    if name:
        return str(name)
    return str(dt)


def _analyze_map(imap, grid):
    """Probe an index map at a few grid points: which grid dims does it
    depend on, and what block does it pick there? Returns
    ``(None, {})`` for data-dependent maps (scalar-prefetch reads)."""
    if imap is None or not grid:
        return (), {}
    extra = 0
    try:
        params = list(inspect.signature(imap).parameters.values())
        if not any(p.kind == p.VAR_POSITIONAL for p in params):
            extra = max(0, len(params) - len(grid))
    except (TypeError, ValueError):
        pass

    def ev(pt):
        out = imap(*pt, *([None] * extra))
        return tuple(int(v) for v in out)

    base = tuple(0 for _ in grid)
    try:
        b0 = ev(base)
    except Exception:
        return None, {}
    probes = {base: b0}
    deps = []
    try:
        for d, n in enumerate(grid):
            changed = False
            for val in sorted({1, 2, int(n) - 1}):
                if not 0 < val < int(n):
                    continue
                pt = base[:d] + (val,) + base[d + 1:]
                o = ev(pt)
                probes[pt] = o
                if o != b0:
                    changed = True
            if changed:
                deps.append(d)
    except Exception:
        return None, {}
    return tuple(deps), probes


def _operand_spec(role, index, aval, bspec, grid) -> OperandSpec:
    block = getattr(bspec, "block_shape", None) if bspec is not None \
        else None
    imap = getattr(bspec, "index_map", None) if bspec is not None \
        else None
    space = _space_of(getattr(bspec, "memory_space", None)
                      if bspec is not None else None)
    if block is None and space == "vmem" and bspec is not None \
            and getattr(bspec, "memory_space", None) is None:
        # pl.BlockSpec() with neither block nor space: whole array
        space = "any"
    deps, probes = _analyze_map(imap, grid)
    return OperandSpec(
        role=role, index=index,
        shape=tuple(int(s) for s in aval.shape),
        dtype=_dtype_name(aval.dtype),
        block=None if block is None else tuple(int(b) for b in block),
        space=space, deps=deps, probes=probes,
        map_id=None if imap is None else id(imap))


def _build_spec(kernel_fn, call_kw, operands, site) -> KernelSpec:
    kfn = kernel_fn
    while isinstance(kfn, functools.partial):
        kfn = kfn.func
    body = getattr(kfn, "__name__", str(kfn))

    gs = call_kw.get("grid_spec")
    if gs is not None:
        grid = tuple(int(g) for g in (gs.grid or ()))
        in_specs = list(gs.in_specs or ())
        out_specs = gs.out_specs
        nsp = int(getattr(gs, "num_scalar_prefetch", 0) or 0)
        scratch = list(getattr(gs, "scratch_shapes", ()) or ())
    else:
        grid = call_kw.get("grid", ())
        grid = (grid,) if isinstance(grid, int) else \
            tuple(int(g) for g in (grid or ()))
        in_specs = list(call_kw.get("in_specs") or ())
        out_specs = call_kw.get("out_specs")
        nsp = 0
        scratch = list(call_kw.get("scratch_shapes") or ())
    if out_specs is None:
        out_specs = []
    elif not isinstance(out_specs, (list, tuple)):
        out_specs = [out_specs]
    out_shape = call_kw.get("out_shape")
    if out_shape is None:
        out_shape = []
    elif not isinstance(out_shape, (list, tuple)):
        out_shape = [out_shape]

    tensor_ops = list(operands)[nsp:]
    inputs = [
        _operand_spec("in", nsp + i, aval, bspec, grid)
        for i, (aval, bspec) in enumerate(zip(tensor_ops, in_specs))]
    outputs = [
        _operand_spec("out", i, sd, bspec, grid)
        for i, (sd, bspec) in enumerate(zip(out_shape, out_specs))]
    scratch_specs = [
        ScratchSpec(shape=tuple(int(s) for s in
                                getattr(sc, "shape", ()) or ()),
                    dtype=_dtype_name(getattr(sc, "dtype", "float32")),
                    space=_space_of(getattr(sc, "memory_space", None)))
        for sc in scratch]
    aliases = dict(call_kw.get("input_output_aliases") or {})

    abspath, line = site
    return KernelSpec(
        body=body, path="", abspath=abspath, line=line, grid=grid,
        num_scalar_prefetch=nsp, inputs=inputs, outputs=outputs,
        scratch=scratch_specs,
        aliases={int(k): int(v) for k, v in aliases.items()})


@contextlib.contextmanager
def intercept_pallas(records: List[KernelSpec]):
    """Patch ``pl.pallas_call`` to record a KernelSpec per launch while
    delegating to the real implementation (trace semantics unchanged)."""
    from jax.experimental import pallas as pl
    orig = pl.pallas_call

    def shim(kernel, *call_args, **call_kw):
        inner = orig(kernel, *call_args, **call_kw)
        site = _launch_site()

        def traced(*operands):
            records.append(_build_spec(kernel, call_kw, operands, site))
            return inner(*operands)
        return traced

    pl.pallas_call = shim
    try:
        yield records
    finally:
        pl.pallas_call = orig


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def harvest(run: Callable[[], Any],
            root: Optional[str] = None) -> List[KernelSpec]:
    """Run ``run()`` (which should drive kernel wrappers under
    ``jax.eval_shape``) with interception on; return the harvested
    specs with ``path`` made repo-relative."""
    root = root or repo_root()
    records: List[KernelSpec] = []
    with intercept_pallas(records):
        run()
    for spec in records:
        spec.path = os.path.relpath(spec.abspath, root).replace(
            os.sep, "/")
    return records


# ---------------------------------------------------------------------------
# geometry registry
# ---------------------------------------------------------------------------

# the bench model ladder (tools/profile_decode.py PD_SIZE, bench.py
# trials): tiny smoke geometry, GPT-3 350M, and the r06 recapture
# flagship (gpt3_1p3b)
LADDER: Dict[str, Dict[str, Any]] = {
    "tiny": dict(dm=64, layers=2, heads=2, kv_heads=2, vocab=256,
                 seq=64, page=128, dtype="float32"),
    "350m": dict(dm=1024, layers=24, heads=16, kv_heads=16,
                 vocab=50304, seq=1024, page=128, dtype="bfloat16"),
    "r06": dict(dm=2048, layers=24, heads=16, kv_heads=16,
                vocab=50304, seq=2048, page=128, dtype="bfloat16"),
}

KERNEL_MODULES = (
    "flash_attention", "decode_attention", "paged_attention",
    "fused_ce", "layer_norm", "quant_matmul")


@dataclasses.dataclass
class GeomCase:
    """One registry row: drive ``run()`` (under eval_shape) and label
    the harvested specs with (kernel, geometry, config)."""

    kernel: str
    geometry: str
    config: str
    run: Callable[[], Any]


def sds(shape, dtype):
    """jax.ShapeDtypeStruct from a shape tuple + dtype name string."""
    import jax
    import jax.numpy as jnp
    if isinstance(dtype, str):
        dtype = getattr(jnp, dtype)
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def iter_cases(kernels: Optional[Sequence[str]] = None,
               geoms: Optional[Sequence[str]] = None,
               extra_modules: Sequence[Any] = ()) -> List[GeomCase]:
    """Collect every ``ptgeom_cases()`` row from the kernel modules
    (plus any extra modules), filtered by kernel family / geometry."""
    cases: List[GeomCase] = []
    for name in KERNEL_MODULES:
        mod = importlib.import_module(f"paddle_tpu.ops.pallas.{name}")
        cases.extend(mod.ptgeom_cases())
    for mod in extra_modules:
        cases.extend(mod.ptgeom_cases())
    if kernels:
        keep = {k.strip() for k in kernels if k and k.strip()}
        cases = [c for c in cases if c.kernel in keep]
    if geoms:
        keepg = {g.strip() for g in geoms if g and g.strip()}
        cases = [c for c in cases if c.geometry in keepg]
    return cases


def sweep(cases: Sequence[GeomCase], root: Optional[str] = None):
    """Harvest every case. Returns ``(specs, errors)`` where errors is
    ``[(case, exception), ...]`` — a failed harvest means the geometry
    was NOT checked, so callers treat it like a parse error."""
    specs: List[KernelSpec] = []
    errors: List[Tuple[GeomCase, Exception]] = []
    for case in cases:
        try:
            got = harvest(case.run, root=root)
        except Exception as e:  # the case itself is broken
            errors.append((case, e))
            continue
        for spec in got:
            spec.kernel = case.kernel
            spec.geometry = case.geometry
            spec.config = case.config
        specs.extend(got)
    return specs, errors


def budget_reason(run: Callable[[], Any],
                  budget: Optional[int] = None) -> Optional[str]:
    """Autotune guard (PT006): dry-run ``run`` under interception and
    return a refusal reason if any harvested launch exceeds the VMEM
    budget — ``autotune.tune`` skips such candidates without spending
    chip time on them. Returns None when everything fits."""
    budget = vmem_budget_bytes() if budget is None else budget
    worst = None
    for spec in harvest(run):
        est = vmem_estimate(spec)
        if est > budget and (worst is None or est > worst[1]):
            worst = (spec, est)
    if worst is None:
        return None
    spec, est = worst
    return (f"{spec.name()}: estimated VMEM {est / (1 << 20):.2f} MiB "
            f"exceeds budget {budget / (1 << 20):.2f} MiB "
            f"({est / max(budget, 1):.1f}x) [PT006]")
