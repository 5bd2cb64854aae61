"""Device-time attribution (ISSUE 15 tentpole): where do the
nanoseconds go on-device?

The fleet plane (PR 13) answers *where a request goes*; this module
answers what the chip did with the time once the request got there.
Three meters, one ``prof/`` namespace:

- **Roofline capture** — :func:`capture_jit` AOT-lowers a jitted fn and
  pulls XLA's ``cost_analysis()`` (FLOPs, HBM bytes moved) plus
  ``memory_analysis()`` for THE program that runs (not a paper model of
  it). :func:`roofline_tokens_per_sec` combines the capture with the
  device peak specs (detected from the attached device, overridable via
  ``PT_PROF_PEAK_FLOPS`` / ``PT_PROF_PEAK_HBM_GBPS``) into an analytic
  tok/s bound, and :func:`record_roofline` turns a measured number into
  the ``prof/roofline_frac`` gauge. Both engines expose
  ``dispatch_cost()`` which captures their decode-dispatch jit at the
  current geometry.
- **Launch-tax meter** — :func:`launch_tax_s` calibrates the
  per-dispatch overhead once per process by timing a no-op jitted
  launch end to end (enqueue + tiny device→host readback: the exact
  shape of the engines' dispatch+harvest round). Multiplied by the
  PR 13 ``serve/dispatch_launches`` counters
  (:func:`launch_tax_fraction`), it prices the "one-pallas-launch-per-
  layer at short lengths" hypothesis (PAPERS: "LLM Inference
  Acceleration via Efficient Operation Fusion") as a printed fraction
  of token time instead of a suspicion. The number is an upper bound
  under pipelining (in-flight dispatches overlap their launch costs).
- **Step decomposition** — :func:`step_fractions` splits a serve/train
  window into device-busy / host-gap / dispatch-queue fractions using
  ``observability/comm.py``'s exact interval algebra over the already-
  recorded trace spans (``serve/dispatch`` = host enqueueing device
  work, ``serve/harvest`` = host blocked on device output; anything
  else is host gap). ``prof/host_bound`` flags a pipeline whose host
  gap exceeds the device-interaction time.

Everything records into the stats registry under ``prof/`` (catalogued
in docs/observability.md) so /statsz, /metricsz, and bench provenance
all see the same numbers.
"""

import os
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

from paddle_tpu.observability import comm

__all__ = ["CostCapture", "capture_jit", "peak_specs", "hbm_seconds",
           "roofline_tokens_per_sec", "record_roofline",
           "launch_tax_s", "pallas_launch_tax_s", "launch_tax_fraction",
           "step_fractions", "count_pallas_launches",
           "count_hlo_custom_calls"]


# ---------------------------------------------------------------------------
# device peak specs
# ---------------------------------------------------------------------------

def peak_specs(device=None) -> Tuple[float, float]:
    """``(peak_flops_per_s, peak_hbm_bytes_per_s)`` for ``device``
    (default: the first local device), from the cost model's public
    per-generation table. ``PT_PROF_PEAK_FLOPS`` (FLOP/s) and
    ``PT_PROF_PEAK_HBM_GBPS`` (GB/s) override detection — the knob for
    chips the table predates or deliberately derated rooflines."""
    env_f = os.environ.get("PT_PROF_PEAK_FLOPS")
    env_b = os.environ.get("PT_PROF_PEAK_HBM_GBPS")
    flops = bw = None
    if env_f:
        flops = float(env_f)
    if env_b:
        bw = float(env_b) * 1e9
    if flops is None or bw is None:
        from paddle_tpu.cost_model import _peak
        if device is None:
            import jax
            device = jax.devices()[0]
        det_f, det_b, _ = _peak(device)
        flops = det_f if flops is None else flops
        bw = det_b if bw is None else bw
    return flops, bw


def hbm_seconds(nbytes: float, device=None) -> float:
    """Analytic seconds to move ``nbytes`` through HBM at the device's
    peak bandwidth — the roofline price tag ptgeom's PT009 attaches to
    redundant refetch traffic. Raises when no device/override is
    available (callers guard; static analysis must stay device-free)."""
    _, bw = peak_specs(device)
    return float(nbytes) / bw


# ---------------------------------------------------------------------------
# roofline capture
# ---------------------------------------------------------------------------

@dataclass
class CostCapture:
    """One AOT-lowered program's cost profile: FLOPs and HBM bytes per
    call (XLA cost_analysis) plus the static memory footprint
    (memory_analysis, ``mem/compiled_*`` fields)."""
    name: str
    flops: float
    hbm_bytes: float
    memory: Dict[str, int] = field(default_factory=dict)

    def analytic_seconds(self, peaks: Tuple[float, float]) -> float:
        """Roofline seconds per call: max(compute time, HBM time)."""
        pf, pb = peaks
        return max(self.flops / pf, self.hbm_bytes / pb)


def capture_jit(jfn, *args, name: Optional[str] = None,
                record: bool = True, **kwargs) -> CostCapture:
    """AOT-lower ``jfn`` (a ``jax.jit`` callable) on ``args`` and pull
    its cost/memory analysis. Never executes the program — donated
    buffers stay live. Records ``prof/flops[/name]`` and
    ``prof/hbm_bytes[/name]`` gauges plus the ``mem/compiled_*``
    footprint (runtime.memory_analysis_gauges) unless ``record=False``.
    Compilation rides the jit/persistent cache, so a warmed engine pays
    only the (re)trace."""
    compiled = jfn.lower(*args, **kwargs).compile()
    data = compiled.cost_analysis()
    cap = CostCapture(name=name or getattr(jfn, "__name__", "jit"),
                      flops=float(data.get("flops", 0.0)),
                      hbm_bytes=float(data.get("bytes accessed", 0.0)))
    if record:
        from paddle_tpu import stats
        from paddle_tpu.observability import runtime
        sfx = f"/{name}" if name else ""
        stats.set_value(f"prof/flops{sfx}", cap.flops)
        stats.set_value(f"prof/hbm_bytes{sfx}", cap.hbm_bytes)
        cap.memory = runtime.memory_analysis_gauges(compiled, name)
    else:
        try:
            ma = compiled.memory_analysis()
            cap.memory = {"temp_size_in_bytes":
                          int(getattr(ma, "temp_size_in_bytes", 0))}
        except Exception:
            pass
    return cap


def count_pallas_launches(fn, *args, **kwargs) -> int:
    """Kernel launches per CALL of ``fn``, from its jaxpr: every
    ``pallas_call`` equation counts once (a multi-step grid is still
    ONE launch), weighted by the trip count of enclosing ``scan``s —
    so a chunked decode dispatch reports chunk × launches-per-step.
    Backend-independent (interpret-mode pallas_calls count the same),
    which is what lets the CPU suite hold a decode dispatch to its
    launches per layer. ``while`` bodies
    count once (trip count unknown — a lower bound); ``cond`` branches
    count at the worst case."""
    import jax

    def walk(jaxpr, mult):
        n = 0
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "pallas_call":
                n += mult
            elif prim == "scan":
                n += walk(eqn.params["jaxpr"].jaxpr,
                          mult * int(eqn.params["length"]))
            elif prim == "while":
                n += walk(eqn.params["cond_jaxpr"].jaxpr, mult)
                n += walk(eqn.params["body_jaxpr"].jaxpr, mult)
            elif prim == "cond":
                n += max((walk(b.jaxpr, mult)
                          for b in eqn.params["branches"]), default=0)
            else:
                for key in ("jaxpr", "call_jaxpr"):
                    sub = eqn.params.get(key)
                    if sub is not None:
                        n += walk(getattr(sub, "jaxpr", sub), mult)
        return n

    return walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr, 1)


def count_hlo_custom_calls(jfn, *args, **kwargs) -> Optional[int]:
    """Custom-call count from the AOT-COMPILED HLO of ``jfn`` (a
    ``jax.jit`` callable) — on TPU every pallas kernel lowers to one
    ``tpu_custom_call``, so this is launches-per-call as the runtime
    sees them. Interpret-mode pallas (CPU) lowers to plain HLO, so the
    count reads 0 there — pair with `count_pallas_launches` for a
    backend-independent number. None when lowering fails."""
    try:
        txt = jfn.lower(*args, **kwargs).compile().as_text()
    except Exception:
        return None
    return txt.count("custom-call")


def roofline_tokens_per_sec(cap: CostCapture, tokens_per_call: float,
                            device=None,
                            peaks: Optional[Tuple[float, float]] = None
                            ) -> float:
    """Analytic roofline tok/s for a captured dispatch emitting
    ``tokens_per_call`` tokens: tokens / max(flops/peak_flops,
    bytes/peak_bw). Returns 0.0 when the capture carries no cost data
    (a backend without cost_analysis) — callers treat 0 as "no
    roofline", never as a target."""
    if peaks is None:
        peaks = peak_specs(device)
    t = cap.analytic_seconds(peaks)
    if t <= 0.0 or tokens_per_call <= 0:
        return 0.0
    return tokens_per_call / t


def record_roofline(name: str, measured_tps: float,
                    analytic_tps: float) -> float:
    """Record ``prof/roofline_tps[/name]`` and ``prof/roofline_frac
    [/name]`` (measured/analytic; 0 when no analytic bound exists) and
    return the fraction."""
    from paddle_tpu import stats
    frac = measured_tps / analytic_tps if analytic_tps > 0 else 0.0
    sfx = f"/{name}" if name else ""
    stats.set_value(f"prof/roofline_tps{sfx}", analytic_tps)
    stats.set_value(f"prof/roofline_frac{sfx}", frac)
    return frac


# ---------------------------------------------------------------------------
# launch-tax meter
# ---------------------------------------------------------------------------

_launch_cache: Dict[str, float] = {}


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def launch_tax_s(force: bool = False) -> float:
    """Per-dispatch overhead of one no-op jitted launch, calibrated
    ONCE per process (``force=True`` recalibrates): median wall time of
    enqueue + scalar readback on an 8-element array — the same
    host↔device round the engines pay per dispatch+harvest, with zero
    device work inside. Iteration count via ``PT_PROF_LAUNCH_ITERS``
    (default 64; the median is robust to GC/scheduler outliers).
    Records the ``prof/launch_tax_s`` gauge."""
    if not force and "jit" in _launch_cache:
        return _launch_cache["jit"]
    import jax
    import jax.numpy as jnp
    iters = int(os.environ.get("PT_PROF_LAUNCH_ITERS", "64"))
    f = jax.jit(lambda v: v + 1)
    x = jnp.zeros((8,), jnp.int32)
    x = f(x)
    int(x[0])  # ptlint: disable=PT001 -- calibration IS the timed sync
    samples = []
    for _ in range(max(8, iters)):
        t0 = time.perf_counter()
        y = f(x)
        int(y[0])  # ptlint: disable=PT001 -- calibration IS the timed sync
        samples.append(time.perf_counter() - t0)
    tax = _median(samples)
    _launch_cache["jit"] = tax
    from paddle_tpu import stats
    stats.set_value("prof/launch_tax_s", tax)
    return tax


def pallas_launch_tax_s(force: bool = False) -> Optional[float]:
    """Per-dispatch overhead of one no-op Pallas kernel launch —
    the per-layer cost the fused paged path pays at short lengths.
    TPU-only: returns None elsewhere (interpret-mode Pallas on CPU
    times the interpreter, not a launch). Cached per process; records
    ``prof/launch_tax_pallas_s`` when measurable."""
    if not force and "pallas" in _launch_cache:
        return _launch_cache["pallas"]
    try:
        import jax
        if jax.default_backend() != "tpu":
            return None
        import jax.numpy as jnp
        from jax.experimental import pallas as pl

        def _noop(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        f = jax.jit(lambda v: pl.pallas_call(
            _noop, out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype))(v))
        x = jnp.zeros((8, 128), jnp.float32)
        x = f(x)
        float(x[0, 0])  # ptlint: disable=PT001 -- calibration sync
        iters = int(os.environ.get("PT_PROF_LAUNCH_ITERS", "64"))
        samples = []
        for _ in range(max(8, iters)):
            t0 = time.perf_counter()
            y = f(x)
            float(y[0, 0])  # ptlint: disable=PT001 -- calibration sync
            samples.append(time.perf_counter() - t0)
        tax = _median(samples)
    except Exception:
        return None
    _launch_cache["pallas"] = tax
    from paddle_tpu import stats
    stats.set_value("prof/launch_tax_pallas_s", tax)
    return tax


def launch_tax_fraction(dispatches: int, wall_s: float,
                        tax_s: Optional[float] = None,
                        name: Optional[str] = None) -> float:
    """Fraction of ``wall_s`` spent on per-dispatch launch overhead:
    ``dispatches * tax / wall``, clamped to [0, 1] (pipelined launches
    overlap, so the product is an upper bound). ``dispatches`` is the
    PR 13 ``serve/dispatch_launches`` delta over the window. Records
    ``prof/launch_tax_frac[/name]``."""
    if tax_s is None:
        tax_s = launch_tax_s()
    frac = 0.0 if wall_s <= 0 else min(1.0, dispatches * tax_s / wall_s)
    from paddle_tpu import stats
    sfx = f"/{name}" if name else ""
    stats.set_value(f"prof/launch_tax_frac{sfx}", frac)
    return frac


# ---------------------------------------------------------------------------
# step decomposition
# ---------------------------------------------------------------------------

def step_fractions(events=None,
                   window: Optional[Tuple[float, float]] = None,
                   dispatch_prefix: str = "serve/dispatch",
                   harvest_prefix: str = "serve/harvest",
                   host_bound_threshold: float = 0.5,
                   record: bool = True) -> Dict[str, float]:
    """Split a serving window into device-interaction vs host-gap
    fractions from the trace ring, with comm.py's exact interval
    algebra doing the union/subtraction:

    - ``device_frac`` — union(dispatch ∪ harvest spans) / wall: the
      host is feeding the device or blocked on its output.
    - ``queue_frac`` — union(harvest spans) / wall: blocked draining
      the dispatch queue (the device-bound signature — ⊂ device_frac).
    - ``host_frac`` — 1 − device_frac: pure host work (scheduling,
      detokenize, python) the device idles through at depth 1.
    - ``host_bound`` — 1.0 when host_frac > ``host_bound_threshold``.

    ``window`` defaults to the extent of the matched spans. Returns {}
    when nothing matched (no tracing, or an empty window). Pass
    ``dispatch_prefix="compute/"`` / ``harvest_prefix="collective/"``
    to decompose a train window with the same algebra. Records the
    ``prof/device_frac`` / ``prof/queue_frac`` / ``prof/host_frac`` /
    ``prof/host_bound`` gauges."""
    if events is None:
        from paddle_tpu.observability import trace
        events, _ = trace.events()
    disp = comm.span_intervals(events, dispatch_prefix, window)
    harv = comm.span_intervals(events, harvest_prefix, window)
    both = disp + harv
    if not both:
        return {}
    if window is None:
        window = (min(a for a, _ in both), max(b for _, b in both))
    wall = window[1] - window[0]
    if wall <= 0:
        return {}
    # exposed_time([window], spans) = window time covered by NO span —
    # the same union/intersection machinery comm/exposed_s runs on
    host_gap = comm.exposed_time([window], both)
    queue_busy = wall - comm.exposed_time([window], harv)
    out = {
        "wall_s": wall,
        "device_frac": (wall - host_gap) / wall,
        "queue_frac": queue_busy / wall,
        "host_frac": host_gap / wall,
    }
    out["host_bound"] = 1.0 if out["host_frac"] > host_bound_threshold \
        else 0.0
    if record:
        from paddle_tpu import stats
        for k in ("device_frac", "queue_frac", "host_frac",
                  "host_bound"):
            stats.set_value(f"prof/{k}", out[k])
    return out
