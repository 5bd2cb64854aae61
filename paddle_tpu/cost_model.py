"""Cost model (ref: python/paddle/cost_model/cost_model.py — CostModel:
build_program/profile_measure/static_cost_data/get_static_op_time, backed
by static_op_benchmark.json profiles).

TPU-native re-design: instead of a shipped JSON of pre-profiled CUDA op
times, costs come from the two sources that exist on this stack —
(a) XLA's own cost analysis of a compiled callable (exact FLOPs/bytes for
THE program that will run), and (b) live profile_measure timing on the
attached device. A tiny analytic roofline turns (a) into seconds, which
is what the auto-parallel planner consumes (distributed/planner.py cites
this module's estimates for its fsdp-vs-tp choice)."""

import time

import jax

__all__ = ["CostModel"]

# THE peak table (the one place chip peaks live; bench.py,
# profiler/timer.py and observability/devprof.py all read it): bf16 peak
# FLOP/s, HBM bytes/s and aggregate per-chip ICI bytes/s per TPU
# generation, from the Google Cloud TPU documentation ("TPU v5e":
# 197 TFLOP/s bf16, 819 GB/s HBM; likewise the v3/v4/v5p/v6e pages). ICI
# is the inter-chip bandwidth a collective can ride — the scaling-book's
# beta term. Keys are matched as substrings of the lower-cased
# ``device_kind`` in order ("v5p" before "v5": a v5e reports
# "TPU v5 lite"). "cpu" is a NOMINAL planning figure so the planner and
# its tests can rank strategies on the host backend — it is not a
# measured peak and no device metric is ever derived from it.
_PEAKS = {"v6": (918e12, 1640e9, 360e9), "v5p": (459e12, 2765e9, 480e9),
          "v5": (197e12, 819e9, 160e9), "v4": (275e12, 1228e9, 240e9),
          "v3": (123e12, 900e9, 140e9), "cpu": (1e11, 5e10, 1e10)}


def peaks_for_kind(device_kind: str):
    """``(peak_flops, hbm_bytes_per_s, ici_bytes_per_s)`` for a
    ``device_kind`` string. A kind the table does not know is an error,
    never a default: a roofline share against the wrong chip's peak is
    worse than no number."""
    kind = str(device_kind).lower()
    for key, val in _PEAKS.items():
        if key in kind:
            return val
    raise ValueError(
        f"unknown device_kind {device_kind!r}: not in the peak table "
        f"(keys {sorted(_PEAKS)}); add its published peaks to "
        f"paddle_tpu/cost_model.py")


def _peak(device):
    return peaks_for_kind(device.device_kind)


class CostModel:
    """(≙ cost_model.py CostModel:23)."""

    #: per-host DCN bandwidth (bytes/s) a cross-host collective can ride —
    #: a 200 Gbps NIC ballpark; ~an order of magnitude below ICI, which is
    #: why the planner routes only low-volume axes (pp activations) over it
    #: (≙ auto_parallel/cost/comm_op_cost.py's cross-machine link tier)
    DCN_BW = 25e9

    def __init__(self, dcn_bw: float = None, device_kind: str = None):
        """``device_kind`` ("v5", "v4", ...) plans for a TARGET chip
        without being attached to it — the search path runs on CPU but
        must reason with real TPU peaks (≙ the reference shipping
        static_op_benchmark.json profiles for absent hardware)."""
        if device_kind is not None:
            # planning for a TARGET chip: never touch the local backend
            # (the search runs where no such chip is attached)
            self.device = None
            self.peak_flops, self.peak_bw, self.ici_bw = peaks_for_kind(
                device_kind)
        else:
            self.device = jax.devices()[0]
            self.peak_flops, self.peak_bw, self.ici_bw = _peak(self.device)
        self.dcn_bw = dcn_bw if dcn_bw is not None else self.DCN_BW
        self._measured = {}

    def collective_time(self, nbytes: float, tier: str = "ici") -> float:
        """Seconds to move ``nbytes`` over the given link tier ("ici"
        within a slice, "dcn" across hosts; bandwidth term only — latency
        is negligible at the message sizes the planner reasons about)."""
        bw = self.dcn_bw if tier == "dcn" else self.ici_bw
        return float(nbytes) / bw

    # -- static (analysis-based) costs --------------------------------------

    def static_cost_data(self, fn, *example_args):
        """XLA cost analysis of ``jit(fn)`` on example args: returns the
        raw dict (flops, bytes accessed, ...) — the analog of the
        reference's static_op_benchmark.json rows, but for the exact
        program (≙ static_cost_data:65)."""
        compiled = jax.jit(fn).lower(*example_args).compile()
        return dict(compiled.cost_analysis())

    def get_static_op_time(self, fn, *example_args, forward=True,
                           dtype="float32"):
        """Roofline seconds for ``fn``: max(flops/peak, bytes/bandwidth)
        (≙ get_static_op_time:75; here per-callable, not per-op-name —
        there is no per-op dispatch to look up)."""
        data = self.static_cost_data(fn, *example_args)
        flops = float(data.get("flops", 0.0))
        if not forward:
            flops *= 3.0  # bwd ≈ 2x fwd on top of fwd
        nbytes = float(data.get("bytes accessed", 0.0))
        return max(flops / self.peak_flops, nbytes / self.peak_bw)

    # -- measured costs ------------------------------------------------------

    def profile_measure(self, fn, *example_args, warmup=1, iters=3):
        """Wall-clock measure of ``jit(fn)`` on the attached device
        (≙ profile_measure:46). Returns seconds per call."""
        jfn = jax.jit(fn)
        out = jfn(*example_args)
        for _ in range(warmup):
            out = jfn(*example_args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = jfn(*example_args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        self._measured[getattr(fn, "__name__", repr(fn))] = dt
        return dt
