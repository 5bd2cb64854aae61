"""IPS / MFU benchmark timer (ref: python/paddle/profiler/timer.py —
``benchmark()`` hooks reporting ips during training; extended here with MFU
as BASELINE.md requires: MFU = model_flops / (chips × peak_flops))."""

import time

__all__ = ["Benchmark", "benchmark"]

def detect_peak_flops():
    """bf16 peak FLOP/s of the first attached device, from the one peak
    table (``cost_model.peaks_for_kind``); a device the table does not
    know raises instead of borrowing another chip's peak."""
    import jax
    from paddle_tpu.cost_model import peaks_for_kind
    return peaks_for_kind(jax.devices()[0].device_kind)[0]


class Benchmark:
    """Step timer with ips/MFU reporting."""

    def __init__(self, flops_per_step=None, num_chips=1, peak_flops=None):
        self.flops_per_step = flops_per_step
        self.num_chips = num_chips
        # lazy: detect_peak_flops() calls jax.devices(), which INITIALIZES
        # the backend — constructing a Benchmark (the module-level default
        # below runs at `import paddle_tpu`!) must never do that.
        # Falsy values (0/None) defer to detection, like the old
        # `peak_flops or detect_peak_flops()`.
        self._peak_flops = peak_flops or None
        self.reset()

    def reset(self):
        self.times = []
        self._last = None

    def begin(self):
        self._last = time.perf_counter()

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last is not None:
            dt = now - self._last
            self.times.append((dt, num_samples))
            # per-step latency histogram: table() then shows train-loop
            # p50/p99 alongside the serving ones (docs/observability.md)
            from paddle_tpu import stats
            stats.observe("train/step_s", dt)
        self._last = now

    def end(self):
        self._last = None

    @property
    def avg_step_time(self):
        if not self.times:
            return float("nan")
        # skip warmup step
        ts = [t for t, _ in self.times[1:]] or [self.times[0][0]]
        return sum(ts) / len(ts)

    def ips(self):
        ts = self.times[1:] or self.times
        total_t = sum(t for t, _ in ts)
        total_n = sum(n or 0 for _, n in ts)
        return total_n / total_t if total_t > 0 else float("nan")

    @property
    def peak_flops(self):
        if self._peak_flops is None:
            self._peak_flops = detect_peak_flops()
        return self._peak_flops

    def mfu(self):
        if self.flops_per_step is None:
            return float("nan")
        return self.flops_per_step / (
            self.avg_step_time * self.num_chips * self.peak_flops)

    def report(self):
        out = {"step_time_s": self.avg_step_time, "ips": self.ips(),
               "mfu": self.mfu()}
        # publish into the named-stat registry (≙ monitor.h STAT_ADD
        # consumers scraping the benchmark numbers)
        from paddle_tpu import stats
        for k, v in out.items():
            # NaN publishes too: gauges are last-value-wins, and a stale
            # number from a previous run is worse than an honest NaN
            stats.set_value(f"benchmark/{k}", v)
        # train-loop gauges under the observability namespace: when
        # num_samples counts tokens, ips IS tokens/s (the LM-training
        # convention BENCH uses); MFU rides along for the capacity view
        stats.set_value("train/tokens_per_s", out["ips"])
        stats.set_value("train/mfu", out["mfu"])
        return out


_global_benchmark = Benchmark()


def benchmark():
    return _global_benchmark
