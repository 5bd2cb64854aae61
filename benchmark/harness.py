"""What every kind of run shares: finding the cell's files by name, the look
for a chip, the compile cache, the benchmark's own spans, the traced
stretch, and the result line.
"""

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, ".out")           # git-ignored: caches, traces
CACHE_DIR = os.path.join(OUT_DIR, "xla_cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# ------------------------------------------------------------ files by name
def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(directory: str, name: str):
    """``benchmark/<directory>/<name>.py`` as a module. Names may hold
    dots (``step_mfu.train``), so this goes by path, not by import."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"{os.path.relpath(path, ROOT)} does not exist: a "
            f"{directory[:-1].replace('_', ' ')} is one file of that name")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{directory}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str) -> dict:
    """The cell as BENCHMARK.json has it, with its configuration's file,
    its traffic's file and its limits."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"bench: no workload {workload!r} in BENCHMARK.json "
                         f"(has: {', '.join(sorted(cells))})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, config["file"])) as f:
        config_file = json.load(f)
    return {
        "bench": bench,
        "workload": workload,
        "chips": cell["chips"],
        "config": config_file,
        "model": config_file["model"],
        "traffic": load_json("traffic", cell["traffic"] + ".json"),
        "limits": load_json("limits", workload + ".json"),
    }


def metrics_of(cell: dict, group: str):
    """The metrics of ``group`` (``end_to_end`` / ``per_layer``) that this
    cell reports: those that list it under ``workloads``, and those with no
    such list (for a per-layer metric: if the cell reports what it moves)."""
    bench, name = cell["bench"], cell["workload"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if group == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


# ------------------------------------------------------------------ device
def require_tpu(chips: int):
    """No accelerator, or fewer chips than the cell asks for: no run and no
    result line. Returns (jax, devices used)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU; JAX found platform "
            f"{devices[0].platform!r} ({devices[0].device_kind}). Nothing "
            f"was run.")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devices)}. Nothing was run.")
    return jax, devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else
    at one fixed path inside the checkout (the path is part of the key).
    The program's own ``compile_cache.enable()`` leaves a directory that is
    already set alone, so it takes this one."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def device_info(devices) -> dict:
    """The device as JAX reports it. ``memory_peak_bytes`` is the peak on
    the fullest chip: the allocator's ``peak_bytes_in_use`` (arrays) plus
    ``peak_bytes_reserved`` (what running programs reserve for their
    temporaries, which the first number leaves out on a TPU)."""
    peak, parts = 0, (0, 0)
    for d in devices:
        stats = d.memory_stats() or {}
        in_use = int(stats.get("peak_bytes_in_use", 0))
        reserved = int(stats.get("peak_bytes_reserved", 0))
        if in_use + reserved >= peak:
            peak, parts = in_use + reserved, (in_use, reserved)
    say(f"device memory peak: {parts[0]} bytes of arrays + {parts[1]} "
        f"reserved by programs = {peak}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def seconds_since_process_start(fallback_t0: float) -> float:
    """From the kernel's record of when this process started; the
    interpreter's own start-up is part of set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - fallback_t0


def kernel_blocks(model: dict, batch=None, seq_len=None, page=None) -> str:
    """The kernels' block sizes in effect, for the log: whatever the normal
    entry points give a user (the program's defaults, or its autotune cache
    where a user has filled one; the benchmark calls no tuner). Read through
    the program's private helpers, so a refactor may make a part unreadable:
    that is said, not fatal."""
    import jax.numpy as jnp
    parts = []
    H, D, dt = model["n_heads"], model["head_dim"], jnp.dtype(model["dtype"])
    try:
        from paddle_tpu.ops.pallas.autotune import get_cache
        if seq_len is not None:
            fa = importlib.import_module(
                "paddle_tpu.ops.pallas.flash_attention")
            from paddle_tpu.ops.pallas.fused_ce import _pick_block_v
            hit = get_cache().get(fa._tune_key(
                batch, seq_len, seq_len, H, H, D, dt, True, False, False,
                False))
            parts.append(f"flash (block_q, block_k) = "
                         f"{tuple(hit) if hit else fa._DEFAULT_BLOCKS}"
                         f"{' (autotune cache)' if hit else ' (default)'}")
            parts.append(f"fused_ce block_v = "
                         f"{_pick_block_v(model['vocab_size'], 512)}")
        if page is not None:
            from paddle_tpu.ops.pallas.paged_attention import _resolve_config
            columns = -(-model["max_seq_len"] // page)
            parts.append(
                f"paged_append_attend (pages_per_program, head_block) = "
                f"{_resolve_config(None, None, page, H, D, dt, 1, columns, True)}")
    except (ImportError, AttributeError, TypeError) as e:
        parts.append(f"not readable ({type(e).__name__}: {e})")
    return "kernel blocks in effect: " + "; ".join(parts)


def settle_host() -> None:
    """Last thing before the window: collect garbage once and move what
    set-up left alive (traced programs, caches: millions of objects) out
    of the collector's sight, so that a full collection cannot stall the
    host for a second in the middle of the window."""
    import gc
    gc.collect()
    gc.freeze()


# ------------------------------------------------------------------- spans
class Spans:
    """The benchmark's own spans, around its calls into the program: kept
    in memory on the host clock, and written into the profiler's trace as
    ``TraceAnnotation``s so that idle gaps can be attributed."""

    def __init__(self):
        self.records = {}              # name -> [(start_s, seconds), ...]

    def span(self, name: str):
        return _Span(self, name)

    def durations(self, name: str, t0=None, t1=None):
        return [d for s, d in self.records.get(name, [])
                if (t0 is None or s >= t0) and (t1 is None or s + d <= t1)]


class _Span:
    def __init__(self, spans, name):
        import jax
        self._spans, self._name = spans, name
        self._ann = jax.profiler.TraceAnnotation(name)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._spans.records.setdefault(self._name, []).append((self._t0, dt))
        return False


class TracedStretch:
    """Profile a short stretch of the run: ``start()``, the work, ``stop()``
    (which returns the reduced trace). The Python tracer is off, since it
    slows the host loop that is being measured; the trace directory is
    inside the checkout and is emptied before and after."""

    def __init__(self, workload: str):
        self.dir = os.path.join(OUT_DIR, "trace", workload)
        self._window = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("bench/traced_window")
        self._window.__enter__()

    def stop(self) -> dict:
        import jax
        from benchmark import trace_reduce
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            trace = trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce_trace(trace)


# ------------------------------------------------------------- result line
def percentile(values, q: float) -> float:
    """Nearest-rank on the sorted sample, interpolated (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))


def print_checks(checks, stream=None) -> None:
    """Each number compared beside its limit, as the last lines on
    standard error."""
    stream = sys.stderr if stream is None else stream
    for name, value, limit in checks:
        verdict = "ok" if value <= limit else "OVER"
        print(f"[bench] compared {name} = {value:.6g} (limit {limit:.6g}) "
              f"{verdict}", file=stream, flush=True)


def emit(result: dict, metrics: dict, device: dict, breakdown=None) -> None:
    line = {
        "correct": bool(result["correct"]),
        "attempted": int(result["attempted"]),
        "failed": int(result["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in result["checks"]}
    sys.stdout.flush()
    print_checks(result["checks"])
    print(json.dumps(line), flush=True)
