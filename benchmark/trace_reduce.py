"""From a profiler trace to three things, and no more:

(a) the union of the intervals in which an operation ran on the device,
    hence busy seconds and the idle share of the traced window;
(b) device time per operation name (self time: an operation that contains
    others, such as a ``while``, is charged only what its children leave),
    with the number of calls;
(c) the idle gaps longer than ``MIN_GAP_S``, each labelled by what the
    host was doing: the innermost of the program's ``serve/...`` spans and
    the benchmark's own ``bench/...`` spans that covers most of the gap.

What a v5e trace looks like (looked at by hand, PR 24): the device is the
plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one event per executed
HLO operation, named by the HLO text (``%flash_attention_fwd.17 = (...)
custom-call(...)``), properly nested; ``XLA Modules`` holds one event per
program run. ``jax.profiler.TraceAnnotation`` spans land on the plane
``/host:CPU``, line ``python``, on the same clock: the benchmark's own
(``harness.Spans``) and, while a profiler session runs, the program's
(``paddle_tpu/observability/trace.py``), each under its own name.

The reduction works on a neutral form, ``{plane: {line: [(name, start_ns,
dur_ns), ...]}}``, so that the recorded fixture in ``tests/`` is a small
JSON file and not a binary.
"""

import glob
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("bench/", "serve/")      # the benchmark's, the program's
WINDOW_SPAN = "bench/traced_window"
MIN_GAP_S = 0.0005
UNLABELLED = "host:outside_bench_spans"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` into the neutral form, keeping only the
    device planes' operation lines and the host's ``bench/`` and
    ``serve/`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            keep = lambda line, ev: line.name == OPS_LINE
        elif plane.name == HOST_PLANE:
            keep = lambda line, ev: ev.name.startswith(SPAN_PREFIXES)
        else:
            continue
        lines = {}
        for line in plane.lines:
            events = [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                      for ev in line.events if keep(line, ev)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        out[plane.name] = lines
    return out


def load_json(path: str) -> dict:
    with open(path) as f:
        raw = json.load(f)
    return {plane: {line: [tuple(ev) for ev in events]
                    for line, events in lines.items()}
            for plane, lines in raw.items()}


def op_name(hlo_text: str) -> str:
    """``%fusion.364 = bf16[...] fusion(...)`` -> ``fusion.364``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def kernel_family(name: str) -> str:
    """``flash_attention_fwd.17`` -> ``flash_attention_fwd``: the name a
    kernel was given, without the compiler's instance number."""
    return re.sub(r"\.\d+$", "", name)


def _union(intervals):
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _self_times(events):
    """Events of one line nest properly. Yields (name, self_ns)."""
    stack = []      # [name, end, self]
    out = []
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][1] <= start:
            out.append((stack[-1][0], stack[-1][2]))
            stack.pop()
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, dur])
    out.extend((s[0], s[2]) for s in stack)
    return out


def _spans(trace):
    return [ev for events in trace.get(HOST_PLANE, {}).values()
            for ev in events if ev[0].startswith(SPAN_PREFIXES)]


def _label(gap_start, gap_end, spans):
    """The innermost span (other than the window's own) that covers most
    of the gap: the shortest of those that hold more than half of it, so a
    gap inside an admission reads ``serve/admit`` and not the
    ``bench/frontend_step`` around it. Where no span holds half, the one
    that holds most."""
    half = 0.5 * (gap_end - gap_start)
    best, best_key = UNLABELLED, (False, 0.0)
    for name, start, dur in spans:
        if name == WINDOW_SPAN:
            continue
        overlap = min(gap_end, start + dur) - max(gap_start, start)
        if overlap <= 0:
            continue
        # covering spans before the others; among them the shortest,
        # among the others the largest overlap
        key = (True, -dur) if overlap > half else (False, overlap)
        if key > best_key:
            best, best_key = name, key
    return best


def reduce_trace(trace: dict) -> dict:
    """See the module's docstring. Times in seconds. Busy seconds and the
    per-operation times are averaged over the device planes present; gaps
    are those of the first device."""
    spans = _spans(trace)
    windows = [(s, s + d) for n, s, d in spans if n == WINDOW_SPAN]
    devices = sorted(p for p in trace if DEVICE_PLANE.match(p))
    if not devices:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    all_ops = [ev for p in devices for ev in trace[p].get(OPS_LINE, [])]
    if not all_ops:
        raise ValueError("no operation ran on the device in the trace")
    if windows:
        w0, w1 = windows[0][0], windows[-1][1]
    else:
        w0 = min(s for _, s, _ in all_ops)
        w1 = max(s + d for _, s, d in all_ops)
    busy_ns, op_self, op_calls, gaps = 0.0, {}, {}, []
    for i, plane in enumerate(devices):
        events = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in trace[plane].get(OPS_LINE, [])
                  if s + d > w0 and s < w1]
        merged = _union((s, s + d) for _, s, d in events)
        busy_ns += sum(e - s for s, e in merged)
        for hlo, self_ns in _self_times(events):
            name = op_name(hlo)
            op_self[name] = op_self.get(name, 0.0) + self_ns
        for hlo, _, _ in events:
            name = op_name(hlo)
            op_calls[name] = op_calls.get(name, 0) + 1
        if i == 0:
            edges = [w0] + [t for pair in merged for t in pair] + [w1]
            for g0, g1 in zip(edges[0::2], edges[1::2]):
                if (g1 - g0) * 1e-9 >= MIN_GAP_S:
                    gaps.append((_label(g0, g1, spans), (g1 - g0) * 1e-9))
    n = len(devices)
    window_s = (w1 - w0) * 1e-9
    busy_s = busy_ns * 1e-9 / n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_self_s": {k: v * 1e-9 / n for k, v in op_self.items()},
        "op_calls": {k: v // n for k, v in op_calls.items()},
        "gaps": sorted(gaps, key=lambda g: -g[1]),
        "n_devices": n,
    }


def family_time(reduced: dict, family: str):
    """(seconds, calls) summed over every instance of a named kernel:
    ``flash_attention_fwd`` matches ``flash_attention_fwd.17`` and
    ``jvp_flash_attention_fwd_.3`` alike. (0.0, 0) when it never ran."""
    seconds, calls = 0.0, 0
    for name, s in reduced["op_self_s"].items():
        if family in kernel_family(name):
            seconds += s
            calls += reduced["op_calls"][name]
    return seconds, calls


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the operations that took most device
    time, and idle time by what the host was doing."""
    ops = sorted(reduced["op_self_s"].items(), key=lambda kv: -kv[1])[:top]
    by_label = {}
    for label, seconds in reduced["gaps"]:
        by_label[label] = by_label.get(label, 0.0) + seconds
    idle = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
