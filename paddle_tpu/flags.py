"""Typed runtime flag system.

Reference analog: gflags + `PADDLE_DEFINE_EXPORTED_*`
(paddle/fluid/platform/flags.cc, 74 definitions) exposed to Python via
`paddle.set_flags/get_flags` (paddle/fluid/pybind/global_value_getter_setter.cc:212).

Here flags are plain typed Python registrations, overridable via environment
variables ``PT_FLAGS_<NAME>`` at import time and ``set_flags`` at runtime.
"""

import os
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

_lock = threading.Lock()


# ---------------------------------------------------------------------------
# PT_* environment-variable contract registry.
#
# Every ``os.environ`` / ``os.getenv`` read of a ``PT_*`` name anywhere in
# the package must have a ``declare_env`` entry here (enforced by ptlint
# rule PT005 — paddle_tpu/analysis/rules_env.py). The registry is the one
# source of truth the docs table in docs/observability.md is generated
# from (``env_contract_markdown``), so a knob like PT_SERVE_INFLIGHT can
# never silently fork from its documentation.
# ---------------------------------------------------------------------------

@dataclass
class EnvVar:
    name: str
    help: str
    default: Optional[str] = None
    owner: str = ""          # module that consumes it (doc pointer)


_ENV_REGISTRY: Dict[str, EnvVar] = {}
_ENV_PREFIXES: Dict[str, EnvVar] = {}
_TOOL_PREFIXES: Dict[str, EnvVar] = {}


def declare_tool_prefix(prefix: str, help: str, owner: str = "") -> None:
    """Bring a TOOL env namespace (e.g. ``PD_`` for profile_decode
    report knobs) under the contract. Unlike ``declare_env_prefix``
    this does NOT declare every name in the namespace — it widens the
    checked set: once ``PD_`` is registered, ptlint PT005 flags any
    ``PD_*`` read (in paddle_tpu/ *and* tools/) that lacks its own
    ``declare_env`` entry, exactly like a ``PT_*`` read would be."""
    if not prefix.endswith("_"):
        raise ValueError(f"tool prefix must end with '_', got {prefix!r}")
    _TOOL_PREFIXES[prefix] = EnvVar(prefix + "*", help, None, owner)


def _in_contract_namespace(name: str) -> bool:
    return name.startswith("PT_") or any(
        name.startswith(p) for p in _TOOL_PREFIXES)


def declare_env(name: str, help: str, default: Optional[str] = None,
                owner: str = "") -> None:
    """Register one environment variable in the contract: a ``PT_*``
    name, or a tool name under a ``declare_tool_prefix`` namespace
    (register the prefix first)."""
    if not _in_contract_namespace(name):
        raise ValueError(
            f"env contract covers PT_* and registered tool-prefix "
            f"names ({sorted(_TOOL_PREFIXES)}), got {name!r}")
    _ENV_REGISTRY[name] = EnvVar(name, help, default, owner)


def declare_env_prefix(prefix: str, help: str, owner: str = "") -> None:
    """Register a PT_* name FAMILY (e.g. ``PT_FLAGS_<flag>``)."""
    if not prefix.startswith("PT_"):
        raise ValueError(f"env contract covers PT_* names, got {prefix!r}")
    _ENV_PREFIXES[prefix] = EnvVar(prefix + "*", help, None, owner)


def env_registry() -> Dict[str, EnvVar]:
    out = dict(_ENV_REGISTRY)
    out.update({k + "*": v for k, v in _ENV_PREFIXES.items()})
    return out


def tool_prefix_registry() -> Dict[str, EnvVar]:
    """The registered tool env namespaces (``declare_tool_prefix``)."""
    return dict(_TOOL_PREFIXES)


def env_declared(name: str) -> bool:
    """True iff ``name`` is covered by the contract (exact or prefix)."""
    if name in _ENV_REGISTRY:
        return True
    return any(name.startswith(p) for p in _ENV_PREFIXES)


def env_contract_markdown() -> str:
    """The docs/observability.md env-contract table, generated from the
    registry (regenerate with
    ``python -c "import paddle_tpu.flags as f; print(f.env_contract_markdown())"``)."""
    rows = sorted(env_registry().values(), key=lambda v: v.name)
    lines = ["| variable | default | consumed by | meaning |",
             "|---|---|---|---|"]
    for v in rows:
        default = "—" if v.default is None else f"`{v.default}`"
        owner = f"`{v.owner}`" if v.owner else "—"
        lines.append(f"| `{v.name}` | {default} | {owner} | {v.help} |")
    return "\n".join(lines)


@dataclass
class _Flag:
    name: str
    default: Any
    type: type
    help: str
    validator: Optional[Callable[[Any], bool]] = None
    value: Any = None


_REGISTRY: Dict[str, _Flag] = {}


def _coerce(ftype: type, raw: Any) -> Any:
    if ftype is bool and isinstance(raw, str):
        return raw.lower() in ("1", "true", "yes", "on")
    return ftype(raw)


def define_flag(name: str, default: Any, help: str = "",
                ftype: Optional[type] = None,
                validator: Optional[Callable[[Any], bool]] = None) -> None:
    """Register a flag. Environment ``PT_FLAGS_<NAME>`` overrides the default."""
    ftype = ftype or type(default)
    env = os.environ.get("PT_FLAGS_" + name.upper())
    value = _coerce(ftype, env) if env is not None else default
    with _lock:
        _REGISTRY[name] = _Flag(name, default, ftype, help, validator, value)


def get_flags(names=None) -> Dict[str, Any]:
    """Read flag values. ``names`` may be a str, list of str, or None (=all)."""
    if names is None:
        names = list(_REGISTRY)
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        if n not in _REGISTRY:
            raise KeyError(f"unknown flag {n!r}")
        out[n] = _REGISTRY[n].value
    return out


def get_flag(name: str) -> Any:
    return get_flags(name)[name]


def set_flags(flags: Dict[str, Any]) -> None:
    with _lock:
        for name, val in flags.items():
            if name not in _REGISTRY:
                raise KeyError(f"unknown flag {name!r}")
            f = _REGISTRY[name]
            val = _coerce(f.type, val)
            if f.validator is not None and not f.validator(val):
                raise ValueError(f"invalid value {val!r} for flag {name!r}")
            f.value = val


def describe_flags() -> str:
    lines = []
    for f in sorted(_REGISTRY.values(), key=lambda f: f.name):
        lines.append(f"{f.name} (={f.value!r}, default {f.default!r}): {f.help}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Core framework flags (analogs of paddle/fluid/platform/flags.cc entries).
# ---------------------------------------------------------------------------
define_flag("check_nan_inf", False,
            "Sweep op outputs for NaN/Inf during training "
            "(ref: FLAGS_check_nan_inf, framework/details/nan_inf_utils_detail.cc).")
define_flag("benchmark", False, "Synchronize and time each step.")
define_flag("matmul_precision", "default",
            "Precision for matmul/conv on TPU: default|high|highest "
            "(maps to jax.lax.Precision).")
define_flag("default_dtype", "float32", "Default floating dtype for creation ops.")
define_flag("conv_workspace_limit_mb", 512,
            "Kept for API parity; XLA manages conv scratch itself.")
define_flag("use_pallas_kernels", True,
            "Use Pallas TPU kernels for fused ops (flash attention etc.) "
            "when running on TPU; falls back to XLA-fused reference impls.")
define_flag("decode_kernel_min_t", 1024,
            "Cache length at/above which the decode engine's one-token "
            "step routes attention through the flash-decode kernel "
            "(reads only valid prefix blocks) instead of the dense "
            "einsum over the whole cache. Short caches stay on the "
            "einsum — the kernel's per-program overhead beats the "
            "bandwidth saving there.")
define_flag("scan_layers", True,
            "Run homogeneous transformer stacks as lax.scan over stacked "
            "block weights (one compiled layer body instead of L unrolled "
            "copies — L-fold faster XLA compiles). Off restores the "
            "unrolled Python loop.")
define_flag("log_level", "warning", "Framework log level.")
define_flag("stats_at_exit", False,
            "Dump the StatRegistry table to stderr at process exit "
            "(operator scrape path for launch/elastic CLI processes).")
define_flag("allocator_strategy", "xla",
            "Kept for API parity (ref auto_growth/naive_best_fit); on TPU the "
            "XLA/PJRT runtime owns HBM allocation.")


# ---------------------------------------------------------------------------
# The PT_* env contract (ptlint PT005 checks every read against this;
# the table in docs/observability.md is generated from it).
# ---------------------------------------------------------------------------

# -- multi-process topology (launch CLI → workers) --
declare_env("PT_COORDINATOR", "jax.distributed coordinator 'host:port'.",
            owner="distributed/env.py")
declare_env("PT_NUM_PROCESSES", "Total worker processes across nodes.",
            default="1", owner="distributed/env.py")
declare_env("PT_PROCESS_ID", "Global rank of this worker.", default="0",
            owner="distributed/env.py")
declare_env("PT_LOCAL_RANK", "Rank within this node.", default="0",
            owner="distributed/launch.py")
declare_env("PT_NNODES", "Node count.", default="1",
            owner="distributed/launch.py")
declare_env("PT_RANK", "RPC agent rank (rpc.init_rpc fallback).",
            default="0", owner="distributed/rpc.py")
declare_env("PT_WORLD_SIZE", "RPC / fleet world size fallback.",
            default="1", owner="distributed/rpc.py")
declare_env("PT_TRAINER_ENDPOINTS", "Comma-separated worker endpoints "
            "(fleet API parity).", owner="distributed/fleet")
declare_env("PT_MASTER", "Elastic store endpoint 'host:port'.",
            owner="distributed/elastic.py")
declare_env("PT_ELASTIC_VERSION", "Elastic job generation counter "
            "(set by the elastic manager on re-launch).",
            owner="distributed/elastic.py")
declare_env("PT_INIT_DEADLINE", "Seconds init_parallel_env may spend in "
            "rendezvous before CollectiveWatchdog raises.", default="120",
            owner="distributed/env.py")
declare_env("PT_RESTART_ATTEMPT", "Which auto-restart attempt this worker "
            "is (launch --max_restarts exports it; 0 = first run).",
            default="0", owner="distributed/launch.py")
declare_env("PT_ELASTIC_RESHAPE", "1 turns the --max_restarts relaunch "
            "into a local RESHAPE: the group relaunches at the "
            "surviving worker count and workers see the NEW world size "
            "/ membership via PT_NUM_PROCESSES / PT_PROCESS_ID "
            "(fleet/elastic_train re-plans its mesh and "
            "restore_resharded-resumes onto it). 0 keeps same-size "
            "restarts.", default="0", owner="distributed/launch.py")

# -- elastic fleet controller --
declare_env("PT_FLEET_MIN_REPLICAS", "Per-tier serving replica floor: "
            "the fleet controller heals back up to this many alive "
            "replicas (bypassing policy and cooldown) when deaths or "
            "drains drop a tier below it.", default="1",
            owner="fleet/controller.py")
declare_env("PT_FLEET_MAX_REPLICAS", "Per-tier serving replica "
            "ceiling: scale-up clamps here no matter how hard the SLO "
            "burns.", default="8", owner="fleet/controller.py")
declare_env("PT_FLEET_COOLDOWN_S", "Seconds after any policy-driven "
            "scale action before the controller takes the next one "
            "(actuation latency must not read as an unanswered "
            "signal). Healing below the floor ignores it.",
            default="5", owner="fleet/controller.py")
declare_env("PT_FLEET_DRAIN_GRACE_S", "How long a draining replica "
            "may take to finish its in-flight requests before the "
            "controller SIGKILLs it and the router's death sweep "
            "redistributes the remainder.", default="10",
            owner="fleet/controller.py")
declare_env("PT_RESHARD_INPLACE", "1 (default) lets elastic reshape "
            "events move live train state between (mesh, layout) "
            "pairs in HBM via distributed/redistribute.py — "
            "O(collective) instead of a checkpoint round trip. 0 "
            "forces the save + load_resharded fallback path (also "
            "taken automatically, loudly, when planning or transfer "
            "fails).", default="1", owner="fleet/elastic_train.py")
declare_env("PT_RESHARD_VERIFY", "1 (default) digests every leaf "
            "before and after an in-HBM redistribute — a mismatch "
            "(in-transit corruption) raises RedistributeError and the "
            "reshape degrades to the checkpoint fallback, counted "
            "under fleet/reshard_fallbacks, instead of training on "
            "corrupted state. 0 trades the host round trip for speed "
            "on trusted fabrics.", default="1",
            owner="distributed/redistribute.py")
declare_env("PT_DRAIN_MIGRATE", "1 (default) makes a draining serve "
            "replica MIGRATE its in-flight decode requests to "
            "survivors mid-decode (KV rows + token history over the "
            "fp32 wire, byte-identical streams) instead of finishing "
            "them in place; per-request failures fall back to "
            "finish-in-place. 0 restores drain-by-completion.",
            default="1", owner="serving/router.py")

# -- observability --
declare_env("PT_TRACE_DIR", "Enable tracing; rank traces land here as "
            "trace_rank{N}.json and the launcher merges them.",
            owner="observability/trace.py")
declare_env("PT_TRACE_FILE", "Exact trace output path (wins over "
            "PT_TRACE_DIR).", owner="observability/trace.py")
declare_env("PT_TRACE_RING", "Trace ring-buffer capacity in events.",
            default="65536", owner="observability/trace.py")
declare_env("PT_STATSZ_PORT", "Serve live /statsz snapshots on this port "
            "(launcher hands rank r port base+1+r).",
            owner="observability/statsz.py")
declare_env("PT_TRACE_FLUSH_S", "Seconds between periodic atomic "
            "rewrites of the (partial) trace file when tracing is "
            "env-enabled — a SIGKILLed replica still leaves its last "
            "flush on disk for stitching. 0 disables (atexit export "
            "only).", default="5", owner="observability/trace.py")
declare_env("PT_FLIGHT_RING", "Per-request flight recorder bound: how "
            "many requests' event timelines stay resident (FIFO "
            "eviction; each request keeps at most 64 events). 0 "
            "disables recording entirely.", default="256",
            owner="observability/flight.py")
declare_env("PT_FLIGHT_DIR", "Directory terminal-failure flight "
            "records dump into as flight_<rid>.json (falls back to "
            "PT_TRACE_DIR; with neither set the record is one "
            "structured stderr line).", owner="observability/flight.py")
declare_env("PT_NUMERICS_EVERY", "Training-numerics capture cadence: "
            "compute the in-graph tensor-stat pack every N optimizer "
            "steps (one packed device vector per sampled step). 0 "
            "(default) builds the step without the stats subgraph "
            "entirely.", default="0",
            owner="observability/numerics.py")
declare_env("PT_NUMERICS_RING", "Numerics flight-recorder bound: how "
            "many decoded snapshots stay resident for the "
            "detector-triggered dump.", default="64",
            owner="observability/numerics.py")
declare_env("PT_NUMERICS_DIR", "Directory numerics alert dumps land "
            "in as numerics_<step>.<pid>.json (falls back to "
            "PT_FLIGHT_DIR then PT_TRACE_DIR; with none set the dump "
            "is one structured stderr line).",
            owner="observability/numerics.py")
declare_env("PT_NUMERICS_WINDOW", "Numerics watch history window "
            "(samples) for the median/MAD spike detectors.",
            default="32", owner="observability/numerics.py")
declare_env("PT_NUMERICS_Z", "Numerics watch robust z-score "
            "threshold: loss/grad-norm spikes fire when the value "
            "exceeds median + z*(1.4826*MAD) over the window.",
            default="6.0", owner="observability/numerics.py")
declare_env("PT_NUMERICS_OVERFLOW", "Numerics watch overflow "
            "threshold: alert when any family's dtype-overflow "
            "fraction (|x| above 90% of finfo.max) exceeds this.",
            default="0.01", owner="observability/numerics.py")
declare_env("PT_NUMERICS_EF", "Numerics watch error-feedback runaway "
            "threshold: alert when any bucket's EF-to-grad magnitude "
            "ratio exceeds this.", default="8.0",
            owner="observability/numerics.py")
declare_env("PT_SLO_TTFT_P99_MS", "Fleet SLO target: merged p99 TTFT "
            "in milliseconds. The fleet watch publishes the "
            "fleet/slo_ttft_burn gauge (p99/target) and fires "
            "fleet/alert_slo_ttft on the burn>1 edge. Unset disables.",
            owner="observability/fleet.py")
declare_env("PT_SLO_GOODPUT", "Fleet SLO target: goodput floor in "
            "tokens/s summed over replicas (token-progress rate from "
            "the heartbeat load gauges). fleet/alert_slo_goodput "
            "fires while a busy fleet runs below it. Unset disables.",
            owner="observability/fleet.py")
declare_env("PT_SLO_QUEUE_AGE_S", "Runaway-queue detector threshold: "
            "a replica whose oldest waiting request exceeds this age "
            "raises fleet/alert_queue_age.", default="30",
            owner="observability/fleet.py")
declare_env("PT_PROF_PEAK_FLOPS", "Device-profiler roofline override: "
            "peak FLOP/s the prof/roofline_frac denominator uses "
            "instead of the detected per-generation table entry.",
            owner="observability/devprof.py")
declare_env("PT_PROF_PEAK_HBM_GBPS", "Device-profiler roofline "
            "override: peak HBM bandwidth in GB/s (detected table "
            "entry otherwise).", owner="observability/devprof.py")
declare_env("PT_PROF_LAUNCH_ITERS", "No-op launches timed by the "
            "once-per-process launch-tax calibration (the median is "
            "the per-dispatch overhead estimate).", default="64",
            owner="observability/devprof.py")

# -- serving --
declare_env("PT_SERVE_INFLIGHT", "Decode-engine pipeline depth: how many "
            "dispatches may be in flight before the oldest is harvested "
            "(1 = synchronous).", default="2",
            owner="inference/decode_engine.py")
declare_env("PT_SERVE_PREFILL_TOKENS", "Per-step prompt-token budget for "
            "interleaved chunked prefill (0 = largest bucket).",
            default="0", owner="inference/decode_engine.py")
declare_env("PT_SERVE_QUEUE_DEPTH", "Serving front-end admission-queue "
            "bound: submissions beyond this many waiting requests are "
            "rejected (serve/queue_rejects) instead of queued.",
            default="256", owner="serving/scheduler.py")
declare_env("PT_SERVE_ADMISSION", "Front-end admission-queue ordering "
            "policy: fifo (arrival), priority (higher priority= first), "
            "edf (earliest absolute deadline first).",
            default="priority", owner="serving/scheduler.py")
declare_env("PT_SERVE_ROUTER_PORT", "TCPStore port for the multi-"
            "replica router's control plane (membership, mailboxes, "
            "results).", default="8997", owner="serving/router.py")
declare_env("PT_SERVE_LOADGEN_SEED", "Deterministic load-generator "
            "seed — one knob pinning the exact SLO-bench/CI workload.",
            default="0", owner="serving/loadgen.py")
declare_env("PT_KV_WIRE", "KV-page transfer wire format for "
            "disaggregated prefill/decode serving and the fleet prefix "
            "directory: int8 (default, block-scaled ~3.9x compression), "
            "fp8, or fp32 (bit-identity opt-out — disaggregated decode "
            "exactly matches same-replica serving).", default="int8",
            owner="serving/kv_transfer.py")
declare_env("PT_SERVE_ROLE", "This serving replica's role in a "
            "disaggregated fleet: both (symmetric, default), prefill "
            "(big-bucket prefill only, KV handed off over the wire), "
            "decode (installs handoffs, deep decode occupancy).",
            default="both", owner="serving/disagg.py")
declare_env("PT_STORE_RETRY_S", "Per-op retry budget (seconds) for the "
            "guarded control-plane store client (GuardedStore): a store "
            "op failing for longer than this raises StorePartitioned "
            "and the replica degrades to partition mode — buffered "
            "results, missed heartbeats, decode keeps stepping.",
            default="2.0", owner="distributed/resilience.py")
declare_env("PT_KV_TRANSPORT", "Data plane for KV handoff/migration "
            "blobs in disaggregated serving: socket (default, direct "
            "replica-to-replica P2P — the TCPStore carries only "
            "membership/directory/results) or store (PR 16 TCPStore "
            "chunked-blob path, the fallback when the native P2P "
            "endpoint is unavailable).", default="socket",
            owner="serving/kv_transfer.py")
declare_env("PT_SERVE_HOST", "Host address replicas advertise for "
            "their socket KV-transport endpoint (kv_ep locators).",
            default="127.0.0.1", owner="serving/kv_transfer.py")
declare_env("PT_ROUTER_ENDPOINT_FILE", "Path of the router endpoint "
            "file ({host, port, gen, pid} JSON, atomically replaced): "
            "each router generation writes gen+1 here and replica "
            "RouterLinks watch it to dial the successor store after a "
            "router death. Unset disables cross-generation failover "
            "(single-generation PR 10 behavior).",
            owner="serving/router.py")
declare_env("PT_ROUTER_STANDBY", "1 makes the RouterSupervisor keep a "
            "warm standby router process (imports paid, waiting on a "
            "promotion token file) so failover costs store-bind + "
            "journal-replay only; 0 (default) cold-spawns the "
            "successor on death.", default="0",
            owner="fleet/controller.py")
declare_env("PT_FLEET_PREFIX", "0 disables the fleet-wide prefix-cache "
            "directory (publication, lookup, and the router's "
            "pre-placement consult) — replicas fall back to local "
            "radix caches only.", default="1", owner="serving/disagg.py")
declare_env("PT_PAGED_PREFIX", "0 disables prefix (radix) caching over "
            "the page pool — every prompt prefills cold and retirement "
            "frees pages instead of keeping them warm.", default="1",
            owner="inference/paged_engine.py")
declare_env("PT_SERVE_ENGINE", "Default serving engine for the "
            "front-end/bench ladder: 'paged' (default) or 'contiguous' "
            "(the slot-contiguous DecodeEngine kept behind this flag).",
            default="paged", owner="inference/factory.py")

# -- cross-chip communication --
declare_env("PT_COMM_QUANT", "Wire format for the quantized gradient/"
            "weight collectives: none/bf16/int8/fp8/auto. auto asks "
            "planner._axis_tier per axis — DCN-crossing axes quantize "
            "to int8, ICI axes stay full precision.", default="auto",
            owner="distributed/compression.py")
declare_env("PT_COMM_BLOCK", "Block size for block-scaled quantization "
            "on the collective wire (one fp32 scale per block).",
            default="256", owner="distributed/compression.py")
declare_env("PT_COMM_QUANT_PSUM", "1 selects the legacy psum wire for "
            "compressed dp sync (int8 payloads upcast to int32 on the "
            "wire — the tested parity reference, NOT a volume win).",
            default="0", owner="distributed/compression.py")
declare_env("PT_COMM_BUCKET_MB", "Gradient-sync bucket budget in MB for "
            "the overlap scheduler: backward partitions grad leaves "
            "into ~this-many-MB buckets in reverse-layer order, one "
            "quantized reduce-scatter per bucket launched as the layer's "
            "grads appear.", default="4", owner="distributed/overlap.py")
declare_env("PT_COMM_OVERLAP", "0 disables overlap scheduling in the "
            "bucketed train step: collectives hoist to a tail sync "
            "after the full backward (same math, bit-identical params "
            "— the A/B baseline the train_overlap bench measures "
            "against).", default="1", owner="distributed/overlap.py")
declare_env("PT_COMM_STRIPE", "Link striping for large bucket payloads: "
            "0 off; auto/1 splits per planner.stripe_plan into a "
            "full-precision ICI stripe plus a quantized DCN stripe "
            "launched concurrently; a float in (0,1) forces that DCN "
            "fraction.", default="0", owner="distributed/overlap.py")

# -- bench driver (bench.py) --
declare_env("PT_BENCH_BUDGET_S", "bench.py wall budget: sub-benches "
            "past it are skipped with <name>_skipped rows (headline "
            "metric secured first).", default="7200", owner="bench.py")
declare_env("PT_BENCH_ONLY", "Comma-set of sub-benches to re-capture "
            "(e.g. bert,decode) without paying the flagship compile.",
            owner="bench.py")
declare_env("PT_DECODE_SECTIONS", "Comma-set of bench_decode sections "
            "(generate,int8,engine,engine_longctx,engine_paged,"
            "engine_paged_prefix,engine_int8,spec,spec_paged).",
            owner="bench.py")

# -- compilation / data / testing --
declare_env("PT_COMPILE_CACHE_GUARD", "0 disables the persistent-compile-"
            "cache failure guard (compile_cache.guard).", default="1",
            owner="compile_cache.py")
declare_env("PT_AUTOTUNE_CACHE", "Kernel autotuner cache file path "
            "(default: .pt_cache/autotune.json inside the checkout).",
            owner="ops/autotune.py")
declare_env("PT_VMEM_BUDGET_MB", "Static per-core VMEM budget (MiB) "
            "the ptgeom PT006 rule and autotune's geometry guard "
            "check pallas launches against; a fixed 0.5 MiB compiler "
            "reserve is subtracted.", default="16",
            owner="analysis/kernelmodel.py")
declare_env("PT_DATA_DIR", "Root directory for bundled datasets.",
            owner="vision/datasets.py")
declare_env("PT_FAULTS", "Fault-injection plan: ';'-separated "
            "site:action[:k=v,...] rules (testing/faults.py).",
            owner="testing/faults.py")
declare_env_prefix("PT_FLAGS_", "Per-flag override of any define_flag "
                   "entry, e.g. PT_FLAGS_SCAN_LAYERS=0.", owner="flags.py")

# ---------------------------------------------------------------------------
# Tool env namespaces (ISSUE 15 satellite): report/smoke knobs the
# tools/ scripts read. declare_tool_prefix brings the NAMESPACE under
# the PT005 contract (tools/ is linted like paddle_tpu/ is); each knob
# still needs its own declare_env row below.
# ---------------------------------------------------------------------------
declare_tool_prefix("PD_", "profile_decode.py report knobs.",
                    owner="tools/profile_decode.py")
declare_tool_prefix("FLEETOBS_", "fleet-observability smoke/test "
                    "worker handshake.", owner="tests/_fleetobs.py")
declare_tool_prefix("PTGEOM_", "ptgeom.py kernel-geometry sweep "
                    "knobs.", owner="tools/ptgeom.py")

declare_env("PD_SIZE", "profile_decode model size: 1p3b (default), "
            "350m, or tiny (the CPU smoke).", default="1p3b",
            owner="tools/profile_decode.py")
declare_env("PD_SECTIONS", "Comma-set of profile_decode report "
            "sections: engine, paged, prof.",
            default="engine,paged", owner="tools/profile_decode.py")
declare_env("PD_INFLIGHT", "Comma-list of pipeline depths to sweep "
            "(e.g. 1,2,4); unset uses the engine default.",
            owner="tools/profile_decode.py")
declare_env("PD_SPEC", "1 adds the chunked speculative run on "
            "repetitive prompts to the engine section.", default="0",
            owner="tools/profile_decode.py")
declare_env("PD_PREFIX", "1 adds the repeated-system-prompt cold/warm "
            "radix-cache sweep (the ci.sh paged gate).", default="0",
            owner="tools/profile_decode.py")
declare_env("PD_LENGTHS", "Comma-list of prompt lengths the prof "
            "section sweeps per decode path (default by model size; "
            ">=3 lengths make the launch-tax-vs-length curve).",
            owner="tools/profile_decode.py")
declare_env("PTGEOM_GEOMS", "Comma-set of ladder geometries the "
            "ptgeom sweep drives (tiny,350m,r06); unset sweeps all "
            "(tools/ptgeom.py --geoms overrides).",
            owner="tools/ptgeom.py")
declare_env("FLEETOBS_TRACE_FILE", "Per-replica trace path handed to "
            "launch-spawned fleet workers; translated to PT_TRACE_FILE "
            "at worker startup so the launcher's own atexit export "
            "cannot clobber replica traces.", owner="tests/_fleetobs.py")
