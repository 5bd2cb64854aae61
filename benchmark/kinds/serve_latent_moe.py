"""Traffic kind ``serve_latent_moe``: ``serve_closed``'s closed loop (its
``Loop`` and ``pick_samples``, by import; it has no function for the
window, which is typed again below) over a model of latent-attention
layers with sparse experts: the benchmark's weights come from
``reference/latent_moe.py`` (a layer at a time), the model from
``program_latent_moe.py``, and the plain reference is that file's
expanded attention with a loop over the experts. The engine is what
``inference.make_engine`` gives: the paged engine with ONE pool of latent
rows, which prefills a prompt of any length in chunks of the traffic
file's ``prefill_chunk`` into pages and decodes in the absorbed form.

Parameters of a traffic file, beside ``serve_closed``'s: ``prefill_chunk``.
``kv_pool_pages`` has to hold the mix's longest request in every slot.

Set-up: weights, model, engine, one warm-up request of more than one
chunk (the chunk program and the decode program), then the ramp: every
client submits, and the window opens when each holds its first token.

``correct`` compares numbers that all come from what the engine did
inside the run. ``logit_gap``, as the other serving kinds: after the
window the reference runs once over each checked request (prompt +
served tokens, padded to a multiple of ``REFERENCE_PAD``) and the widest
gap by which a served token's logit lies below the reference's best is
taken. The other two read the ROUTING of the served path: the engine's
decode step brings back, for one slot, what every expert layer's router
saw and chose (``PagedDecodeEngine.on_routing``; `RoutingTap` keeps
them), and the requests that slot finished in the window are among the
checked ones. ``routing_mismatch`` is the share of those token-layer
routings on which the served path and the reference, each on its own
activations, chose different sets: what any lower precision BEFORE a
router moves (products, the cache). ``router_mismatch`` is the share on
which the served path's choice differs from what the reference's router
(float32, HIGHEST) chooses for the very tokens the served router saw:
the router alone, whatever came before it.

A ``--trace 1`` run also reads the device time under each of the
program's named scopes (``counters["scope_s"]``) in its one pass over
the trace: an operation's event names its instruction, and the event's
metadata (``tf_op``) the scopes it was traced under; a fusion carries
the name of ONE of the operations fused into it (its root's). The
expert layer's metrics divide by it; without the reader of the raw
trace (``tensorflow``'s ``xplane_pb2``) they are left out.
"""

import time

import numpy as np

from benchmark import correct, harness, traffic_gen, work_latent_moe
from benchmark import weights as weights_lib
from benchmark.kinds.serve_closed import (FIRST_TOKEN_WAIT_S, Loop,
                                          _retraces, pick_samples)
from benchmark.program_latent_moe import build_model, model_config
from benchmark.reference import latent_moe

REFERENCE_PAD = 2048            # few lengths, so few programs to compile
SCOPES = work_latent_moe.EXPERT_SCOPES + ("mla_step", "mla_prefill")


class RoutingTap:
    """What the engine reports of one slot's routing (``on_routing``),
    request by request: positions, what each expert layer's router saw,
    what it chose."""

    def __init__(self, eng):
        self.rows = {}          # id(request) -> (request, [(at, saw, chose)])
        eng.on_routing = self.take

    def take(self, req, at, saw, chose):
        self.rows.setdefault(id(req), (req, []))[1].append((at, saw, chose))

    def of(self, finished):
        """``{"at", "saw", "chose"}`` (arrays over the tokens the request
        fed to decode steps) for a finished request as `Loop` keeps it,
        None where the reported slot did not serve all of it."""
        n0, n = len(finished["prompt"]), len(finished["tokens"])
        for req, rows in self.rows.values():
            if (len(req.prompt), len(req.tokens)) == (n0, n) \
                    and [at for at, _, _ in rows] == list(range(
                        n0, n0 + n - 1)) \
                    and list(req.prompt) == finished["prompt"] \
                    and list(req.tokens) == finished["tokens"]:
                return {"at": np.asarray([at for at, _, _ in rows]),
                        "saw": np.stack([saw for _, saw, _ in rows]),
                        "chose": np.stack([c for _, _, c in rows])}
        return None


def checked_samples(finished, k, seed, tap):
    """`pick_samples`' ``k`` requests, the last of them giving their
    places to the requests whose routing the engine reported (the
    longest stays); each of those carries it under ``routing``."""
    samples = pick_samples(finished, k, seed)
    routed = [dict(f, routing=r) for f, r in (
        (f, tap.of(f)) for f in finished) if r is not None and len(r["at"])]
    routed = routed[:max(k - 1, 0)]
    theirs = [r["prompt"] for r in routed]
    keep = [s for s in samples if s["prompt"] not in theirs]
    return keep[:len(samples) - len(routed)] + routed


def _differ(a, b):
    """How many rows of (N, k) ``a`` and ``b`` hold different SETS."""
    return int(np.sum(np.any(np.sort(np.asarray(a), -1)
                             != np.sort(np.asarray(b), -1), axis=-1)))


def routing_numbers(weights, model, routed, at, saw, chose):
    """Over the tokens at positions ``at`` of one request: (token-layer
    routings; those on which ``chose`` (a layer's (N, k) each) is not
    the set the reference chose there: ``routed``, as
    ``latent_moe.forward`` gives it; those on which it is not the set
    the reference's router chooses for ``saw``, a layer's (N, d)
    each)."""
    import jax.numpy as jnp
    lead = len(weights["dense"])
    differ = alone = 0
    for i, (_, theirs) in enumerate(routed):
        lp = latent_moe.layer_weights(weights, lead + i)
        again, _ = latent_moe.choose_experts(
            jnp.asarray(saw[i]).astype(jnp.float32),
            lp["experts.w_router"], lp["experts.router_bias"],
            model["experts_per_token"], float(model["routed_scaling"]))
        differ += _differ(chose[i], np.asarray(theirs)[at])
        alone += _differ(chose[i], again)
    return len(at) * len(routed), differ, alone


def reference_gaps(cell, weights, samples, mode="f32", router="float32",
                   cache="float32", memo=None):
    """For each checked request the gap, at every served position,
    between the reference's best logit and the served token's, and for
    one whose routing the engine reported (``routing``) the numbers of
    `routing_numbers`; with a control (``mode`` / ``router`` / ``cache``
    below the configuration's) in the program's place: the gap of the
    token that the control puts first, and what the control's routers
    saw and chose at the same positions. ``memo`` (a dict) keeps the
    reference's own pass over a sample for the next call (the
    calibration reads several controls over the same samples)."""
    import jax.numpy as jnp
    model, traffic = cell["model"], cell["traffic"]
    window = max(traffic_gen.quantile_lengths(traffic["answer_len"],
                                              traffic["request_pool"]))
    control = (mode, router, cache) != ("f32", "float32", "float32")
    out = []
    for s in samples:
        seq = s["prompt"] + s["tokens"]
        n0, n = len(s["prompt"]), len(s["tokens"])
        padded = -(-(n0 + window) // REFERENCE_PAD) * REFERENCE_PAD
        tokens = np.zeros((padded,), np.int32)
        tokens[:len(seq)] = seq
        tokens = jnp.asarray(tokens)
        if memo is not None and id(s) in memo:
            rows, routed = memo[id(s)]
        else:
            rows, routed = latent_moe.forward(
                weights, tokens, model, first_row=n0 - 1, n_rows=window)
            rows = rows[:n]
            if memo is not None:
                memo[id(s)] = rows, routed
        picked, routing = jnp.asarray(s["tokens"], jnp.int32), s.get("routing")
        if routing is not None:
            at = routing["at"]
            saw = np.moveaxis(routing["saw"], 1, 0)
            chose = np.moveaxis(routing["chose"], 1, 0)
        if control:
            low, low_routed = latent_moe.forward(
                weights, tokens, model, first_row=n0 - 1, n_rows=window,
                mode=mode, router=router, cache=cache)
            picked = jnp.argmax(low[:n], axis=-1)
            if routing is not None:
                saw = [np.asarray(normed)[at] for normed, _ in low_routed]
                chose = [np.asarray(e)[at] for _, e in low_routed]
        best = jnp.max(rows, axis=-1)
        chosen = jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]
        checked = {"gaps": [float(g) for g in np.asarray(best - chosen)],
                   "complete": s["complete"], "routings": 0,
                   "routings_differ": 0, "router_differs": 0}
        if routing is not None:
            (checked["routings"], checked["routings_differ"],
             checked["router_differs"]) = routing_numbers(
                weights, model, routed, at, saw, chose)
        out.append(checked)
    return out


def compare(checked, limits):
    """``correct.compare_serve``'s numbers, the mean of the gaps beside
    the widest, ``routing_mismatch`` and ``router_mismatch`` (module
    docstring); infinite where no checked request has its routing."""
    total = sum(s["routings"] for s in checked)
    share = lambda key: (sum(s[key] for s in checked) / total if total
                         else float("inf"), None)
    gaps = [g for s in checked for g in s["gaps"]]
    numbers = dict(correct.serve_numbers(checked),
                   logit_gap_mean=(float(np.mean(gaps)) if gaps
                                   else float("inf"), None),
                   routing_mismatch=share("routings_differ"),
                   router_mismatch=share("router_differs"))
    return correct._checks(numbers, limits)


def read_trace(path):
    """One pass over an ``.xplane.pb``: (the trace in ``trace_reduce``'s
    neutral form, as ``load_xplane`` gives it; {scope: device self
    seconds inside the traced window} over the first device's
    operations, each under the scope of ``SCOPES`` that its metadata's
    ``tf_op`` names, None where the trace names none). Without
    ``xplane_pb2``: ``load_xplane``'s trace and None."""
    from benchmark import trace_reduce as tr
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:
        harness.say("trace: no xplane_pb2 to read the operations' scopes")
        return tr.load_xplane(path), None
    space = xplane_pb2.XSpace()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    trace, scoped, window = {}, None, None
    for plane in space.planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        if not device and plane.name != tr.HOST_PLANE:
            continue
        names = {k: m.name for k, m in plane.event_metadata.items()}
        lines = {}
        for line in plane.lines:
            if device and line.name != tr.OPS_LINE:
                continue
            events = [(names[ev.metadata_id],
                       line.timestamp_ns + ev.offset_ps * 1e-3,
                       ev.duration_ps * 1e-3) for ev in line.events]
            if not device:
                events = [e for e in events
                          if e[0].startswith(tr.SPAN_PREFIXES)]
                window = next(((s, s + d) for n, s, d in events
                               if n == tr.WINDOW_SPAN), window)
            elif scoped is None:
                scopes = _scopes(plane)
                scoped = [(scopes[ev.metadata_id], s, d) for ev, (_, s, d)
                          in zip(line.events, events)]
            if events:
                lines.setdefault(line.name, []).extend(events)
        trace[plane.name] = lines
    return trace, _scope_seconds(scoped or [], window)


def _scopes(plane):
    """{an event metadata's id: the scope of ``SCOPES`` its operation
    was traced under, '' for none}, from the metadata's ``tf_op``
    (``jit(f)/.../moe_route/dot_general:``)."""
    tf_op = next((k for k, m in plane.stat_metadata.items()
                  if m.name == "tf_op"), None)
    out = {}
    for k, m in plane.event_metadata.items():
        path = next((st.str_value or plane.stat_metadata[st.ref_value].name
                     for st in m.stats if st.metadata_id == tf_op), "")
        parts = path.rstrip(":").split("/")
        out[k] = next((s for s in SCOPES if s in parts), "")
    return out


def _scope_seconds(scoped, window):
    from benchmark import trace_reduce as tr
    if not any(scope for scope, _, _ in scoped):
        return None
    if window is not None:
        w0, w1 = window
        scoped = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                  for n, s, d in scoped if s + d > w0 and s < w1]
    out = {}
    for name, self_ns in tr._self_times(scoped):
        if name:
            out[name] = out.get(name, 0.0) + self_ns * 1e-9
    return out


class ScopedStretch(harness.TracedStretch):
    """``TracedStretch`` whose one pass over the trace (`read_trace`)
    also leaves ``scope_s``."""

    scope_s = None

    def stop(self):
        import shutil
        import jax
        from benchmark import trace_reduce
        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            trace, self.scope_s = read_trace(
                trace_reduce.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace_reduce.reduce_trace(trace)


def setup(cell, seed, spans):
    import jax
    from paddle_tpu import inference, serving
    model_cfg, traffic = cell["model"], cell["traffic"]
    model_config(model_cfg)     # a program without such layers stops here
    t0 = time.perf_counter()
    weights = latent_moe.make_weights(model_cfg, seed)
    jax.block_until_ready(weights)
    t_weights = time.perf_counter() - t0
    model = build_model(model_cfg, weights)
    eng = inference.make_engine(model, max_slots=traffic["max_slots"],
                                n_pages=traffic["kv_pool_pages"],
                                prefill_chunk=traffic["prefill_chunk"])
    fe = serving.FrontEnd(eng)
    tap = RoutingTap(eng)
    jax.block_until_ready(eng.state)
    longest = traffic_gen.longest_request(traffic)
    if longest > model_cfg["max_seq_len"]:
        raise ValueError(f"the mix's longest request ({longest} tokens) "
                         f"passes the model's context")
    if eng.S * -(-(longest + 2) // eng.page) > eng.P:
        raise ValueError(
            f"kv_pool_pages {eng.P} cannot hold the mix's longest request "
            f"({longest} tokens) in all {eng.S} slots at once: a decode "
            f"step could run out of pages")
    pools = {k: (tuple(v.shape), str(v.dtype)) for k, v in eng.state.items()}
    harness.say(
        f"serve: engine {type(eng).__name__}, {eng.kind.name} layers, "
        f"{eng.S} slots, {eng.P} pages of {eng.page}, pools {pools} = "
        f"{sum(a.nbytes for a in eng.state.values())} bytes, prefill "
        f"chunk {eng.prefill_chunk}, in-flight depth {eng.depth}; weights "
        f"({sum(a.nbytes for a in jax.tree_util.tree_leaves(weights))} "
        f"bytes) made in {t_weights:.1f} s, model and engine built in "
        f"{time.perf_counter() - t0 - t_weights:.1f} s")
    rng = np.random.default_rng([int(seed), 0x7761726D])
    t0 = time.perf_counter()
    fe.submit(rng.integers(0, model_cfg["vocab_size"],
                           eng.prefill_chunk + 7).tolist(),
              max_new_tokens=3)
    fe.run()
    harness.say(f"serve: warmed the chunk and decode programs in "
                f"{time.perf_counter() - t0:.1f} s")
    source = traffic_gen.ClosedLoopTraffic(traffic, seed,
                                           model_cfg["vocab_size"])
    return weights, eng, Loop(fe, source, traffic["clients"], spans), tap


def run(env):
    cell, seed, seconds = env["cell"], env["seed"], env["seconds"]
    spans, devices = env["spans"], env["devices"]
    traffic = cell["traffic"]
    weights, eng, loop, tap = setup(cell, seed, spans)

    # ---- ramp: every client in, and holding its first token
    for c in loop.clients:
        loop.submit(c)
    deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
    while loop.waiting_for_first_token() and time.perf_counter() < deadline:
        loop.pump()
    ramp_requests = loop.attempted
    retraces0 = _retraces()

    # ---- the window: whole FrontEnd steps, as serve_closed's
    harness.settle_host()
    setup_s = harness.seconds_since_process_start(env["t0"])
    t0 = time.perf_counter()
    while True:
        t1 = loop.pump()
        if t1 - t0 >= seconds:
            break

    # ---- the traced stretch (a --trace 1 run only): the loop goes on
    reduced, traced, scope_s = None, None, None
    if env["trace"]:
        stretch = ScopedStretch(cell["workload"])
        stretch.start()
        ta = time.perf_counter()
        while loop.pump() < ta + traffic["traced_seconds"]:
            pass
        tb = time.perf_counter()
        reduced = stretch.stop()
        traced, scope_s = (ta, tb), stretch.scope_s

    # ---- close: no new requests; wait for the first token of each one
    # that is out (late is late, not wrong: its wait is in its TTFT)
    loop.submitting = False
    deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
    while loop.waiting_for_first_token() and time.perf_counter() < deadline:
        loop.pump()
    never = [c for c in loop.waiting_for_first_token()
             if t0 <= c.t_submit < t1]
    compiled_in_window = _retraces() - retraces0

    in_window = lambda t: t0 < t <= t1
    n_tokens = sum(1 for t, _, _ in loop.tokens if in_window(t))
    ttft_ms = [s * 1e3 for t, s in loop.ttft if in_window(t)] \
        + [float("inf")] * len(never)
    gap_ms = [s * 1e3 for t, s in loop.gaps if in_window(t)]
    done = [f for f in loop.finished if in_window(f["t_done"])]
    in_steps = [s for s in loop.steps if in_window(s[0])]
    step_ms = np.asarray([s[1] for s in in_steps]) * 1e3
    step_at = in_steps[int(step_ms.argmax())][0] - t0
    prompt_tokens = sum(n for t, n in loop.prefills if in_window(t))
    harness.say(
        f"serve: window {t1 - t0:.3f} s: {n_tokens} tokens delivered, "
        f"{len(ttft_ms)} requests submitted ({len(never)} never answered) "
        f"with {prompt_tokens} prompt tokens, {len(done)} finished, "
        f"{len(gap_ms)} token gaps, {len(in_steps)} FrontEnd steps, "
        f"{ramp_requests} requests before the window, programs traced "
        f"inside the window: {compiled_in_window}; FrontEnd.step ms median "
        f"{np.median(step_ms):.1f}, longest {step_ms.max():.1f} "
        f"({step_at:.1f} s into the window)")
    live_tokens = [sum(s[3]) for s in in_steps]
    token_bytes = work_latent_moe.cache_bytes_per_token(cell["model"])
    pool_tokens = eng.P * eng.page
    harness.say(
        f"serve: live cached tokens in the window: mean "
        f"{np.mean(live_tokens):.0f} = "
        f"{np.mean(live_tokens) * token_bytes:.0f} bytes of latent rows, "
        f"most {max(live_tokens)}, of a pool of {pool_tokens} tokens = "
        f"{pool_tokens * token_bytes} bytes")

    device = harness.device_info(devices)
    samples = checked_samples(done, traffic["checked_requests"], seed, tap)
    failed = loop.failed + len(never)
    attempted = loop.attempted
    counters = {
        "window": (t0, t1), "traced": traced, "steps": loop.steps,
        "tokens": loop.tokens, "prefills": loop.prefills,
        "ttft_ms": ttft_ms, "gap_ms": gap_ms, "slots": eng.S,
        "kv_pool_tokens": pool_tokens, "scope_s": scope_s,
        "compiled_in_window": compiled_in_window,
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    # free the program's state (the weights are the benchmark's own:
    # the engine scanned over the maker's stacks, and the reference
    # takes them as they are)
    weights_lib.free((eng.state, eng.kp, eng.vp))
    del eng, loop, tap

    t_ref = time.perf_counter()
    checked = reference_gaps(cell, weights, samples)
    harness.say(f"serve: reference over {len(samples)} requests, "
                f"{sum(len(s['prompt']) for s in samples)} prompt and "
                f"{sum(len(s['tokens']) for s in samples)} served tokens, "
                f"{sum(1 for s in samples if 'routing' in s)} with "
                f"{sum(c['routings'] for c in checked)} routings the "
                f"engine reported, in {time.perf_counter() - t_ref:.1f} s")
    checks = compare(checked, cell["limits"])
    e2e = {"setup_s": setup_s,
           "serve_tokens_per_s": n_tokens / (t1 - t0)}
    if ttft_ms:
        e2e["serve_ttft_p50_ms"] = harness.percentile(ttft_ms, 50)
    return {
        "correct": (failed == 0 and bool(samples)
                    and all(v <= lim for _, v, lim in checks)),
        "attempted": attempted, "failed": failed, "checks": checks,
        "end_to_end": e2e, "device": device, "trace": reduced,
        "counters": counters,
    }
