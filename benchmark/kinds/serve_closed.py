"""Traffic kind ``serve_closed``: a closed loop of ``clients`` callers over
``serving.FrontEnd`` on the engine ``inference.make_engine`` chooses by
default, in one process, with no server and no thread: the loop below IS
the callers. Each client submits its next request the moment its last one
ends; a token is "in the client's hands" when the client finds it after a
``FrontEnd.step``.

Set-up: weights, model, engine, then one warm-up request per prefill
bucket the traffic can hit (the engine's own ``warmup()`` doubles the pool
and compiles suffix programs this traffic never uses), then the ramp: all
clients submit, and the window opens when each has its first token.

The engine's page pool is the traffic file's ``kv_pool_pages`` (the file
says why that many); ``make_engine`` chooses everything else.
"""

import time

import numpy as np

from benchmark import correct, harness, traffic_gen, weights as weights_lib
from benchmark.program import build_model
from benchmark.reference import gpt_dense

FIRST_TOKEN_WAIT_S = 60.0
REFERENCE_PAD = 128             # reference sequences padded to a multiple


class _Client:
    __slots__ = ("index", "req", "prompt", "asked", "t_submit", "seen",
                 "t_last")

    def __init__(self, index):
        self.index = index
        self.req = None


def kv_bytes_per_token(model):
    """Keys and values of one token over all layers, as the cache holds
    them (the configuration's type)."""
    itemsize = {"bfloat16": 2, "float16": 2, "float32": 4}[model["dtype"]]
    return 2 * model["n_layers"] * model["n_heads"] * model["head_dim"] \
        * itemsize


class Loop:
    """The callers' side of the run: submits, pumps, stamps tokens."""

    def __init__(self, fe, traffic_source, n_clients, spans):
        self.fe, self.source, self.spans = fe, traffic_source, spans
        self.clients = [_Client(i) for i in range(n_clients)]
        self.submitting = True
        self.tokens = []        # (t, prompt length, index) per delivered token
        self.ttft = []          # (t_submit, seconds)
        self.gaps = []          # (t, seconds)
        self.finished = []      # dicts, see _retire
        self.steps = []         # (t_end, seconds, live slots, contexts)
        self.prefills = []      # (t_submit, prompt tokens)
        self.failed = 0
        self.attempted = 0

    def submit(self, client):
        prompt, asked = self.source.next_request(client.index)
        client.prompt, client.asked = prompt, asked
        client.seen, client.t_last = 0, None
        client.t_submit = time.perf_counter()
        with self.spans.span("bench/submit"):
            client.req = self.fe.submit(prompt, max_new_tokens=asked)
        self.attempted += 1
        self.prefills.append((client.t_submit, len(prompt)))

    def _retire(self, client, now):
        req = client.req
        ok = (req.status == "done" and len(req.tokens) == client.asked)
        if not ok:
            self.failed += 1
            harness.say(f"serve: request {req.id} ended {req.status!r} "
                        f"({req.error}) with {len(req.tokens)} of "
                        f"{client.asked} tokens")
        self.finished.append({
            "t_done": now, "prompt": client.prompt,
            "tokens": list(req.tokens), "asked": client.asked,
            "complete": ok})
        client.req = None

    def pump(self):
        """One ``FrontEnd.step`` and the clients' look at what it brought."""
        t0 = time.perf_counter()
        with self.spans.span("bench/frontend_step"):
            self.fe.step()
        now = time.perf_counter()
        with self.spans.span("bench/harvest"):
            contexts = []
            for c in self.clients:
                if c.req is None:
                    continue
                n = len(c.req.tokens)
                for j in range(c.seen, n):
                    if j == 0:
                        self.ttft.append((c.t_submit, now - c.t_submit))
                    else:
                        self.gaps.append((now, now - c.t_last))
                    self.tokens.append((now, len(c.prompt), j))
                    c.t_last = now
                c.seen = n
                if c.req.done:
                    self._retire(c, now)
                    if self.submitting:
                        self.submit(c)
                else:
                    contexts.append(len(c.prompt) + n)
            eng = self.fe.engine
            self.steps.append((now, now - t0, eng.S - eng.free_slots,
                               contexts))
        return now

    def waiting_for_first_token(self):
        return [c for c in self.clients if c.req is not None and c.seen == 0]


def reference_gaps(cell, weights, samples, mode="f32"):
    """For each sampled request, run the reference once over its prompt
    with its served tokens, and read at every served position the gap
    between the reference's best logit and the served token's. With
    ``mode`` below float32 it reads instead the gap of the token that the
    lower precision puts first (the control)."""
    import jax.numpy as jnp
    n_heads = cell["model"]["n_heads"]
    traffic = cell["traffic"]
    # one padded length for every request of the mix: one set of programs
    longest = traffic_gen.longest_request(traffic)
    padded = -(-longest // REFERENCE_PAD) * REFERENCE_PAD
    out = []
    for s in samples:
        seq = s["prompt"] + s["tokens"]
        n0, n = len(s["prompt"]), len(s["tokens"])
        tokens = np.zeros((1, padded), np.int32)
        tokens[0, :len(seq)] = seq
        tokens = jnp.asarray(tokens)
        ref = gpt_dense.forward_logits(weights, tokens, n_heads)[0]
        rows = ref[n0 - 1:n0 - 1 + n]                 # predicts token j
        if mode == "f32":
            picked = jnp.asarray(s["tokens"], jnp.int32)
        else:
            low = gpt_dense.forward_logits(weights, tokens, n_heads,
                                           mode=mode)[0]
            picked = jnp.argmax(low[n0 - 1:n0 - 1 + n], axis=-1)
        best = jnp.max(rows, axis=-1)
        chosen = jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]
        out.append({"gaps": [float(g) for g in np.asarray(best - chosen)],
                    "complete": s["complete"]})
    return out


def pick_samples(finished, k, seed):
    """``k`` of the finished requests, drawn from the seed, the longest
    always among them."""
    if not finished:
        return []
    order = sorted(range(len(finished)), key=lambda i: -(
        len(finished[i]["prompt"]) + len(finished[i]["tokens"])))
    rng = np.random.default_rng([int(seed), 0x636865636B])
    rest = rng.permutation(order[1:]).tolist()
    return [finished[i] for i in [order[0]] + rest[:k - 1]]


def setup(cell, seed, spans):
    from paddle_tpu import inference, serving
    model_cfg, traffic = cell["model"], cell["traffic"]
    import jax
    t0 = time.perf_counter()
    weights = weights_lib.make_weights(model_cfg, seed)
    jax.block_until_ready(weights)
    t_weights = time.perf_counter() - t0
    model = build_model(model_cfg, weights, remat=False)
    eng = inference.make_engine(model, max_slots=traffic["max_slots"],
                                n_pages=traffic["kv_pool_pages"])
    fe = serving.FrontEnd(eng)
    jax.block_until_ready((eng.kp, eng.vp))
    longest = traffic_gen.longest_request(traffic)
    if longest > model_cfg["max_seq_len"]:
        raise ValueError(f"the mix's longest request ({longest} tokens) "
                         f"passes the model's context")
    if eng.S * -(-(longest + 2) // eng.page) > eng.P:
        raise ValueError(
            f"kv_pool_pages {eng.P} cannot hold the mix's longest request "
            f"({longest} tokens) in all {eng.S} slots at once: a decode "
            f"step could run out of pages")
    harness.say(
        f"serve: engine {type(eng).__name__}, "
        f"{eng.S} slots, {eng.P} pages of {eng.page}, buckets {eng.buckets}, "
        f"in-flight depth {eng.depth}; weights made in {t_weights:.1f} s, "
        f"model and engine built in "
        f"{time.perf_counter() - t0 - t_weights:.1f} s")
    harness.say(harness.kernel_blocks(model_cfg, page=eng.page))
    # one request per bucket this traffic's prompts can fall into
    prompts = traffic_gen.quantile_lengths(traffic["prompt_len"],
                                           traffic["request_pool"])
    lo, hi = min(prompts), max(prompts)
    rng = np.random.default_rng([int(seed), 0x7761726D])
    t0 = time.perf_counter()
    previous = 0
    for b in eng.buckets:
        if previous < hi and b >= lo:
            n = min(b, hi)
            fe.submit(rng.integers(0, model_cfg["vocab_size"], n).tolist(),
                      max_new_tokens=2)
            fe.run()
        previous = b
    harness.say(f"serve: warmed prefill buckets and decode in "
                f"{time.perf_counter() - t0:.1f} s")
    source = traffic_gen.ClosedLoopTraffic(traffic, seed,
                                           model_cfg["vocab_size"])
    loop = Loop(fe, source, traffic["clients"], spans)
    return weights, eng, loop


def _retraces():
    from paddle_tpu import stats
    return int(stats.get("compile/retrace", 0))


def run(env):
    cell, seed, seconds = env["cell"], env["seed"], env["seconds"]
    spans, devices = env["spans"], env["devices"]
    traffic = cell["traffic"]
    weights, eng, loop = setup(cell, seed, spans)

    # ---- ramp: every client in, and holding its first token
    for c in loop.clients:
        loop.submit(c)
    deadline = time.perf_counter() + FIRST_TOKEN_WAIT_S
    while loop.waiting_for_first_token() and time.perf_counter() < deadline:
        loop.pump()
    ramp_requests = loop.attempted
    retraces0 = _retraces()

    # ---- the window: opens at the end of a step and closes at the end of
    # the step in flight when --seconds have passed, so it holds whole steps
    # (tokens arrive a step's worth at a time: a window cut at a fixed
    # instant would read one step more or less from run to run)
    harness.settle_host()
    setup_s = harness.seconds_since_process_start(env["t0"])
    t0 = time.perf_counter()
    while True:
        t1 = loop.pump()
        if t1 - t0 >= seconds:
            break

    # ---- the traced stretch (a --trace 1 run only): the loop goes on
    reduced, traced = None, None
    if env["trace"]:
        stretch = harness.TracedStretch(cell["workload"])
        stretch.start()
        ta = time.perf_counter()
        while loop.pump() < ta + traffic["traced_seconds"]:
            pass
        tb = time.perf_counter()
        reduced = stretch.stop()
        traced = (ta, tb)

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
    live_tokens = [sum(s[3]) for s in in_steps]
    token_bytes = kv_bytes_per_token(cell["model"])
    pool_tokens = eng.P * eng.page
    harness.say(
        f"serve: window {t1 - t0:.3f} s: {n_tokens} tokens delivered, "
        f"{len(ttft_ms)} requests submitted ({len(never)} never answered), "
        f"{len(done)} finished, {len(gap_ms)} token gaps, "
        f"{sum(1 for s in loop.steps if in_window(s[0]))} FrontEnd steps, "
        f"{ramp_requests} requests before the window, programs traced "
        f"inside the window: {compiled_in_window}; FrontEnd.step ms median "
        f"{np.median(step_ms):.1f}, longest {step_ms.max():.1f} "
        f"({step_at:.1f} s into the window)")
    harness.say(
        f"serve: keys and values live in the window: mean "
        f"{np.mean(live_tokens):.0f} tokens = "
        f"{np.mean(live_tokens) * token_bytes:.0f} bytes, most "
        f"{max(live_tokens)} tokens = {max(live_tokens) * token_bytes} "
        f"bytes, of a pool of {pool_tokens} tokens = "
        f"{pool_tokens * token_bytes} bytes")

    device = harness.device_info(devices)
    samples = pick_samples(done, traffic["checked_requests"], seed)
    failed = loop.failed + len(never)
    attempted = loop.attempted
    counters = {
        "window": (t0, t1), "traced": traced, "steps": loop.steps,
        "tokens": loop.tokens, "prefills": loop.prefills,
        "ttft_ms": ttft_ms, "gap_ms": gap_ms, "slots": eng.S,
        "kv_pool_tokens": pool_tokens,
        "compiled_in_window": compiled_in_window,
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    # free the program's state (the weights are the benchmark's own and
    # the reference takes them as they are)
    weights_lib.free((eng.kp, eng.vp, getattr(eng, "_stacked", None)))
    del eng, loop

    t_ref = time.perf_counter()
    checked = reference_gaps(cell, weights, samples)
    harness.say(f"serve: reference over {len(samples)} requests, "
                f"{sum(len(s['tokens']) for s in samples)} served tokens, "
                f"in {time.perf_counter() - t_ref:.1f} s")
    checks = correct.compare_serve(checked, cell["limits"])
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
