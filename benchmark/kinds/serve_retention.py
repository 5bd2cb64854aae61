"""Traffic kind ``serve_retention``: ``serve_closed``'s closed loop (its
``Loop``, ``pick_samples`` and window, by import) over a model of
power-retention layers: the benchmark's weights come from
``reference/power_retention.py`` (a layer at a time), the model from
``program_retention.py``, and the plain reference is that file's attention
form. The engine is what ``inference.make_engine`` gives by default: the
paged engine with no page pool and a per-slot state pool, which prefills
a prompt of any length in chunks of the traffic file's ``prefill_chunk``.

Set-up: weights, model, engine, one warm-up request of more than one
chunk (the chunk program and the decode program), then the ramp: every
client submits, and the window opens when each holds its first token.

After the window the reference runs once over each sampled request
(prompt + served tokens, padded to a multiple of ``REFERENCE_PAD`` so
that a few lengths share their programs) and gives the logits of the
served positions only.
"""

import time

import numpy as np

from benchmark import correct, harness, traffic_gen, work_retention
from benchmark import weights as weights_lib
from benchmark.kinds.serve_closed import (FIRST_TOKEN_WAIT_S, Loop,
                                          _retraces, pick_samples)
from benchmark.program_retention import build_model, model_config
from benchmark.reference import power_retention

REFERENCE_PAD = 4096


def reference_gaps(cell, weights, samples, mode="f32", state="float32"):
    """For each sampled request the gap, at every served position,
    between the reference's best logit and the served token's; with a
    control (``mode`` / ``state`` below the configuration's) the gap of
    the token that the control puts first."""
    import jax.numpy as jnp
    model, traffic = cell["model"], cell["traffic"]
    window = max(traffic_gen.quantile_lengths(traffic["answer_len"],
                                              traffic["request_pool"]))
    out = []
    for s in samples:
        seq = s["prompt"] + s["tokens"]
        n0, n = len(s["prompt"]), len(s["tokens"])
        padded = -(-(n0 + window) // REFERENCE_PAD) * REFERENCE_PAD
        tokens = np.zeros((padded,), np.int32)
        tokens[:len(seq)] = seq
        tokens = jnp.asarray(tokens)
        rows = power_retention.forward_logits(
            weights, tokens, model, first_row=n0 - 1, n_rows=window)[:n]
        if mode == "f32" and state == "float32":
            picked = jnp.asarray(s["tokens"], jnp.int32)
        else:
            low = power_retention.forward_logits(
                weights, tokens, model, first_row=n0 - 1, n_rows=window,
                mode=mode, state=state)[:n]
            picked = jnp.argmax(low, axis=-1)
        best = jnp.max(rows, axis=-1)
        chosen = jnp.take_along_axis(rows, picked[:, None], axis=-1)[:, 0]
        out.append({"gaps": [float(g) for g in np.asarray(best - chosen)],
                    "complete": s["complete"]})
    return out


def setup(cell, seed, spans):
    import jax
    from paddle_tpu import inference, serving
    model_cfg, traffic = cell["model"], cell["traffic"]
    model_config(model_cfg)     # a program without such layers stops here
    t0 = time.perf_counter()
    weights = power_retention.make_weights(model_cfg, seed)
    jax.block_until_ready(weights)
    t_weights = time.perf_counter() - t0
    model = build_model(model_cfg, weights)
    eng = inference.make_engine(model, max_slots=traffic["max_slots"],
                                prefill_chunk=traffic["prefill_chunk"])
    fe = serving.FrontEnd(eng)
    jax.block_until_ready(eng.state)
    longest = traffic_gen.longest_request(traffic)
    if longest > model_cfg["max_seq_len"]:
        raise ValueError(f"the mix's longest request ({longest} tokens) "
                         f"passes the model's context")
    state_bytes = sum(a.nbytes for a in eng.state.values())
    harness.say(
        f"serve: engine {type(eng).__name__}, {eng.kind.name} layers, "
        f"{eng.S} slots, {eng.P} pages, state pools "
        f"{ {k: tuple(v.shape) for k, v in eng.state.items()} } = "
        f"{state_bytes} bytes, prefill chunk {eng.prefill_chunk}, "
        f"in-flight depth {eng.depth}; weights made in {t_weights:.1f} s, "
        f"model and engine built in "
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
    return weights, eng, Loop(fe, source, traffic["clients"], spans)


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

    # ---- the window: whole FrontEnd steps, as serve_closed's
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
    contexts = [c for s in in_steps for c in s[3]]
    harness.say(
        f"serve: live contexts in the window: mean "
        f"{np.mean(contexts):.0f} tokens, longest {max(contexts)}; the "
        f"state of a slot is "
        f"{work_retention.state_bytes_per_slot(cell['model'])} bytes at "
        f"any of them")

    device = harness.device_info(devices)
    samples = pick_samples(done, traffic["checked_requests"], seed)
    failed = loop.failed + len(never)
    attempted = loop.attempted
    counters = {
        "window": (t0, t1), "traced": traced, "steps": loop.steps,
        "tokens": loop.tokens, "prefills": loop.prefills,
        "ttft_ms": ttft_ms, "gap_ms": gap_ms, "slots": eng.S,
        "compiled_in_window": compiled_in_window,
        "memory_peak_bytes": device["memory_peak_bytes"],
    }
    # free the program's state (the weights are the benchmark's own:
    # the engine scanned over the maker's stacks, and the reference
    # takes them as they are)
    weights_lib.free((eng.state, eng.kp, eng.vp))
    del eng, loop

    t_ref = time.perf_counter()
    checked = reference_gaps(cell, weights, samples)
    harness.say(f"serve: reference over {len(samples)} requests, "
                f"{sum(len(s['prompt']) for s in samples)} prompt and "
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
