"""Traffic kind ``train``: one training job on one chip, through the entry
points a user calls — ``gpt.init_train_state(stacked=True)`` and
``gpt.build_train_step`` — with the benchmark's own weights loaded into the
model as a checkpoint would be.

Set-up builds ONE compiled step with its state, drives it through the
first ``checked_steps`` steps (the ones the plain reference follows), and
hands the same object to the window. Every batch is fresh, made on the host
from ``--seed`` before the window (no input pipeline is timed); every row
differs. A step is "dispatch, then fetch the loss", as a loop that logs its
loss runs it.
"""

import contextlib
import functools
import math
import time

import numpy as np

from benchmark import correct, harness, weights as weights_lib
from benchmark.program import build_model
from benchmark.reference import gpt_dense


def _optimizer(spec):
    import jax.numpy as jnp
    from paddle_tpu import optimizer as optim
    if spec["name"] != "AdamW":
        raise ValueError(f"train kind knows AdamW, not {spec['name']!r}")
    return optim.AdamW(
        learning_rate=spec["learning_rate"], beta1=spec["beta1"],
        beta2=spec["beta2"], epsilon=spec["epsilon"],
        weight_decay=spec["weight_decay"],
        moment_dtype=jnp.dtype(spec["moment_dtype"]))


def make_batches(seed, n, batch, seq_len, vocab):
    rng = np.random.default_rng([int(seed), 0x7261696E])
    return rng.integers(0, vocab, size=(n, batch, seq_len), dtype=np.int32)


# ----------------------------------------- the program's state, leaf by leaf
@functools.lru_cache(maxsize=None)
def _norm_fns():
    import jax
    import jax.numpy as jnp

    def split_norms(name, x, out, axes_from):
        for sub, part in gpt_dense.split_qkv_leaves(name, x).items():
            out[sub] = jnp.sqrt(jnp.sum(
                jnp.square(part.astype(jnp.float32)),
                axis=tuple(range(axes_from, part.ndim))))

    @jax.jit
    def norms(top, stacked):
        """top: {leaf: array}; stacked: {leaf: (L, ...)} -> norms, per
        layer for the stacked ones."""
        out_top, out_stacked = {}, {}
        for name, x in top.items():
            split_norms(name, x, out_top, 0)
        for name, x in stacked.items():
            split_norms(name, x, out_stacked, 1)
        return out_top, out_stacked

    @jax.jit
    def layer_change(stacked, i, old_layer):
        out = {}
        for name, x in stacked.items():
            new = jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False)
            split_norms(name, new.astype(jnp.float32)
                        - old_layer[name].astype(jnp.float32), out, 0)
        return out

    @jax.jit
    def top_change(new, old):
        out = {}
        for name, x in new.items():
            split_norms(name, x.astype(jnp.float32)
                        - old[name].astype(jnp.float32), out, 0)
        return out

    return norms, layer_change, top_change


TOP_LEAVES = ("wte", "wpe", "lnf_scale", "lnf_bias")


def _stacked_dict(block, pick=lambda x: x):
    return {name: pick(getattr(block, name))
            for name in weights_lib.LAYER_LEAVES}


def first_gradient_norms(opt_state, beta1):
    """The norm of the first gradient as the optimizer got it, from its
    state after one step: AdamW's first moment is (1 - beta1) * g then."""
    import jax
    norms, _, _ = _norm_fns()
    slots = opt_state["slots"]
    first = lambda slot: slot[0]
    top, stacked = norms({k: first(slots[k]) for k in TOP_LEAVES},
                         _stacked_dict(slots["_stacked_blocks"], first))
    top, stacked = jax.device_get((top, stacked))
    out = {k: float(v) / (1 - beta1) for k, v in top.items()}
    for name, per_layer in stacked.items():
        for i, v in enumerate(per_layer):
            out[f"layers.{i}.{name}"] = float(v) / (1 - beta1)
    return out


def change_norms(params, weights):
    """Norm of (parameters now - the weights the run started from), for
    every leaf."""
    import jax
    import jax.numpy as jnp
    _, layer_change, top_change = _norm_fns()
    pending = [top_change({k: params[k] for k in TOP_LEAVES},
                          {k: weights[k] for k in TOP_LEAVES})]
    stacked = _stacked_dict(params["_stacked_blocks"])
    for i, old in enumerate(weights["layers"]):
        pending.append(layer_change(stacked, jnp.int32(i), old))
    got = jax.device_get(pending)
    out = {k: float(v) for k, v in got[0].items()}
    for i, layer in enumerate(got[1:]):
        for k, v in layer.items():
            out[f"layers.{i}.{k}"] = float(v)
    return out


# ------------------------------------------------------------------ the run
def setup(cell, seed):
    """Weights, model, state and the jitted step: the ONE object that the
    checked steps and then the window drive."""
    import jax
    from paddle_tpu.models import gpt
    model_cfg, job = cell["model"], cell["traffic"]
    if job["seq_len"] > model_cfg["max_seq_len"]:
        raise ValueError("job's seq_len exceeds the position table")
    t0 = time.perf_counter()
    weights = weights_lib.make_weights(model_cfg, seed)
    jax.block_until_ready(weights)
    t1 = time.perf_counter()
    model = build_model(model_cfg, weights, job["remat"])
    opt = _optimizer(job["optimizer"])
    params, opt_state = gpt.init_train_state(model, opt, stacked=True)
    step = gpt.build_train_step(model, opt)
    jax.block_until_ready((params, opt_state))
    harness.say(f"train: weights made in {t1 - t0:.1f} s, model and state "
                f"built in {time.perf_counter() - t1:.1f} s")
    harness.say(harness.kernel_blocks(model_cfg, job["batch"],
                                      job["seq_len"]))
    return {
        "cell": cell, "weights": weights, "step": step, "params": params,
        "opt_state": opt_state, "rng": jax.random.PRNGKey(0),
        "checked": job["checked_steps"], "losses": [],
    }


def drive_checked_steps(run, batches):
    """The first steps, through the window's own call and feed."""
    job = run["cell"]["traffic"]
    t0 = time.perf_counter()
    for i in range(run["checked"]):
        loss = one_step(run, batches[i])
        run["losses"].append(loss)
        if i == 0:
            harness.say(f"train: first step (compiles unless cached) "
                        f"{time.perf_counter() - t0:.1f} s")
            run["grad_norm"] = first_gradient_norms(
                run["opt_state"], job["optimizer"]["beta1"])
    run["change_norm"] = change_norms(run["params"], run["weights"])


def one_step(run, tokens, spans=None):
    """Dispatch, then fetch the loss; in the window each half is a span."""
    import jax.numpy as jnp
    span = spans.span if spans is not None else (
        lambda name: contextlib.nullcontext())
    with span("bench/step"):
        run["params"], run["opt_state"], loss = run["step"](
            run["params"], run["opt_state"], jnp.asarray(tokens), run["rng"])
    with span("bench/loss_fetch"):
        return float(loss)


def _programs(step) -> int:
    """How many programs the jitted step holds (a rise inside the window
    is a compilation inside the window)."""
    size = getattr(step, "_cache_size", None)
    return size() if callable(size) else 0


def program_readings(run):
    return {"loss": run["losses"], "grad_norm": run["grad_norm"],
            "change_norm": run["change_norm"]}


def reference_readings(cell, weights, batches, mode="f32", rows=None):
    job = cell["traffic"]
    return gpt_dense.train_steps(
        weights, [batches[i] for i in range(job["checked_steps"])],
        cell["model"]["n_heads"], job["optimizer"], mode=mode, rows=rows)


def run(env):
    cell, seed, seconds = env["cell"], env["seed"], env["seconds"]
    spans, devices = env["spans"], env["devices"]
    model_cfg, job = cell["model"], cell["traffic"]
    B, S = job["batch"], job["seq_len"]
    state = setup(cell, seed)
    n_batches = state["checked"] + int(math.ceil(seconds * 2.5)) + 8 \
        + job["traced_steps"]
    batches = make_batches(seed, n_batches, B, S, model_cfg["vocab_size"])
    drive_checked_steps(state, batches)
    cursor = state["checked"]

    # ---- the window
    harness.settle_host()
    setup_s = harness.seconds_since_process_start(env["t0"])
    losses, steps, ends = [], 0, []
    programs = _programs(state["step"])
    t_start = time.perf_counter()
    while True:
        losses.append(one_step(state, batches[cursor % n_batches], spans))
        cursor += 1
        steps += 1
        t_end = time.perf_counter()
        ends.append(t_end)
        if t_end - t_start >= seconds:
            break
    window_s = t_end - t_start
    tokens = steps * B * S
    step_ms = np.diff([t_start] + ends) * 1e3
    harness.say(f"train: window {window_s:.3f} s, {steps} whole steps of "
                f"{B} x {S} tokens, loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
                f"step ms median {np.median(step_ms):.1f}, first "
                f"{step_ms[0]:.1f}, longest {step_ms.max():.1f} (step "
                f"{int(step_ms.argmax()) + 1}); programs compiled inside "
                f"the window: {_programs(state['step']) - programs}")

    # ---- the traced stretch (a --trace 1 run only), after the window
    reduced, traced_steps = None, 0
    if env["trace"]:
        stretch = harness.TracedStretch(cell["workload"])
        stretch.start()
        for _ in range(job["traced_steps"]):
            losses.append(one_step(state, batches[cursor % n_batches], spans))
            cursor += 1
            traced_steps += 1
        reduced = stretch.stop()

    device = harness.device_info(devices)
    bad = sum(1 for x in state["losses"] + losses if not math.isfinite(x))
    program = program_readings(state)
    weights = state["weights"]
    weights_lib.free((state["params"], state["opt_state"]))
    state.clear()

    # ---- the plain reference over the checked steps, program state freed
    t0 = time.perf_counter()
    reference = reference_readings(cell, weights, batches)
    harness.say(f"train: reference over {job['checked_steps']} steps in "
                f"{time.perf_counter() - t0:.1f} s")
    checks = correct.compare_train(program, reference, cell["limits"])
    return {
        "correct": bad == 0 and all(v <= lim for _, v, lim in checks),
        "attempted": job["checked_steps"] + steps + traced_steps,
        "failed": bad,
        "checks": checks,
        "end_to_end": {"train_tokens_per_s": tokens / window_s,
                       "setup_s": setup_s},
        "device": device,
        "trace": reduced,
        "counters": {"traced_steps": traced_steps, "window_steps": steps,
                     "tokens_per_step": B * S, "batch": B, "seq_len": S,
                     "memory_peak_bytes": device["memory_peak_bytes"]},
    }
