#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip: GPT-3 1.3B at its published width and depth through the two entry
points a user calls, in ONE process.

    python chip_smoke.py              one TPU chip: train, then serve
    python chip_smoke.py --chips 4    four chips: ONLY the GSPMD train step
                                      and the one-chip step it is compared
                                      with

Phases (each a hard failure — the first that fails ends the run non-zero
and no result line is printed):

1. train — ``gpt.GPT(gpt.gpt3_1p3b(remat=True))``, AdamW with bf16 moments,
   ``init_train_state(stacked=True)``, ``build_train_step`` (bench.py's
   recipe at ``LEARNING_RATE``); batch 4 x 2048 tokens from ``--seed``;
   compile, then 3 steps on the repeated batch. The
   loss is finite at every step and lower at step 3 than at step 1. The
   compiled program must contain the flash-attention and fused-CE Pallas
   kernels (a quiet XLA route does not pass).
2. serve — the same widths, ``serving.FrontEnd`` over the DEFAULT
   ``PagedDecodeEngine`` (page 128, 8 slots, a pool of 8 x 640 tokens); 8
   requests with prompts spread over 32..512 tokens, submitted while earlier
   ones decode, 64 greedy tokens each. Every request finishes with 64 tokens
   and no failure; two are compared token for token with ``gpt.generate``
   on the same weights. Where a stream parts from the reference the
   position and the reference's top-2 logit margin there are printed, and
   the run fails unless that margin is a bf16 tie (``TIE_ULPS``). The decode
   program must contain the paged attention kernel.
3. ``--chips 4`` only — the GSPMD step on ``init_mesh(fsdp=2, tp=2)`` with
   the stacked state, 3 steps, its first loss within ``SHARDED_LOSS_TOL`` of
   the one-chip step's on the same batch; parameters and optimizer state
   are shown (``addressable_shards``) to be spread over all four devices.

The script refuses to start without a TPU, starts no child process, and
the LAST line of its standard output is one JSON object naming the device
as JAX reports it. Weights are random, made from ``--seed``.
"""

import argparse
import json
import math
import sys
import time

import numpy as np

TRAIN_BATCH = 4
TRAIN_STEPS = 3
#: bench.py's recipe but for the learning rate. With no warm-up, AdamW's
#: first updates are full-size sign steps: at bench.py's 1e-4 the 1.3B loss
#: on a repeated batch went 11.24, 10.79, 11.27, 10.54 (third step
#: overshoots), at 1e-5 it went 11.24, 10.81, 10.45, 10.01, falling at every
#: step (one v5e, measured in PR 21). The smoke asks whether training
#: descends, so it takes the rate at which three steps can show it.
LEARNING_RATE = 1e-5
SLOTS = 8
PAGE = 128
POOL_TOKENS_PER_SLOT = 640
PROMPT_LENS = (32, 96, 160, 224, 288, 352, 448, 512)   # spread over 32..512
NEW_TOKENS = 64
COMPARED = (1, 6)      # indices into PROMPT_LENS checked against gpt.generate
#: a stream may part from the reference only where the reference itself
#: could not tell its two best tokens apart in bf16: their logit margin is
#: at most this many bf16 spacings (2**-7 relative) of the top logit. The
#: two decode paths round differently (paged online softmax vs one XLA
#: softmax), which moves a bf16 logit by about one spacing.
TIE_ULPS = 4
#: one-chip vs sharded first-step loss (a mean over ~8k tokens of a value
#: near ln(50304) = 10.8): the two programs differ in kernels (flash + fused
#: CE vs XLA attention + vocab-parallel CE) and in reduction order
SHARDED_LOSS_TOL = 0.05


def say(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def model_config():
    from paddle_tpu.models import gpt
    return gpt.gpt3_1p3b(remat=True)


def _require_tpu(jax, chips):
    """First thing the run does: no TPU, no run (and no result line)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found platform "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run")
    if len(jax.devices()) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} devices, JAX "
            f"found {len(jax.devices())}; nothing was run")


def _require_kernels(hlo_text, names, what):
    """The compiled program must CONTAIN the named Pallas kernels — read
    off the lowered text, so a route that quietly went through XLA
    cannot pass."""
    calls = [ln for ln in hlo_text.splitlines()
             if "tpu_custom_call" in ln]
    for name in names:
        check(any(name in ln for ln in calls),
              f"{what}: no `{name}` Pallas kernel in the compiled "
              f"program ({len(calls)} custom calls found)")
    say(f"{what}: kernels in the compiled program: {', '.join(names)}")


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _free(*trees):
    import jax
    for leaf in jax.tree_util.tree_leaves(trees):
        if hasattr(leaf, "delete"):
            leaf.delete()


def _optimizer():
    import jax.numpy as jnp
    from paddle_tpu import optimizer as optim
    return optim.AdamW(learning_rate=LEARNING_RATE, weight_decay=0.01,
                       moment_dtype=jnp.bfloat16)


def _batch(cfg, seed):
    import jax
    import jax.numpy as jnp
    tokens = jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (TRAIN_BATCH, cfg.max_seq_len)), jnp.int32)
    return tokens, jax.random.PRNGKey(seed)


def _print_block_sizes(cfg):
    """Block sizes come from code defaults (flash's autotune cache was
    cleared: no earlier sweep on this disk, and no timing sweep here,
    picks them; the paged kernels read no cache)."""
    from paddle_tpu.ops.pallas.flash_attention import _DEFAULT_BLOCKS
    from paddle_tpu.ops.pallas.fused_ce import _pick_block_v
    from paddle_tpu.ops.pallas.paged_attention import _default_head_block
    hb = _default_head_block(PAGE, cfg.kv_heads, cfg.head_dim, cfg.dtype,
                             cfg.n_heads // cfg.kv_heads)
    say(f"kernel blocks (code defaults): flash (block_q, block_k)="
        f"{_DEFAULT_BLOCKS}; fused_ce (block_n, block_v)="
        f"(128, {_pick_block_v(cfg.vocab_size, 512)}); "
        f"paged_append_attend head_block={hb}")


def _train_one_chip(model, seed, steps):
    """The one-chip train recipe (bench.py's): returns the per-step
    losses. Frees its state before returning."""
    import jax
    from paddle_tpu.models import gpt
    cfg = model.cfg
    opt = _optimizer()
    params, opt_state = gpt.init_train_state(model, opt, stacked=True)
    step = gpt.build_train_step(model, opt)
    tokens, rng = _batch(cfg, seed)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens, rng).compile()
    say(f"train: compile {time.perf_counter() - t0:.1f} s")
    _require_kernels(compiled.as_text(),
                     ("flash_attention_fwd", "flash_attention_bwd",
                      "fused_ce_fwd", "fused_ce_bwd_dx",
                      "fused_ce_bwd_dw"), "train step")
    losses, ms = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, loss = compiled(params, opt_state, tokens, rng)
        losses.append(float(loss))          # blocks until the step is done
        ms.append((time.perf_counter() - t0) * 1e3)
    med = float(np.median(ms))
    say(f"train: batch {TRAIN_BATCH} x {cfg.max_seq_len}, losses "
        f"{[round(x, 4) for x in losses]}, step ms "
        f"{[round(x, 1) for x in ms]} (median {med:.1f}), "
        f"{TRAIN_BATCH * cfg.max_seq_len / med * 1e3:.0f} tokens/s, "
        f"peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")
    _free(params, opt_state)
    return losses


def _check_losses(what, losses):
    check(all(math.isfinite(x) for x in losses),
          f"{what}: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"{what}: loss did not fall on the repeated batch: {losses}")


def phase_train(model, seed):
    _check_losses("train", _train_one_chip(model, seed, TRAIN_STEPS))


def _top2_margin(model, prefix):
    """The reference's own logits for the token after ``prefix`` (one
    full forward): (top-1 logit, margin to the runner-up)."""
    import jax.numpy as jnp
    logits = model(jnp.asarray([prefix], jnp.int32))[0, -1]
    top = np.sort(np.asarray(logits.astype(jnp.float32)))[-2:]
    return float(top[1]), float(top[1] - top[0])


def _compare_with_generate(model, prompt, got, label):
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    out = gpt.generate(model, jnp.asarray([prompt], jnp.int32),
                       max_new_tokens=len(got),
                       max_len=len(prompt) + len(got))
    ref = [int(t) for t in np.asarray(out)[0, len(prompt):]]
    part = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b),
                None)
    if part is None:
        say(f"serve: {label}: {len(got)} tokens identical to gpt.generate")
        return
    top, margin = _top2_margin(model, list(prompt) + ref[:part])
    # bf16 keeps 8 significant bits: the spacing of values near `top`
    spacing = 2.0 ** (math.floor(math.log2(max(abs(top), 1e-30))) - 7)
    say(f"serve: {label}: parts from gpt.generate at generated token "
        f"{part} (engine {got[part]}, reference {ref[part]}); reference "
        f"top logit {top:.4f}, top-2 margin {margin:.5f} = "
        f"{margin / spacing:.1f} bf16 spacings (tie threshold {TIE_ULPS})")
    check(margin <= TIE_ULPS * spacing,
          f"serve: {label} parts from the reference at token {part} where "
          f"the reference's top-2 margin ({margin:.5f}) is not a bf16 tie")


def phase_serve(model, seed):
    import jax
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    from paddle_tpu.serving import FrontEnd
    cfg = model.cfg
    rs = np.random.RandomState(seed + 1)
    prompts = [[int(t) for t in rs.randint(0, cfg.vocab_size, n)]
               for n in PROMPT_LENS]
    eng = PagedDecodeEngine(model, n_pages=SLOTS * POOL_TOKENS_PER_SLOT
                            // PAGE, max_slots=SLOTS, page_size=PAGE)
    say(f"serve: PagedDecodeEngine (page {eng.page}, {eng.S} slots, "
        f"{eng.P} pages)")
    fn, args = eng.dispatch_fn_args()
    _require_kernels(fn.lower(*args).compile().as_text(),
                     ("paged_append_attend",), "decode step")
    t0 = time.perf_counter()
    eng.warmup()          # every (bucket, decode) program, compiled once
    say(f"serve: compile (warmup of {len(eng.buckets)} prefill buckets + "
        f"decode) {time.perf_counter() - t0:.1f} s")

    fe = FrontEnd(eng)
    reqs, step_ms = [], []
    t0 = time.perf_counter()
    while len(reqs) < len(prompts) or fe.busy:
        if len(reqs) < len(prompts):
            # a new arrival every few steps: later prompts are admitted
            # and prefilled while earlier requests are mid-decode
            reqs.append(fe.submit(prompts[len(reqs)],
                                  max_new_tokens=NEW_TOKENS))
        for _ in range(3):
            t1 = time.perf_counter()
            fe.step()
            step_ms.append((time.perf_counter() - t1) * 1e3)
    fe.run()
    dt = time.perf_counter() - t0
    for r, n in zip(reqs, PROMPT_LENS):
        check(r.status == "done" and len(r.tokens) == NEW_TOKENS,
              f"serve: request with prompt {n}: status {r.status!r} "
              f"({r.error}), {len(r.tokens)} of {NEW_TOKENS} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"serve: request with prompt {n}: token out of vocabulary")
    total = sum(len(r.tokens) for r in reqs)
    say(f"serve: {len(reqs)} requests (prompts {list(PROMPT_LENS)}) x "
        f"{NEW_TOKENS} tokens done in {dt:.2f} s = {total / dt:.1f} "
        f"tokens/s end to end (prefill included), {eng.steps} engine "
        f"steps; host time per FrontEnd.step (a step harvests the "
        f"dispatch before last, so in steady state this is the device's "
        f"time per dispatch): median {np.median(step_ms):.1f} ms, max "
        f"{max(step_ms):.1f} ms over {len(step_ms)} steps; "
        f"peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")
    for i in COMPARED:
        _compare_with_generate(model, prompts[i], list(reqs[i].tokens),
                               f"request with prompt {PROMPT_LENS[i]}")


def _assert_spread(tree, devices, what):
    """Every device holds about a quarter of ``tree``'s bytes, and no
    matrix leaf is resident whole on any one device."""
    import jax
    per_dev = {d.id: 0 for d in devices}
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        total += leaf.nbytes
        shards = leaf.addressable_shards
        for s in shards:
            per_dev[s.device.id] += s.data.nbytes
        if leaf.ndim >= 2 and leaf.nbytes >= 2 ** 20:
            check(len(shards) == len(devices)
                  and all(s.data.nbytes < leaf.nbytes for s in shards),
                  f"{what}: leaf {jax.tree_util.keystr(path)} "
                  f"{leaf.shape} is whole on a device")
    share = {d: round(b / total, 4) for d, b in per_dev.items()}
    say(f"{what}: {total / 2**30:.2f} GiB in all, share held per device "
        f"{share}")
    check(max(per_dev.values()) <= 0.30 * total,
          f"{what}: a device holds more than 0.30 of the bytes: {share}")


def phase_sharded(model, seed):
    import jax
    from paddle_tpu.distributed import mesh as mesh_lib
    from paddle_tpu.models import gpt
    cfg = model.cfg
    devices = jax.devices()[:4]
    loss_one = _train_one_chip(model, seed, 1)[0]

    topo = mesh_lib.init_mesh(fsdp=2, tp=2, devices=devices)
    try:
        opt = _optimizer()
        params, opt_state = gpt.init_train_state(model, opt, topo.mesh,
                                                 stacked=True)
        _assert_spread(params, devices, "sharded params")
        _assert_spread(opt_state, devices, "sharded optimizer state")
        step = gpt.build_train_step(model, opt, topo.mesh)
        tokens, rng = _batch(cfg, seed)
        losses, ms = [], []
        for _ in range(TRAIN_STEPS):
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, tokens, rng)
            losses.append(float(loss))
            ms.append((time.perf_counter() - t0) * 1e3)
        say(f"sharded: mesh fsdp=2 x tp=2, losses "
            f"{[round(x, 4) for x in losses]}, step ms "
            f"{[round(x, 1) for x in ms]} (the first includes the "
            f"compile), one-chip first loss {loss_one:.4f}, "
            f"peak_bytes_in_use per device "
            f"{[_peak_bytes(d) for d in devices]}")
        _assert_spread(params, devices, "sharded params after 3 steps")
        _assert_spread(opt_state, devices,
                       "sharded optimizer state after 3 steps")
        _check_losses("sharded", losses)
        check(abs(losses[0] - loss_one) <= SHARDED_LOSS_TOL,
              f"sharded: first loss {losses[0]:.4f} vs one-chip "
              f"{loss_one:.4f} differ by more than {SHARDED_LOSS_TOL}")
    finally:
        mesh_lib.set_topology(None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the sharded train step and the "
                         "one-chip step it is compared with")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, batch and prompts are made from it")
    args = ap.parse_args(argv)

    import jax
    _require_tpu(jax, args.chips)

    from paddle_tpu import compile_cache
    from paddle_tpu.models import gpt
    from paddle_tpu.ops.pallas import autotune
    say(f"jax {jax.__version__}, {len(jax.devices())} x "
        f"{jax.devices()[0].device_kind}; compile cache at "
        f"{compile_cache.enable()}")
    autotune.get_cache().clear()
    cfg = model_config()
    _print_block_sizes(cfg)
    t0 = time.perf_counter()
    model = gpt.GPT(cfg, seed=args.seed)
    say(f"model: d_model {cfg.d_model}, {cfg.n_layers} layers, "
        f"{cfg.n_heads} heads, seq {cfg.max_seq_len}, vocab "
        f"{cfg.vocab_size}, {np.dtype(cfg.dtype).name}, "
        f"{cfg.num_params() / 1e9:.2f}B parameters, built in "
        f"{time.perf_counter() - t0:.1f} s")

    if args.chips == 4:
        phase_sharded(model, args.seed)
    else:
        phase_train(model, args.seed)
        phase_serve(model, args.seed)

    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
