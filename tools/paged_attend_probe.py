#!/usr/bin/env python3
"""The paged attend against its geometry and the live pages: the time of
one `paged_append_attend` call (write + attend) and of the attend alone,
in a scan over 24 layers at GPT-3 XL's shapes (16 slots, 16 KV heads of
128, pages of 128, a 16-column table), for every head block and for every
count of heads folded at once, at three fills of the table. Needs a TPU; run
through ``chiprun``:

    python3 tools/paged_attend_probe.py [--other <paged_attention.py>]

``--other`` times another copy of the module (the parent commit's, say)
at ITS default geometry beside this tree's. Prints one JSON line.
"""

import argparse
import importlib.util
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAYERS, SLOTS, HEADS, HEAD_DIM, PAGE, COLUMNS, POOL = 24, 16, 16, 128, 128, 16, 144
# live tokens a slot: chat16's mean, decode-heavy's spread, a full table
FILLS = {"chat16": (83, 83), "decode-heavy": (64, 1088),
         "full": (COLUMNS * PAGE - 1, COLUMNS * PAGE - 1)}


def per_call_ms(mod, append, lengths, table, pools, hb, iters):
    """Median ms of one layer's call inside a jitted scan over the
    layers of the folded pools."""
    import jax
    import jax.numpy as jnp
    q = jnp.ones((SLOTS, HEADS, HEAD_DIM), jnp.bfloat16)

    def step(kp, vp, lengths):
        def layer(carry, i):
            h, kp, vp = carry
            if append:
                o, kp, vp = mod.paged_append_attend(
                    q + h, kp, vp, q, q, i * POOL + table,
                    i * POOL + table[:, 0], lengths, head_block=hb)
            else:
                o = mod.paged_decode_attention(
                    q + h, kp, vp, i * POOL + table, lengths,
                    head_block=hb)
            return (o, kp, vp), None
        (h, kp, vp), _ = jax.lax.scan(layer, (jnp.zeros_like(q), kp, vp),
                                      jnp.arange(LAYERS))
        return h, kp, vp

    fn = jax.jit(step, donate_argnums=(0, 1))
    kp, vp = pools
    times = []
    for i in range(iters + 2):
        t0 = time.perf_counter()
        h, kp, vp = fn(kp, vp, lengths)
        h.block_until_ready()
        if i >= 2:
            times.append(time.perf_counter() - t0)
    pools[:] = [kp, vp]
    return statistics.median(times) * 1e3 / LAYERS


def _require_tpu(jax):
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_attend_probe needs a TPU: a time read here would "
                 "be the interpreter's")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--other", default=None)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147650301)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    _require_tpu(jax)
    from paddle_tpu.ops.pallas import paged_attention as here
    mods = {"here": here}
    if args.other:
        spec = importlib.util.spec_from_file_location("_paged_other",
                                                      args.other)
        mods["other"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mods["other"])
    rng = np.random.default_rng(args.seed)
    n = LAYERS * POOL + 1
    pools = [jnp.asarray(rng.standard_normal((n, HEADS, PAGE, HEAD_DIM)),
                         jnp.bfloat16) for _ in range(2)]
    # each slot owns POOL // SLOTS pages of every layer; the table's
    # later columns repeat the last of them and only "full" reads them
    own = np.arange(SLOTS * (POOL // SLOTS)).reshape(SLOTS, -1)
    cols = np.minimum(np.arange(COLUMNS), own.shape[1] - 1)
    table = jnp.asarray(rng.permutation(POOL)[own[:, cols]], jnp.int32)
    out = {"device": jax.devices()[0].device_kind, "ms_a_call": {}}
    # the default geometry against the gather reference, on the chip
    lens = jnp.asarray(rng.integers(1, own.shape[1] * PAGE, SLOTS), jnp.int32)
    q = jnp.asarray(rng.standard_normal((SLOTS, HEADS, HEAD_DIM)),
                    jnp.bfloat16)
    got = here.paged_decode_attention(q, pools[0], pools[1], table, lens)
    want = here.paged_decode_attention_reference(q, pools[0], pools[1],
                                                 table, lens)
    out["max_abs_gap_to_reference"] = float(jnp.max(jnp.abs(
        got.astype(jnp.float32) - want.astype(jnp.float32))))
    for fill, (lo, hi) in FILLS.items():
        lengths = jnp.asarray(rng.integers(lo, hi + 1, SLOTS), jnp.int32)
        row = out["ms_a_call"][fill] = {
            "live_pages": int(np.sum(-(-(np.asarray(lengths) + 1) // PAGE)))}
        for name, mod in mods.items():
            blocks = [None]
            if mod is here:
                blocks += [1, 2, 4, 8, 16]
            for hb in blocks:
                row[f"{name}.hb{hb}"] = {
                    "append_attend": per_call_ms(mod, True, lengths, table,
                                                 pools, hb, args.iters),
                    "attend": per_call_ms(mod, False, lengths, table,
                                          pools, hb, args.iters)}
        # heads folded at once, at the default geometry (a constant of
        # the module: what it costs to compile is not read here)
        kept = here._HEAD_CHUNK
        for chunk in (1, 2, 4, 8, 16):
            here._HEAD_CHUNK = chunk
            row[f"here.default.chunk{chunk}"] = {
                "append_attend": per_call_ms(here, True, lengths, table,
                                             pools, None, args.iters),
                "attend": per_call_ms(here, False, lengths, table, pools,
                                      None, args.iters)}
        here._HEAD_CHUNK = kept
        print(fill, json.dumps(row), file=sys.stderr, flush=True)
    out["default_head_block"] = here._default_head_block(
        PAGE, HEADS, HEAD_DIM, jnp.bfloat16, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
