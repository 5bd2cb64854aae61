"""The control, kept at a size a test run can hold. The control is the
reference put in the program's place with every matrix product in the
nearest precision below the bfloat16 the configurations state: float8
(e4m3) or int8, one scale per tensor. On the chip, at each cell's own
size, a control comes out as not correct under the cell's limits; WHICH
one does is the cell's own (PERF.md section 2 has the readings): float8
fails the serving cell's ``logit_gap`` and GPT-3 XL's ``grad_norm_gap``;
where the bfloat16 program itself reads as far from float32 as float8
does (the training cells' norms, see PERF.md section 7), the control that
sets the upper reading is int8. At this size the same comparison has to
tell the two precisions apart: float8 reads at least three times what the
configuration's own precision reads, on some number the cell compares, and
the configuration's own precision stays correct under the cell's limits."""

import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import correct, weights as weights_lib
from benchmark.kinds import serve_closed, train
from benchmark.reference import gpt_dense

SEEDS = (2**31 + 1, 2**31 + 2, 2**31 + 3)


@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_is_told_apart(tiny_train_cell, seed):
    cell = tiny_train_cell
    job = cell["traffic"]
    weights = weights_lib.make_weights(cell["model"], seed)
    batches = train.make_batches(seed, job["checked_steps"], job["batch"],
                                 job["seq_len"], cell["model"]["vocab_size"])
    reference = train.reference_readings(cell, weights, batches)
    control = correct.compare_train(
        train.reference_readings(cell, weights, batches, mode="fp8"),
        reference, cell["limits"])
    own = correct.compare_train(
        train.reference_readings(cell, weights, batches, mode="bf16"),
        reference, cell["limits"])
    assert all(value <= limit for _, value, limit in own)
    assert any(c[1] >= 3 * o[1] and c[1] > 0 for c, o in zip(control, own))


@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_is_told_apart(tiny_serve_cell, seed):
    cell = tiny_serve_cell
    vocab, n_heads = cell["model"]["vocab_size"], cell["model"]["n_heads"]
    weights = weights_lib.make_weights(cell["model"], seed)
    rng = np.random.default_rng(seed)
    samples = []
    for n_prompt in (24, 64, 96):
        # served tokens = the reference's own greedy continuation, so the
        # reference reads gap 0 everywhere and only a lower precision's
        # picks can open one
        seq = rng.integers(0, vocab, n_prompt).tolist()
        for _ in range(16):
            padded = jnp.asarray([seq + [0] * (256 - len(seq))], jnp.int32)
            logits = gpt_dense.forward_logits(weights, padded, n_heads)
            seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
        samples.append({"prompt": seq[:n_prompt], "tokens": seq[n_prompt:],
                        "complete": True})
    read = lambda mode: correct.serve_numbers(
        serve_closed.reference_gaps(cell, weights, samples, mode=mode))
    assert read("f32")["logit_gap"][0] == 0.0
    assert read("bf16")["logit_gap"][0] <= cell["limits"]["logit_gap"]
    # every position of the three sequences is read, prompts too
    whole = [{"prompt": s["prompt"][:1], "complete": True,
              "tokens": s["prompt"][1:] + s["tokens"]} for s in samples]
    control = correct.serve_numbers(
        serve_closed.reference_gaps(cell, weights, whole, mode="fp8"))
    own = correct.serve_numbers(
        serve_closed.reference_gaps(cell, weights, whole, mode="bf16"))
    assert control["logit_gap"][0] > 0
    assert control["logit_gap"][0] >= 3 * own["logit_gap"][0]
