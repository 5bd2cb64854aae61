"""The plain reference against the program's own model (``gpt.GPT``) at a
tiny size on the CPU, in float32 on both sides: logits, loss and gradients
have to agree to rounding. (On the chip the comparison is the cell's
``correct``; this only shows that the two write down the same model.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights as weights_lib
from benchmark.program import build_model
from benchmark.reference import gpt_dense
from benchmark.tests.conftest import TINY_MODEL


@pytest.fixture(scope="module")
def f32_pair():
    model_cfg = dict(TINY_MODEL, dtype="float32")
    weights = weights_lib.make_weights(model_cfg, seed=2**31 + 7)
    model = build_model(model_cfg, weights, remat=False)
    tokens = np.random.default_rng(0).integers(
        0, model_cfg["vocab_size"], (2, 256), dtype=np.int32)
    return model_cfg, weights, model, tokens


def test_logits_agree(f32_pair):
    model_cfg, weights, model, tokens = f32_pair
    ref = gpt_dense.forward_logits(weights, jnp.asarray(tokens),
                                   model_cfg["n_heads"])
    with jax.default_matmul_precision("highest"):
        got = model(jnp.asarray(tokens))
    assert ref.shape == got.shape == (2, 256, model_cfg["vocab_size"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0, atol=2e-4)


def test_loss_and_gradient_norms_agree(f32_pair):
    from paddle_tpu.models import gpt
    model_cfg, weights, model, tokens = f32_pair
    hp = {"learning_rate": 1e-4, "beta1": 0.9, "beta2": 0.999,
          "epsilon": 1e-8, "weight_decay": 0.01, "moment_dtype": "float32"}
    ref = gpt_dense.train_steps(weights, [tokens], model_cfg["n_heads"], hp)
    params, _ = model.split_params()

    def loss_fn(p):
        return gpt.lm_loss(model.merge_params(p)(jnp.asarray(tokens)),
                           jnp.asarray(tokens))

    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn)(params)
    assert float(loss) == pytest.approx(ref["loss"][0], rel=1e-5)
    for name in ("wte", "wpe", "lnf_scale"):
        norm = float(jnp.linalg.norm(grads[name]))
        assert norm == pytest.approx(ref["grad_norm"][name], rel=1e-3)
    wo = float(jnp.linalg.norm(grads["blocks.item_1.wo"]))
    assert wo == pytest.approx(ref["grad_norm"]["layers.1.wo"], rel=1e-3)
    d = model_cfg["d_model"]
    k_bias = float(jnp.linalg.norm(grads["blocks.item_0.bqkv"][d:2 * d]))
    assert k_bias < 1e-3 * ref["grad_norm"]["layers.0.bqkv.q"]
    assert ref["grad_norm"]["layers.0.bqkv.k"] \
        < 1e-3 * ref["grad_norm"]["layers.0.bqkv.q"]


def test_weights_are_a_function_of_the_seed():
    a = weights_lib.make_weights(TINY_MODEL, 5)
    b = weights_lib.make_weights(TINY_MODEL, 5)
    c = weights_lib.make_weights(TINY_MODEL, 5 + 2**31)
    assert bool(jnp.all(a["wte"] == b["wte"]))
    assert not bool(jnp.all(a["wte"] == c["wte"]))
    assert a["layers"][1]["wqkv"].dtype == jnp.bfloat16
    assert a["layers"][1]["ln1_scale"].dtype == jnp.float32
