"""Flash-attention Pallas kernel vs the XLA reference path.

Mirrors the reference's fused-attention op tests
(python/paddle/fluid/tests/unittests/test_fused_attention_op.py pattern: a
numpy/naive oracle checked against the fused kernel for output AND grads).
Runs in Pallas interpret mode on the CPU test platform.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.attention import attention_reference
from paddle_tpu.ops.pallas.flash_attention import flash_attention


def _rand_qkv(b, s, h, d, dtype=jnp.float32, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.normal(size=(b, s, h, d)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 128, 2, 64), (2, 256, 2, 32)])
def test_forward_matches_reference(causal, shape):
    q, k, v = _rand_qkv(*shape)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = attention_reference(q, k, v, is_causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_forward_unpadded_seq():
    # seq not a multiple of the block: exercises KV-padding masking
    q, k, v = _rand_qkv(1, 100, 2, 64, seed=3)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_cross_attention_different_kv_len():
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.normal(size=(1, 64, 2, 64)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(1, 200, 2, 64)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(1, 200, 2, 64)), jnp.float32)
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = attention_reference(q, k, v, is_causal=False)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_reference(causal):
    q, k, v = _rand_qkv(1, 128, 2, 64, seed=1)
    cot = jnp.asarray(np.random.RandomState(2).normal(size=q.shape),
                      jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, is_causal=causal) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


def test_grads_unpadded_seq():
    q, k, v = _rand_qkv(1, 100, 1, 32, seed=4)
    cot = jnp.asarray(np.random.RandomState(5).normal(size=q.shape),
                      jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, causal=True, interpret=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(
        attention_reference(*a, is_causal=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


def test_bfloat16_forward():
    q, k, v = _rand_qkv(1, 128, 2, 64, dtype=jnp.bfloat16, seed=6)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_reference(q, k, v, is_causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_jit_compiles():
    q, k, v = _rand_qkv(1, 128, 1, 64, seed=8)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                interpret=True))
    out = f(q, k, v)
    assert out.shape == q.shape


# ---------------------------------------------------------------------------
# v2: kv_lens padding masks, additive bias, deterministic dropout, GQA
# ---------------------------------------------------------------------------


def _padding_bias(kv_lens, sk):
    """(B,) lengths -> additive (B, 1, 1, Sk) -inf mask for the oracle."""
    col = np.arange(sk)[None, :]
    mask = col < np.asarray(kv_lens)[:, None]
    return jnp.asarray(np.where(mask, 0.0, -1e30)[:, None, None, :],
                       jnp.float32)


def test_kv_lens_padding_mask():
    q, k, v = _rand_qkv(3, 160, 2, 64, seed=10)
    kv_lens = jnp.asarray([160, 90, 17], jnp.int32)
    out = flash_attention(q, k, v, kv_lens=kv_lens, interpret=True)
    ref = attention_reference(q, k, v, mask=_padding_bias(kv_lens, 160))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_kv_lens_grads():
    q, k, v = _rand_qkv(2, 128, 2, 32, seed=11)
    kv_lens = jnp.asarray([128, 50], jnp.int32)
    cot = jnp.asarray(np.random.RandomState(12).normal(size=q.shape),
                      jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, kv_lens=kv_lens, interpret=True) * cot), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(attention_reference(
        *a, mask=_padding_bias(kv_lens, 128)) * cot), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("bias_shape", [(1, 1, 128, 128), (2, 1, 128, 128),
                                        (1, 2, 128, 128), (2, 2, 128, 128)])
def test_additive_bias_broadcast_modes(bias_shape):
    q, k, v = _rand_qkv(2, 128, 2, 32, seed=13)
    bias = jnp.asarray(
        np.random.RandomState(14).normal(size=bias_shape), jnp.float32)
    out = flash_attention(q, k, v, bias=bias, interpret=True)
    ref = attention_reference(q, k, v, mask=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_bias_with_causal_and_grads():
    q, k, v = _rand_qkv(1, 128, 2, 32, seed=15)
    bias = jnp.asarray(
        np.random.RandomState(16).normal(size=(1, 2, 128, 128)),
        jnp.float32)
    cot = jnp.asarray(np.random.RandomState(17).normal(size=q.shape),
                      jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, bias=bias, interpret=True) * cot),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(attention_reference(
        *a, is_causal=True, mask=bias) * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("h_q,h_kv", [(4, 2), (4, 1)])
def test_gqa_forward_and_grads(h_q, h_kv):
    rs = np.random.RandomState(18)
    b, s, d = 2, 128, 32
    q = jnp.asarray(rs.normal(size=(b, s, h_q, d)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(b, s, h_kv, d)), jnp.float32)
    group = h_q // h_kv
    k_rep = jnp.repeat(k, group, axis=2)
    v_rep = jnp.repeat(v, group, axis=2)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = attention_reference(q, k_rep, v_rep, is_causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    cot = jnp.asarray(rs.normal(size=out.shape), jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, interpret=True) * cot), argnums=(0, 1, 2))(q, k, v)

    def ref_loss(q, k, v):
        kr = jnp.repeat(k, group, axis=2)
        vr = jnp.repeat(v, group, axis=2)
        return jnp.sum(attention_reference(q, kr, vr, is_causal=True) * cot)

    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name} mismatch")


def test_dropout_deterministic_and_unbiased():
    q, k, v = _rand_qkv(1, 128, 2, 32, seed=19)
    o1 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=42,
                         interpret=True)
    o2 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=42,
                         interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = flash_attention(q, k, v, dropout_p=0.3, dropout_seed=43,
                         interpret=True)
    assert np.abs(np.asarray(o1) - np.asarray(o3)).max() > 1e-4
    # E[dropout(attn)] == attn: mean over many seeds approaches no-dropout
    outs = [flash_attention(q, k, v, dropout_p=0.3, dropout_seed=s,
                            interpret=True) for s in range(24)]
    mean = np.mean([np.asarray(o, np.float64) for o in outs], axis=0)
    base = np.asarray(flash_attention(q, k, v, interpret=True), np.float64)
    assert np.abs(mean - base).mean() < 0.05


def test_dropout_grads_finite_and_match_mask():
    """Backward regenerates the identical keep mask: grads of sum(out)
    computed with dropout must be finite and differ from no-dropout."""
    q, k, v = _rand_qkv(1, 128, 1, 32, seed=20)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, dropout_p=0.25, dropout_seed=7, interpret=True)))(q)
    assert np.isfinite(np.asarray(g)).all()
    g0 = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, interpret=True)))(q)
    assert np.abs(np.asarray(g) - np.asarray(g0)).max() > 1e-6


def test_dropout_seed_traced_no_retrace():
    """Seed is a traced scalar: changing it must not retrigger compilation
    (the training loop changes it every step)."""
    q, k, v = _rand_qkv(1, 128, 1, 32, seed=21)
    calls = []

    @jax.jit
    def f(q, k, v, seed):
        calls.append(1)
        return flash_attention(q, k, v, dropout_p=0.1, dropout_seed=seed,
                               interpret=True)

    f(q, k, v, jnp.int32(1))
    f(q, k, v, jnp.int32(2))
    assert len(calls) == 1


def test_kvlen_zero_row_no_nan():
    q, k, v = _rand_qkv(2, 128, 1, 32, seed=22)
    kv_lens = jnp.asarray([128, 0], jnp.int32)
    out = flash_attention(q, k, v, kv_lens=kv_lens, interpret=True)
    assert np.isfinite(np.asarray(out[0])).all()
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    g = jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, kv_lens=kv_lens, interpret=True)))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_key_only_bias_not_materialized():
    """(B,1,1,Sk) key-padding bias: correct results, and the jaxpr must not
    contain a broadcast to (B, 1, Sq, Sk)."""
    q, k, v = _rand_qkv(2, 128, 2, 32, seed=23)
    bias = jnp.asarray(
        np.where(np.arange(128) < 70, 0.0, -1e30)[None, None, None, :],
        jnp.float32)
    out = flash_attention(q, k, v, bias=bias, interpret=True)
    ref = attention_reference(q, k, v, mask=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # grads through the sq1 bias path
    cot = jnp.asarray(np.random.RandomState(24).normal(size=q.shape),
                      jnp.float32)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, bias=bias, interpret=True) * cot), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(attention_reference(
        *a, mask=bias) * cot), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    # the full (B, H, Sq, Sk) tensor must not appear in the lowered HLO
    txt = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, bias=bias, interpret=True)).lower(q, k, v).as_text()
    assert "2x2x128x128" not in txt and "1x1x128x128" not in txt


def test_sdpa_fallback_honors_kv_lens():
    """scaled_dot_product_attention must apply kv_lens on the XLA fallback
    path too (CPU here), not only in the Pallas kernel."""
    from paddle_tpu.nn.functional.attention import (
        scaled_dot_product_attention)
    q, k, v = _rand_qkv(2, 64, 2, 32, seed=25)
    kv_lens = jnp.asarray([64, 20], jnp.int32)
    out = scaled_dot_product_attention(q, k, v, kv_lens=kv_lens)
    ref = attention_reference(q, k, v, mask=_padding_bias(kv_lens, 64))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# PR 36: what a grid step does follows its block's position (block_plan):
# pairs above the diagonal get no step, pairs under it no mask, and the
# backward is one kernel where float32 dQ for the sequence fits VMEM
# ---------------------------------------------------------------------------

# the module: the package's attribute of that name is the function
fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]


@pytest.mark.parametrize("args,unmasked,masked,skipped", [
    # the training cell: its forward, its backward, and the 8 x 4 pairs
    # of the blocks before PR 36
    ((2048, 2048, 1024, 1024, True), 1, 2, 1),
    ((2048, 2048, 512, 512, True), 6, 4, 6),
    ((2048, 2048, 256, 512, True), 12, 8, 12),
    ((2048, 2048, 256, 256, True), 28, 8, 28),
    ((2048, 2048, 128, 128, True), 120, 16, 120),
    # all three kinds in one call, both ways round
    ((1024, 1024, 128, 256, True), 12, 8, 12),
    ((1024, 1024, 256, 128, True), 12, 8, 12),
    # no mask at all without causal; only the ragged last key block
    ((2048, 2048, 256, 512, False), 32, 0, 0),
    ((64, 200, 128, 128, False), 1, 1, 0),
    # kv_lens: lengths live on the device, every block is masked
    ((384, 384, 128, 128, False, True), 0, 9, 0),
    ((300, 300, 128, 128, True), 3, 3, 3),
])
def test_block_plan_counts(args, unmasked, masked, skipped):
    plan = fa.block_plan(*args)
    assert (plan.unmasked, plan.masked, plan.skipped) == (
        unmasked, masked, skipped)
    assert plan.kinds == tuple(
        m for m in (False, True) if (masked if m else unmasked))


@pytest.mark.parametrize("k_major", [False, True])
@pytest.mark.parametrize("args", [(1024, 1024, 128, 256, True),
                                  (1024, 1024, 256, 128, True),
                                  (300, 300, 128, 128, True),
                                  (64, 200, 128, 128, False)])
def test_block_plan_table_is_the_mask(args, k_major):
    """The table the index maps and bodies read agrees, pair by pair,
    with the dense mask: a skipped pair has no visible cell, an unmasked
    pair no hidden one, and FIRST/LAST bracket each accumulation."""
    sq, sk, bq, bk, causal = args
    plan = fa.block_plan(*args)
    i, j, flags = plan.table(k_major)
    row = np.arange(plan.nq * bq)[:, None]
    col = np.arange(plan.nk * bk)[None, :]
    visible = (col < sk) & ((row >= col) if causal else True)
    seen = set()
    for ii, jj, f in zip(i, j, flags):
        tile = visible[ii * bq:(ii + 1) * bq, jj * bk:(jj + 1) * bk]
        assert tile.any()
        if not f & fa._MASKED:
            assert tile.all()
        seen.add((ii, jj))
    for ii in range(plan.nq):
        for jj in range(plan.nk):
            if (ii, jj) not in seen:
                assert not visible[ii * bq:(ii + 1) * bq,
                                   jj * bk:(jj + 1) * bk][:sq].any()
    run = j if k_major else i
    first = np.r_[True, run[1:] != run[:-1]]
    last = np.r_[run[1:] != run[:-1], True]
    np.testing.assert_array_equal(flags & fa._FIRST != 0, first)
    np.testing.assert_array_equal(flags & fa._LAST != 0, last)
    assert len(set(run[first])) == first.sum()   # each run is contiguous


def _kernel_names(fn, *args):
    return set(re.findall(r"flash_attention_\w+", str(jax.make_jaxpr(fn)(
        *args))))


def _check_against_reference(b, s, h_q, h_kv, d, blocks, causal=True,
                             kv_lens=None, bias=None, seed=30, tol=5e-5):
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.normal(size=(b, s, h_q, d)), jnp.float32)
    k = jnp.asarray(rs.normal(size=(b, s, h_kv, d)), jnp.float32)
    v = jnp.asarray(rs.normal(size=(b, s, h_kv, d)), jnp.float32)
    cot = jnp.asarray(rs.normal(size=q.shape), jnp.float32)
    group = h_q // h_kv
    mask = bias
    if kv_lens is not None:
        mask = _padding_bias(kv_lens, s)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, kv_lens=kv_lens, bias=bias,
            block_q=blocks[0], block_k=blocks[1], interpret=True) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
            is_causal=causal, mask=mask) * cot)

    out = flash_attention(q, k, v, causal=causal, kv_lens=kv_lens,
                          bias=bias, block_q=blocks[0], block_k=blocks[1],
                          interpret=True)
    ref = attention_reference(
        q, jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2),
        is_causal=causal, mask=mask)
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)
    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(a, b_, atol=2 * tol, rtol=2 * tol,
                                   err_msg=f"d{name} mismatch")
    return _kernel_names(jax.grad(loss_flash, argnums=(0, 1, 2)), q, k, v)


_BODIES = {
    # unmasked, diagonal and skipped blocks in one call
    "causal-1024-128x256": dict(b=1, s=1024, h_q=2, h_kv=2, d=32,
                                blocks=(128, 256)),
    "causal-1024-256x128": dict(b=1, s=1024, h_q=2, h_kv=2, d=32,
                                blocks=(256, 128)),
    # sq not a multiple of the block
    "causal-300-ragged": dict(b=2, s=300, h_q=2, h_kv=2, d=32,
                              blocks=(128, 128)),
    "causal-300-ragged-128x256": dict(b=1, s=300, h_q=2, h_kv=2, d=32,
                                      blocks=(128, 256)),
    "gqa-4to1": dict(b=2, s=384, h_q=4, h_kv=1, d=32, blocks=(128, 128),
                     tol=1e-4),
    "kv_lens-noncausal": dict(b=3, s=384, h_q=2, h_kv=2, d=32,
                              blocks=(128, 128), causal=False,
                              kv_lens=[384, 200, 17]),
    "noncausal-ragged-keys": dict(b=1, s=300, h_q=2, h_kv=2, d=32,
                                  blocks=(128, 128), causal=False),
    "bias-causal": dict(b=1, s=256, h_q=2, h_kv=2, d=32,
                        blocks=(128, 128), bias=(1, 2, 256, 256)),
    "key-bias-causal": dict(b=2, s=256, h_q=2, h_kv=2, d=32,
                            blocks=(128, 128), bias=(2, 1, 1, 256)),
}


def _body_case(name):
    case = dict(_BODIES[name])
    if "kv_lens" in case:
        case["kv_lens"] = jnp.asarray(case["kv_lens"], jnp.int32)
    if "bias" in case:
        case["bias"] = jnp.asarray(np.random.RandomState(31).normal(
            size=case["bias"]), jnp.float32)
    return case


@pytest.mark.parametrize("name", sorted(_BODIES))
def test_block_bodies_match_reference(name):
    """Forward and gradients of the position-split bodies, through the
    one-kernel backward."""
    names = _check_against_reference(**_body_case(name))
    assert names == {"flash_attention_fwd", "flash_attention_bwd"}


@pytest.mark.parametrize("name", sorted(_BODIES))
def test_two_kernel_backward_matches_reference(name, monkeypatch):
    """Where float32 dQ for the whole sequence cannot stay in VMEM the
    backward is the dK/dV and dQ kernels, over the same plan."""
    monkeypatch.setattr(fa, "_BWD_VMEM_BYTES", 0)
    names = _check_against_reference(**_body_case(name))
    assert names == {"flash_attention_fwd", "flash_attention_bwd_dkdv",
                     "flash_attention_bwd_dq"}


@pytest.mark.parametrize("sq,d,fits", [(2048, 128, True), (2048, 64, True),
                                       (8192, 128, True), (8192, 64, True),
                                       (16384, 128, False),
                                       # 64 wide is padded to the lanes
                                       (16384, 64, False)])
def test_backward_form_follows_the_shapes(sq, d, fits):
    """Which backward runs is decided by what dQ for the sequence takes
    in VMEM at the backward's blocks (the forward's cut to 512); the
    geometry model's budget holds either way."""
    from paddle_tpu.analysis import kernelmodel as km
    assert fa._default_blocks(sq, sq, 0) == fa._DEFAULT_BLOCKS
    assert (fa._one_kernel_bwd_bytes(sq, d, 512, 512, 2)
            <= fa._BWD_VMEM_BYTES) == fits
    q = km.sds((1, sq, 2, d), "bfloat16")

    def run():
        jax.eval_shape(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True).astype(jnp.float32)),
            argnums=(0, 1, 2)), q, q, q)
    specs = {s.body: s for s in km.harvest(run)}
    assert km.budget_reason(run) is None
    assert ("_bwd_kernel" in specs) == fits
    assert ("_bwd_dq_kernel" in specs) == (not fits)
    # forward at the default blocks, backward at its own
    assert specs["_fwd_kernel"].inputs[0].block == (1, 1024, d)
    bwd = specs["_bwd_kernel" if fits else "_bwd_dkdv_kernel"]
    assert bwd.inputs[0].block == (1, 512, d)


@pytest.mark.parametrize("sq,sk,bias_sq,blocks", [
    (2048, 2048, 0, (1024, 1024)),
    (8192, 8192, 0, (1024, 1024)),
    (2048, 2048, 1, (1024, 1024)),      # a key-only bias is one row
    (2048, 2048, 2048, (512, 512)),     # a (block_q, block_k) bias block
    (1500, 1500, 0, (512, 512)),        # 1,536 and not 2,048 rows
    (2049, 2049, 0, (256, 256)),
    (300, 300, 0, (1024, 1024)),        # clamped to one block of 384 later
    (64, 5000, 0, (1024, 1024)),
])
def test_default_blocks_follow_the_shapes(sq, sk, bias_sq, blocks):
    assert fa._default_blocks(sq, sk, bias_sq) == blocks
