"""Single-dispatch paged decode (ISSUE 19): the layer-folded megakernel
with fused sampling epilogue vs the per-layer fused reference.

The invariants:
- greedy token STREAMS are bit-identical to the per-layer fused path
  and to gpt.generate on every geometry (mixed lengths, eos, GQA,
  rope) — the megakernel is an execution-plan change, not a math
  change;
- within one step the KV pools match the reference bit-exactly at
  layer 0 and to float-ulp order at layers >= 1 (both fold the fresh
  KV row in page order, the per-layer path after writing it into its
  page, but the two accumulate in differently shaped tiles — same set
  of numbers, different float-addition order);
- an INACTIVE slot's writes land in the scratch page only: its mapped
  pages stay bit-identical;
- the dispatch program lowers to <= 2 pallas launches per decode step
  (layer-folded kernel + sampling epilogue) on the plain AND
  speculative paths, while the per-layer reference pays two per layer
  (the row write, then the attend) — counted from the AOT jaxpr, so the assert is backend-independent;
- warm prefix admission, poison eviction and pipelined depth-2 all
  behave identically to the per-layer path.
"""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.paged_engine import PagedDecodeEngine
from paddle_tpu.models import gpt
from paddle_tpu.testing import faults


def _model(max_seq=512, heads=4, kv_heads=None, rope=False, layers=2):
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=max_seq,
                        d_model=32, n_layers=layers, n_heads=heads,
                        n_kv_heads=kv_heads, dtype=jnp.float32,
                        rope=rope)
    return gpt.GPT(cfg, seed=0)


def _reference(model, prompt, n_new, eos=None):
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    out = model.generate(toks, max_new_tokens=n_new,
                         max_len=len(prompt) + n_new, eos_id=eos)
    got = list(np.asarray(out)[0, len(prompt):])
    if eos is not None and eos in got:
        got = got[:got.index(eos) + 1]
    return got


def _run(model, prompts, n_new, **kw):
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=3, **kw)
    reqs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    eng.run()
    return eng, [r.tokens for r in reqs]


@pytest.mark.parametrize("rope,kvh", [(False, None), (True, 2)])
def test_mega_streams_match_per_layer_and_generate(rope, kvh):
    model = _model(rope=rope, kv_heads=kvh)
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 96, size=n)) for n in (5, 170, 23)]
    refs = [_reference(model, p, 9) for p in prompts]
    _, mega = _run(model, prompts, 9, mega=True)
    _, plain = _run(model, prompts, 9, mega=False)
    assert mega == refs, (rope, kvh)
    assert plain == refs, (rope, kvh)


def test_mega_eos_parity():
    model = _model()
    rs = np.random.RandomState(3)
    prompt = list(rs.randint(0, 96, size=31))
    ref = _reference(model, prompt, 24, eos=7)
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=4, mega=True)
    req = eng.submit(prompt, max_new_tokens=24, eos_id=7)
    eng.run()
    assert req.tokens == ref


def _kernel_fixture(rope=False):
    """One-step kernel-level fixture: model, per-layer fused reference
    step and mega step over the SAME randomized pools/table."""
    from paddle_tpu.ops.pallas.decode_megakernel import (
        _WEIGHT_ORDER, mega_decode_layers, mega_logits_sample)
    from paddle_tpu.ops.pallas.paged_attention import paged_append_attend
    from jax import lax

    S, PAGE, P, MX = 4, 128, 12, 4
    model = _model(max_seq=PAGE * MX, kv_heads=2, rope=rope)
    cfg = model.cfg
    head = {"wte": model.wte, "wpe": model.wpe,
            "lnf_scale": model.lnf_scale, "lnf_bias": model.lnf_bias,
            "lm_head": model.lm_head}
    stacked = gpt.stack_block_weights(
        [model.blocks[i] for i in range(cfg.n_layers)])
    weights = {n: getattr(stacked, n) for n in _WEIGHT_ORDER}
    scale = 1.0 / math.sqrt(cfg.head_dim)
    L = cfg.n_layers
    scratch = L * P

    rng = np.random.RandomState(0)
    shape = (L * P + 1, cfg.kv_heads, PAGE, cfg.head_dim)
    kp0 = jnp.asarray(rng.randn(*shape), jnp.float32) * 0.1
    vp0 = jnp.asarray(rng.randn(*shape), jnp.float32) * 0.1
    table = jnp.asarray(
        np.stack([np.arange(i * 3, i * 3 + MX, dtype=np.int32) % P
                  for i in range(S)]))
    lengths = jnp.asarray([5, PAGE - 1, PAGE, 2 * PAGE + 7], jnp.int32)
    last = jnp.asarray([3, 17, 42, 90], jnp.int32)
    active = jnp.asarray([True, True, False, True])

    def per_layer_step(kp, vp):
        x = jnp.take(head["wte"], last, axis=0)
        if head["wpe"] is not None:
            x = x + jnp.take(head["wpe"], lengths, axis=0)
        x = x[:, None, :]
        pidx = jnp.minimum(lengths // PAGE, MX - 1)
        base = jnp.take_along_axis(table, pidx[:, None], axis=1)[:, 0]

        def body(carry, blk_i):
            h, kp, vp = carry
            blk, i = blk_i
            q, k, v = blk._qkv(h, lengths)
            wpids = jnp.where(active, i * P + base, scratch)
            o, kp, vp = paged_append_attend(
                q[:, 0].astype(kp.dtype), kp, vp,
                k[:, 0].astype(kp.dtype), v[:, 0].astype(vp.dtype),
                i * P + table, wpids, lengths, scale=scale)
            h = blk._block_tail(h, o.astype(h.dtype).reshape(h.shape))
            return (h, kp, vp), None

        (x, kp, vp), _ = lax.scan(body, (x, kp, vp),
                                  (stacked, jnp.arange(L)))
        x = gpt.final_ln(x, head["lnf_scale"], head["lnf_bias"])
        w = head["wte"].T if head["lm_head"] is None else head["lm_head"]
        logits = (x @ w)[:, 0]
        tok = jnp.argmax(logits.astype(jnp.float32), -1)
        return kp, vp, tok.astype(jnp.int32)

    def mega_step(kp, vp):
        x = jnp.take(head["wte"], last, axis=0)
        if head["wpe"] is not None:
            x = x + jnp.take(head["wpe"], lengths, axis=0)
        x, kp, vp = mega_decode_layers(
            x, weights, kp, vp, table, lengths,
            jnp.arange(S, dtype=jnp.int32), active.astype(jnp.int32),
            page=PAGE, n_pages=P, n_heads=cfg.n_heads,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
            rope=cfg.rope, rope_theta=cfg.rope_theta, scale=scale)
        w = head["wte"].T if head["lm_head"] is None else head["lm_head"]
        tok, _ = mega_logits_sample(
            x, head["lnf_scale"], head["lnf_bias"], w,
            jnp.zeros((S,), bool))
        return kp, vp, tok

    return (kp0, vp0, table, active, per_layer_step, mega_step,
            dict(S=S, P=P, L=L, scratch=scratch))


def test_mega_pool_parity_one_step():
    """Layer-0 pool slab bit-exact vs the per-layer reference; layers
    >= 1 within float-ulp of the fold-order difference; tokens equal."""
    kp0, vp0, _, _, per_layer, mega, geo = _kernel_fixture()
    kpa, vpa, ta = per_layer(kp0, vp0)
    kpb, vpb, tb = mega(kp0, vp0)
    assert (np.asarray(ta) == np.asarray(tb)).all()
    P, sc = geo["P"], geo["scratch"]
    dk0 = np.abs(np.asarray(kpa)[:P] - np.asarray(kpb)[:P]).max()
    dv0 = np.abs(np.asarray(vpa)[:P] - np.asarray(vpb)[:P]).max()
    assert dk0 == 0.0 and dv0 == 0.0, "layer-0 pool slab not bit-exact"
    dk = np.abs(np.asarray(kpa)[:sc] - np.asarray(kpb)[:sc]).max()
    dv = np.abs(np.asarray(vpa)[:sc] - np.asarray(vpb)[:sc]).max()
    assert dk < 1e-6 and dv < 1e-6, (dk, dv)


def test_mega_inactive_slot_writes_scratch_only():
    """An inactive slot's fresh-KV write must land in the scratch page
    (row L*P): every page the slot's table maps stays bit-identical."""
    kp0, vp0, table, active, _, mega, geo = _kernel_fixture()
    kpb, vpb, _ = mega(kp0, vp0)
    P, L = geo["P"], geo["L"]
    inactive = [s for s in range(geo["S"])
                if not bool(np.asarray(active)[s])]
    assert inactive, "fixture lost its inactive slot"
    for s in inactive:
        for i in range(L):
            rows = i * P + np.asarray(table)[s]
            dk = np.abs(np.asarray(kpb)[rows]
                        - np.asarray(kp0)[rows]).max()
            dv = np.abs(np.asarray(vpb)[rows]
                        - np.asarray(vp0)[rows]).max()
            assert dk == 0.0 and dv == 0.0, (s, i)


def test_mega_launch_counts():
    """Acceptance: the fused paged decode step lowers to <= 2 kernel
    launches per step (megakernel + epilogue) — plain AND speculative —
    vs two per layer (row write + attend) on the reference path. Counted from the dispatch
    program's jaxpr (scan-trip weighted), so the assert holds on any
    backend; the model has 3 layers so the counts cannot coincide."""
    from paddle_tpu.observability import devprof
    model = _model(layers=3)

    def per_step(**kw):
        eng = PagedDecodeEngine(model, n_pages=20, max_slots=2,
                                steps_per_call=4, **kw)
        fn, args = eng.dispatch_fn_args()
        return devprof.count_pallas_launches(fn, *args) / eng.chunk

    assert per_step(mega=True) == 2
    assert per_step(mega=True, speculative_k=3) == 2
    assert per_step(mega=False) == 2 * model.cfg.n_layers


def test_mega_hlo_custom_call_count_is_countable():
    """The AOT-lowering counter must return a number (0 in CPU
    interpret mode — pallas lowers to inline HLO there; one custom-call
    per launch on TPU)."""
    from paddle_tpu.observability import devprof
    model = _model(layers=3)
    eng = PagedDecodeEngine(model, n_pages=20, max_slots=2,
                            steps_per_call=2, mega=True)
    fn, args = eng.dispatch_fn_args()
    n = devprof.count_hlo_custom_calls(fn, *args)
    assert n is not None and n >= 0


@pytest.mark.parametrize("mega", [True, False])
def test_paged_spec_streams_match_generate(mega):
    """Speculative decode revived on the paged path: prompt-lookup
    drafts + the fused verify step must leave greedy streams
    bit-identical to gpt.generate, megakernel and per-layer alike."""
    model = _model()
    rs = np.random.RandomState(1)
    rep = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]   # drafts actually accept
    prompts = [rep, list(rs.randint(0, 96, size=40))]
    refs = [_reference(model, p, 12) for p in prompts]
    _, got = _run(model, prompts, 12, mega=mega, speculative_k=4)
    assert got == refs, mega


@pytest.mark.parametrize("spec", [0, 4])
def test_mega_pipelined_depth2_identical(spec):
    model = _model()
    rs = np.random.RandomState(2)
    rep = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
    prompts = [rep, list(rs.randint(0, 96, size=40))]
    _, d1 = _run(model, prompts, 10, mega=True, speculative_k=spec,
                 inflight=1)
    _, d2 = _run(model, prompts, 10, mega=True, speculative_k=spec,
                 inflight=2)
    assert d1 == d2


def test_mega_warm_prefix_admission():
    """Second admission of a long prompt rides the radix cache (suffix-
    only prefill) and must decode identically through the megakernel."""
    model = _model()
    rs = np.random.RandomState(4)
    long_p = list(rs.randint(0, 96, size=200))
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=1,
                            steps_per_call=2, mega=True)
    r1 = eng.submit(long_p, max_new_tokens=8)
    eng.run()
    r2 = eng.submit(long_p, max_new_tokens=8)
    eng.run()
    ref = _reference(model, long_p, 8)
    assert r1.tokens == ref and r2.tokens == ref


def test_mega_poison_eviction_scrubs_and_isolates():
    """Non-finite logits through the fused epilogue evict ONLY the
    poisoned slot; the survivor stream is untouched and the retired
    slot's pages return to the pool (free or refcount-zero cached)."""
    model = _model()
    rs = np.random.RandomState(5)
    pa, pb = (list(rs.randint(0, 96, size=n)) for n in (5, 23))
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=2, mega=True)
    ra = eng.submit(pa, max_new_tokens=8)
    rb = eng.submit(pb, max_new_tokens=8)
    with faults.inject("engine.poison_logits", "nan", slot=0):
        eng.run()
    assert ra.failed and "non-finite" in ra.error
    assert not rb.failed and rb.tokens == _reference(model, pb, 8)
    cached = (eng._prefix.cached_pages if eng._prefix is not None
              else 0)
    assert eng.free_pages + cached == 14
