"""Power-retention layers (ops/pallas/retention.py, models/layer_kinds.py)
and their serving path through the default engine, at a tiny size on the
CPU with seeded random weights, against the plain reference's attention
form (benchmark/reference/power_retention.py)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmark.program_retention import build_model
from benchmark.reference import power_retention as ref
from paddle_tpu import inference, serving
from paddle_tpu.inference.paged_engine import PagedDecodeEngine
from paddle_tpu.models import gpt, layer_kinds
from paddle_tpu.observability import trace
from paddle_tpu.ops.pallas import retention as R

MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ffn=96, vocab_size=256, max_seq_len=256, rope_theta=1e6,
             norm_eps=1e-6, dtype="float32")
CHUNK = 16


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(MODEL, 7)


@pytest.fixture(scope="module")
def model(weights):
    return build_model(dict(MODEL, use_bias=False, tie_embeddings=False),
                       weights)


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _qkvg(t, heads=4, kv_heads=2, d=16, seed=0, gate_bias=0.0):
    k0, k1, k2, k3 = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(k0, (t, heads, d)),
            jax.random.normal(k1, (t, kv_heads, d)),
            jax.random.normal(k2, (t, kv_heads, d)),
            jax.nn.log_sigmoid(jax.random.normal(k3, (t, kv_heads))
                               + gate_bias))


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(
        0, MODEL["vocab_size"], n).tolist()


def _reference_gap(weights, prompt, tokens):
    """Widest gap by which a served token's reference logit lies under
    the reference's best, and whether every one IS the best."""
    rows = ref.forward_logits(weights, jnp.asarray(prompt + tokens), MODEL,
                              first_row=len(prompt) - 1,
                              n_rows=len(tokens))
    picked = rows[jnp.arange(len(tokens)), jnp.asarray(tokens)]
    return (float(jnp.max(jnp.max(rows, -1) - picked)),
            bool(jnp.all(jnp.argmax(rows, -1) == jnp.asarray(tokens))))


# ------------------------------------------------------------- the forms
def test_phi_gives_the_squared_dot_product():
    x, y = jax.random.normal(jax.random.key(1), (2, 5, 16))
    assert R.phi(x).shape == (5, R.phi_dim(16)) == (5, 9 * 16)
    np.testing.assert_allclose(jnp.sum(R.phi(x) * R.phi(y), -1),
                               jnp.sum(x * y, -1) ** 2, rtol=1e-5)


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_form_is_the_attention_form(chunk):
    q, k, v, g = _qkvg(40, gate_bias=2.0)
    want = ref.retention_attention(q, k, v, g)
    got = R.retention_sequence(q, k, v, g, chunk=chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("form", ["kernel", "jnp"])
def test_state_form_is_the_attention_form(form):
    q, k, v, g = _qkvg(24, gate_bias=2.0)
    want = ref.retention_attention(q, k, v, g)
    step = jax.jit(R.retention_step if form == "kernel"
                   else R.retention_step_reference)
    shapes = R.state_shapes(1, 1, 2, 16)
    S, z = (jnp.zeros(s.shape, s.dtype) for s in shapes.values())
    live = jnp.ones((1,), bool)
    got = []
    for t in range(q.shape[0]):
        o, S, z = step(q[t:t + 1], k[t:t + 1], v[t:t + 1], g[t:t + 1],
                       S, z, 0, live)
        got.append(o[0])
    np.testing.assert_allclose(jnp.stack(got), want, rtol=2e-4, atol=2e-5)


def test_reference_state_form_is_its_attention_form():
    q, k, v, g = _qkvg(24, gate_bias=2.0)
    np.testing.assert_allclose(
        ref.retention_state_form(q, k, v, g, jnp.float32),
        ref.retention_attention(q, k, v, g), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("active", [
    (True, False, True, True), (False, False, False, False),
    (False, True, False, False), (True, True, True, True)])
def test_step_kernel_against_its_jnp_form(active):
    """Interpret mode: live slots updated in place at the layer asked
    for, every other slot and layer left exactly as it was."""
    active = jnp.asarray(active)
    q, k, v, g = _qkvg(4, seed=3)
    shapes = R.state_shapes(2, 4, 2, 16)
    S = jax.random.normal(jax.random.key(5), shapes["S"].shape)
    z = 1.0 + jnp.abs(jax.random.normal(jax.random.key(6),
                                        shapes["z"].shape))
    o1, S1, z1 = R.retention_step(q, k, v, g, S, z, 1, active)
    o2, S2, z2 = R.retention_step_reference(q, k, v, g, S, z, 1, active)
    np.testing.assert_allclose(S1, S2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(z1, z2, rtol=1e-5, atol=1e-5)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(o1)[live], np.asarray(o2)[live],
                               rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(S1)[0], np.asarray(S)[0])
    np.testing.assert_array_equal(np.asarray(S1)[1][~live],
                                  np.asarray(S)[1][~live])
    np.testing.assert_array_equal(np.asarray(z1)[1][~live],
                                  np.asarray(z)[1][~live])


def test_chunk_masks_its_padding():
    q, k, v, g = _qkvg(16, seed=4)
    shapes = R.state_shapes(1, 1, 2, 16)
    S0, z0 = (jnp.zeros(s.shape[2:], s.dtype) for s in shapes.values())
    o_a, S_a, z_a = R.retention_chunk(q[:11], k[:11], v[:11], g[:11],
                                      S0, z0)
    o_b, S_b, z_b = R.retention_chunk(q, k, v, g, S0, z0, n_valid=11)
    np.testing.assert_allclose(o_b[:11], o_a, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S_b, S_a, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(z_b, z_a, rtol=1e-5, atol=1e-6)


def test_long_memory_needs_the_float32_state():
    """Gates near 1 (g about -1e-3, which random W_g never gives): 4,096
    tokens carried over eight chunks agree with the attention form, and
    the control that rounds the state to bfloat16 after every token
    reads at least three times worse. This is the test that catches a
    state kept in a lower precision; the benchmark's cell, whose random
    gates forget within tens of tokens, may not."""
    t = 4096
    q, k, v, _ = _qkvg(t, heads=2, kv_heads=1, seed=9)
    g = -1e-3 * (1.0 + 0.5 * jax.random.uniform(jax.random.key(2), (t, 1)))
    want = ref.retention_attention(q, k, v, g)
    scale = float(jnp.max(jnp.abs(want)))
    program = float(jnp.max(jnp.abs(
        R.retention_sequence(q, k, v, g, chunk=512) - want))) / scale
    control = float(jnp.max(jnp.abs(
        ref.retention_state_form(q, k, v, g, jnp.bfloat16) - want))) / scale
    assert program < 2e-3, program
    assert control > 3 * program and control > 3e-3, (program, control)


# --------------------------------------------------------------- the model
def test_model_forward_is_the_reference(model, weights):
    tokens = jnp.asarray(_prompt(100, 0))
    got = model(tokens[None])[0]
    want = ref.forward_logits(weights, tokens, MODEL)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-4)


def test_reference_in_blocks_is_the_reference_whole(weights, monkeypatch):
    tokens = jnp.asarray(_prompt(64, 1))
    whole = ref.forward_logits(weights, tokens, MODEL, first_row=10,
                               n_rows=20)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    monkeypatch.setattr(ref, "ROW_BLOCK", 32)
    jax.clear_caches()
    blocks = ref.forward_logits(weights, tokens, MODEL, first_row=10,
                                n_rows=20)
    jax.clear_caches()
    np.testing.assert_allclose(blocks, whole, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("control", [{"mode": "fp8"}, {"mode": "int8"},
                                     {"state": "bfloat16"}])
def test_controls_read_away_from_the_reference(weights, control):
    tokens = jnp.asarray(_prompt(48, 2))
    want = ref.forward_logits(weights, tokens, MODEL)
    got = ref.forward_logits(weights, tokens, MODEL, **control)
    assert float(jnp.max(jnp.abs(got - want))) > 1e-3


def test_layer_kinds_describe_what_the_engine_holds(model):
    dense = gpt.gpt_tiny()
    assert layer_kinds.kind_of(dense) is layer_kinds.SOFTMAX
    assert layer_kinds.SOFTMAX.pages
    assert layer_kinds.SOFTMAX.slot_state(dense, 4) == {}
    kind = layer_kinds.kind_of(model.cfg)
    assert kind is layer_kinds.RETENTION and not kind.pages
    assert kind.chunked_prefill
    shapes = kind.slot_state(model.cfg, 3)
    assert shapes["S"].shape == (2, 3, 2, 16, 144)
    assert shapes["z"].shape == (2, 3, 2, 1, 144)
    assert shapes["S"].dtype == jnp.float32


# -------------------------------------------------------------- the engine
def _engine(model, slots=3):
    return inference.make_engine(model, max_slots=slots,
                                 prefill_chunk=CHUNK)


def test_make_engine_gives_the_default_engine_with_no_pages(model):
    eng = _engine(model)
    assert type(eng) is PagedDecodeEngine and eng.P == 0
    assert eng.state["S"].shape == (2, 3, 2, 16, 144)
    assert eng.kp.shape[0] == 1                  # the scratch page alone
    with pytest.raises(ValueError, match="n_pages must be 0"):
        inference.make_engine(model, max_slots=2, n_pages=8)
    eng.check_request(200, 8)                    # many chunks: admitted
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.check_request(250, 8)
    with pytest.raises(NotImplementedError, match="retention"):
        PagedDecodeEngine(model, n_pages=0, max_slots=2, speculative_k=2)
    with pytest.raises(ValueError, match="steps_per_call must be 1"):
        PagedDecodeEngine(model, n_pages=0, max_slots=2, steps_per_call=2)
    for removed in ({"mega": True}, {"fused": False}):
        with pytest.raises(TypeError):
            PagedDecodeEngine(model, n_pages=0, max_slots=2, **removed)


def test_softmax_layers_keep_their_one_pass_cap():
    eng = inference.make_engine(gpt.GPT(gpt.gpt_tiny(max_seq_len=1024)),
                                max_slots=2, n_pages=4)
    assert eng.P == 4 and eng.state == {}
    with pytest.raises(ValueError, match="one pass of at most 512"):
        eng.check_request(600, 8)


def test_other_decode_paths_refuse_retention_layers(model):
    with pytest.raises(NotImplementedError, match="retention"):
        gpt.generate(model, jnp.zeros((1, 4), jnp.int32), 2)


@pytest.mark.parametrize("n_prompt", [5, CHUNK, 3 * CHUNK + 7])
def test_chunked_prefill_then_decode_is_the_reference(model, weights,
                                                      n_prompt):
    """Prompts shorter than, equal to and several times a chunk."""
    eng = _engine(model)
    prompt = _prompt(n_prompt, n_prompt)
    req = eng.submit(prompt, max_new_tokens=8)
    eng.run()
    assert req.done and not req.failed and len(req.tokens) == 8
    gap, greedy = _reference_gap(weights, prompt, list(req.tokens))
    assert greedy and gap == 0.0


def test_engine_is_one_ride_program_and_one_decode_program(model):
    """The benchmark kinds' warm-up (one request of a chunk and a few
    tokens) traces the ride and the decode program; a run that mixes
    long prompts with decoding slots traces nothing more."""
    from paddle_tpu import stats
    names = ("paged_ride", "paged_multi")
    count = lambda: {k: stats.get(f"compile/retrace/{k}") or 0
                     for k in names + ("",)}
    before = count()
    eng = _engine(model, slots=2)
    fe = serving.FrontEnd(eng)
    fe.submit(_prompt(CHUNK + 7, 31), max_new_tokens=3)
    fe.run()
    warm = count()
    assert {k: warm[k] - before[k] for k in names} == {
        "paged_ride": 1, "paged_multi": 1}
    reqs = [fe.submit(_prompt(n, 32 + n), max_new_tokens=6)
            for n in (5, 4 * CHUNK + 1, 2 * CHUNK)]
    fe.run()
    assert all(len(r.tokens) == 6 for r in reqs)
    assert count() == warm


def test_decode_step_lowers_to_the_pinned_program(model):
    """The decode step's program (`PagedDecodeEngine._multi_impl`, what
    `dispatch_fn_args` gives) lowers to the text pinned here: the digest
    is of the program before prompt chunks rode in decode steps, and the
    ride was to leave every decode-only step as it was. A change that
    alters the decode program on purpose pins its new digest."""
    import hashlib
    fn, args = _engine(model).dispatch_fn_args()
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "e8179900f5085330"


# as tests/test_latent_moe.py's: (prompts, tokens asked, each request's
# chunks as (index, last, decoding rows that rode in them))
RIDES = {
    "alone": ((3 * CHUNK - 5,), (6,), [[(0, False, 0), (1, False, 0),
                                        (2, True, 0)]]),
    "beside_decoders": ((5, 3 * CHUNK - 5), (12, 6), [
        [(0, True, 0)], [(0, False, 1), (1, False, 1), (2, True, 1)]]),
    "neighbour_retires": ((5, 4 * CHUNK - 3, CHUNK + 4), (3, 5, 4), [
        [(0, True, 0)],
        [(0, False, 1), (1, False, 1), (2, False, 0), (3, True, 0)],
        [(0, False, 1), (1, True, 1)]]),
}


@pytest.mark.parametrize("case", sorted(RIDES))
def test_a_chunk_rides_with_the_decoding_slots(model, weights, case):
    lengths, asked, rides = RIDES[case]
    prompts = [_prompt(n, 40 + i) for i, n in enumerate(lengths)]
    eng = _engine(model, slots=2)
    fe = serving.FrontEnd(eng)
    trace.enable()
    try:
        reqs = [fe.submit(p, max_new_tokens=k)
                for p, k in zip(prompts, asked)]
        fe.run()
        chunks = [e[6] for e in trace.events()[0]
                  if e[0] == "serve/prefill_chunk"]
    finally:
        trace.disable()
        trace.clear()
    for prompt, k, req in zip(prompts, asked, reqs):
        assert len(req.tokens) == k
        gap, greedy = _reference_gap(weights, prompt, list(req.tokens))
        assert greedy and gap == 0.0
    assert [[(c["index"], c["last"], c["rode"]) for c in chunks
             if c["rid"] == req.id] for req in reqs] == rides


def test_neighbouring_slots_do_not_touch_each_other(model):
    """Two requests of different length side by side, one still in
    prefill while the other decodes: each serves what it serves alone,
    and leaves the state it leaves alone."""
    long_p, short_p = _prompt(5 * CHUNK + 3, 11), _prompt(6, 12)

    def serve(prompts):
        eng = _engine(model, slots=2)
        reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
        eng.run()
        return [list(r.tokens) for r in reqs], eng

    (alone_long,), e1 = serve([long_p])
    (alone_short,), e2 = serve([short_p])
    (both_short, both_long), e3 = serve([short_p, long_p])
    assert both_long == alone_long and both_short == alone_short
    np.testing.assert_allclose(e3.state["S"][:, 0], e2.state["S"][:, 0],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(e3.state["S"][:, 1], e1.state["S"][:, 0],
                               rtol=1e-6, atol=1e-6)


def test_a_prefilling_slot_takes_no_part_in_decode(model):
    eng = _engine(model, slots=2)
    short = eng.submit(_prompt(4, 1), max_new_tokens=20)
    long_ = eng.submit(_prompt(6 * CHUNK, 2), max_new_tokens=2)
    eng.step()
    eng.step()
    assert 1 in eng._prefilling and eng._disp_rem[1] == 0
    assert not bool(eng.active[1]) and int(eng.lengths[1]) == 0
    eng.run()
    assert len(short.tokens) == 20 and len(long_.tokens) == 2


def test_a_released_slot_is_readmitted_from_zero(model):
    first, second = _prompt(40, 21), _prompt(9, 22)
    eng = _engine(model, slots=1)
    eng.submit(first, max_new_tokens=6)
    eng.run()
    assert float(jnp.max(jnp.abs(eng.state["S"]))) > 0
    again = eng.submit(second, max_new_tokens=6)
    eng.run()
    fresh_eng = _engine(model, slots=1)
    fresh = fresh_eng.submit(second, max_new_tokens=6)
    fresh_eng.run()
    assert list(again.tokens) == list(fresh.tokens)
    np.testing.assert_allclose(eng.state["S"], fresh_eng.state["S"],
                               rtol=1e-6, atol=1e-6)


def test_long_prompt_is_answered_while_others_decode(model):
    """Through FrontEnd: a prompt of many chunks is admitted, prefilled
    one chunk a step, and answered; the slots that decode meanwhile get
    a token every step."""
    eng = _engine(model)
    fe = serving.FrontEnd(eng)
    talkers = [fe.submit(_prompt(5, i), max_new_tokens=40) for i in (1, 2)]
    for _ in range(4):
        fe.step()
    reader = fe.submit(_prompt(10 * CHUNK + 5, 3), max_new_tokens=4)
    seen = []
    while not reader.tokens:
        before = sum(len(t.tokens) for t in talkers)
        fe.step()
        seen.append(sum(len(t.tokens) for t in talkers) - before)
    assert len(seen) >= 10              # eleven chunks, one a step
    assert all(n == 2 for n in seen[1:-1]), seen
    fe.run()
    assert reader.status == "done" and len(reader.tokens) == 4
    assert all(len(t.tokens) == 40 for t in talkers)


def test_eviction_mid_prefill_frees_the_slot(model):
    eng = _engine(model, slots=1)
    doomed = eng.submit(_prompt(8 * CHUNK, 5), max_new_tokens=4,
                        deadline_s=0.0)
    eng.step()
    assert doomed.failed and not eng._prefilling and eng.free_slots == 1
    after = eng.submit(_prompt(7, 6), max_new_tokens=3)
    eng.run()
    assert after.done and not after.failed and len(after.tokens) == 3


def test_handoff_of_state_is_refused_by_name(model):
    eng = _engine(model, slots=1)
    req = eng.submit(_prompt(7, 6), max_new_tokens=3)
    eng.step()
    with pytest.raises(NotImplementedError, match="wire form"):
        eng.detach_handoff(req)


def test_spans_of_the_chunked_prefill(model):
    eng = _engine(model, slots=2)
    fe = serving.FrontEnd(eng)
    trace.enable()
    try:
        fe.submit(_prompt(4, 1), max_new_tokens=12)
        fe.submit(_prompt(2 * CHUNK + 3, 2), max_new_tokens=3)
        fe.run()
        events = trace.events()[0]
    finally:
        trace.disable()
        trace.clear()
    chunks = [e[6] for e in events if e[0] == "serve/prefill_chunk"]
    # the short prompt's one chunk with nothing decoding, then the long
    # one's three, each with the short prompt's token riding in it
    assert [(c["slot"], c["tokens"], c["index"], c["last"], c["rode"])
            for c in chunks] == [(0, 4, 0, True, 0),
                                 (1, CHUNK, 0, False, 1),
                                 (1, CHUNK, 1, False, 1),
                                 (1, 3, 2, True, 1)]
    # a step with a chunk dispatches that one program: what it says it
    # enqueued is the chunk's tokens and the rows that rode in it
    chunk_of = {e[5]: e[6] for e in events if e[0] == "serve/prefill_chunk"}
    with_chunk = [(e[6], chunk_of[e[4]]) for e in events
                  if e[0] == "serve/step" and e[4] in chunk_of]
    assert len(with_chunk) == 4
    for step, chunk in with_chunk:
        assert (step["prefill_tokens"], step["decode_tokens"]) == (
            chunk["tokens"], chunk["rode"])
    # an admission of a chunk-prefilled kind only binds the slot: its
    # programs are the chunks, dispatched later
    admits = [e[6] for e in events if e[0] == "serve/admit"]
    assert [a["programs"] for a in admits] == [0, 0]
    steps = [e[6] for e in events if e[0] == "serve/step"]
    per_slot = sum(a.nbytes for a in eng.state.values()) // eng.S
    assert max(s["state_slots"] for s in steps) == 2
    assert max(s["state_bytes"] for s in steps) == 2 * per_slot
    assert max(s["prefilling"] for s in steps) >= 1
    assert sum(s["prefill_tokens"] for s in steps) == 4 + 2 * CHUNK + 3
    assert sum(s["decode_tokens"] for s in steps) >= 11 + 2
    assert all(s["pages"] == 0 and s["live_pages"] == 0 for s in steps)
