"""Latent-attention layers over dropless sparse experts
(ops/pallas/latent_attention.py, ops/pallas/moe_experts.py,
models/expert_layer.py, models/layer_kinds.py LATENT) against the plain
reference (benchmark/reference/latent_moe.py) on seeded random weights at
a tiny size: the engine's chunked prefill then decode through the latent
pages, the absorbed form against the expanded form, the expert layer
against the reference's loop over experts, and what the decode step
reports of one slot's routing (`on_routing`)."""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.program_latent_moe import build_model, model_config  # noqa: E402
from benchmark.reference import latent_moe as ref  # noqa: E402
from paddle_tpu import inference, serving  # noqa: E402
from paddle_tpu.models import expert_layer, layer_kinds  # noqa: E402
from paddle_tpu.ops.pallas import latent_attention as la  # noqa: E402
from paddle_tpu.ops.pallas import moe_experts as me  # noqa: E402

TINY = {
    "n_layers": 3, "leading_dense": 1, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 4, "head_dim": 16, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "d_ffn": 128, "n_experts": 32, "experts_per_token": 4,
    "expert_width": 32, "n_shared_experts": 1, "routed_scaling": 2.5,
    "vocab_size": 512, "max_seq_len": 1024, "rope_theta": 10000.0,
    "norm_eps": 1e-6, "use_bias": False, "tie_embeddings": False,
    "dtype": "float32",
}
CHUNK = 128
SEED = 2**31 + 35


@pytest.fixture(scope="module")
def weights():
    return ref.make_weights(TINY, SEED)


@pytest.fixture(scope="module")
def model(weights):
    return build_model(TINY, weights)


def _serve(model, prompts, new_tokens, slots=2, pages=16):
    eng = inference.make_engine(model, max_slots=slots, n_pages=pages,
                                prefill_chunk=CHUNK)
    fe = serving.FrontEnd(eng)
    reqs = [fe.submit(p, max_new_tokens=new_tokens) for p in prompts]
    fe.run()
    return eng, [list(r.tokens) for r in reqs]


def _gap(weights, prompt, served):
    """The widest gap by which a served token's reference logit lies
    below the reference's best at its position."""
    seq = jnp.asarray(prompt + served, jnp.int32)
    logits = ref.forward_logits(weights, seq, TINY)
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(served)]
    picked = rows[jnp.arange(len(served)), jnp.asarray(served)]
    return float(jnp.max(jnp.max(rows, axis=-1) - picked))


# one chunk; two chunks; five chunks; a prompt that ends ON a page
# boundary and one that ends a token past it
@pytest.mark.parametrize("n_prompt", [70, 200, 600, 128, 257])
def test_chunked_prefill_then_decode_matches_the_reference(
        model, weights, n_prompt):
    rng = np.random.default_rng(n_prompt)
    prompt = rng.integers(0, TINY["vocab_size"], n_prompt).tolist()
    eng, (served,) = _serve(model, [prompt], 8)
    assert eng.kind is layer_kinds.LATENT and len(served) == 8
    assert _gap(weights, prompt, served) <= 1e-4
    # every page went back to the pool
    assert eng.free_pages == eng.P


def test_two_slots_interleave_chunks_and_steps(model, weights):
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in (300, 90, 140)]
    eng, served = _serve(model, prompts, 12)
    for prompt, tokens in zip(prompts, served):
        assert len(tokens) == 12
        assert _gap(weights, prompt, tokens) <= 1e-4
    assert set(eng.state) == {"cp", "experts_touched"}
    assert eng.state["cp"].shape == (
        TINY["n_layers"] * eng.P + 1,
        TINY["kv_lora_rank"] + TINY["qk_rope_head_dim"], eng.page)


def test_engine_is_one_chunk_program_and_one_decode_program(model):
    """The ride (a prompt chunk with the decoding slots' token in it)
    is the engine's ONE chunk program: the benchmark kinds' warm-up, one
    request of a chunk and a few tokens, traces it and the decode
    program, and a run that mixes long prompts with decoding slots
    traces nothing more."""
    from paddle_tpu import stats
    names = ("paged_ride", "paged_multi")
    count = lambda: {k: stats.get(f"compile/retrace/{k}") or 0
                     for k in names + ("",)}
    before = count()
    eng = inference.make_engine(model, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK)
    fe = serving.FrontEnd(eng)
    rng = np.random.default_rng(3)
    fe.submit(rng.integers(0, 512, CHUNK + 7).tolist(), max_new_tokens=3)
    fe.run()
    warm = count()
    assert {k: warm[k] - before[k] for k in names} == {
        "paged_ride": 1, "paged_multi": 1}
    reqs = [fe.submit(rng.integers(0, 512, n).tolist(), max_new_tokens=5)
            for n in (60, 400, 200)]
    fe.run()
    assert all(len(r.tokens) == 5 for r in reqs)
    assert count() == warm


def test_decode_step_lowers_to_the_pinned_program(model):
    """The decode step's program (`PagedDecodeEngine._multi_impl`, what
    `dispatch_fn_args` gives) lowers to the text pinned here: the digest
    is of the program before prompt chunks rode in decode steps, and the
    ride was to leave every decode-only step as it was. A change that
    alters the decode program on purpose pins its new digest."""
    import hashlib
    eng = inference.make_engine(model, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK)
    fn, args = eng.dispatch_fn_args()
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "ad0e103f4ea6171f"


# (prompts, tokens asked, each request's chunks as (index, last, decoding
# rows that rode in them)); the chunks of 128 are dispatched one a step,
# the oldest prompt's first: a prompt alone rides with no decoding slot;
# a prompt of three chunks rides beside a decoding slot (non-final and
# final chunks); a neighbour retires in a step whose chunk it rides in
# (its three tokens: one from its own chunk, two from the first two
# rides), and a third request takes its slot while the long prompt
# prefills, its chunks after the long prompt's
RIDES = {
    "alone": ((300,), (6,), [[(0, False, 0), (1, False, 0),
                              (2, True, 0)]]),
    "beside_decoders": ((20, 300), (12, 6), [
        [(0, True, 0)], [(0, False, 1), (1, False, 1), (2, True, 1)]]),
    "neighbour_retires": ((20, 400, 150), (3, 5, 4), [
        [(0, True, 0)],
        [(0, False, 1), (1, False, 1), (2, False, 0), (3, True, 0)],
        [(0, False, 1), (1, True, 1)]]),
}


@pytest.mark.parametrize("case", sorted(RIDES))
def test_a_chunk_rides_with_the_decoding_slots(model, weights, case):
    from paddle_tpu.observability import trace
    lengths, asked, rides = RIDES[case]
    rng = np.random.default_rng(len(lengths) * 1000 + sum(lengths))
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in lengths]
    eng = inference.make_engine(model, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK)
    fe = serving.FrontEnd(eng)
    trace.enable()
    try:
        reqs = [fe.submit(p, max_new_tokens=k)
                for p, k in zip(prompts, asked)]
        fe.run()
        chunks = [a for name, *_, a in trace.events()[0]
                  if name == "serve/prefill_chunk"]
    finally:
        trace.disable()
        trace.clear()
    for prompt, k, req in zip(prompts, asked, reqs):
        assert len(req.tokens) == k
        assert _gap(weights, prompt, list(req.tokens)) <= 1e-4
    assert eng.free_pages == eng.P
    assert [[(c["index"], c["last"], c["rode"]) for c in chunks
             if c["rid"] == req.id] for req in reqs] == rides


def test_refusals_name_what_is_missing(model):
    with pytest.raises(ValueError, match="whole pages"):
        inference.make_engine(model, max_slots=2, n_pages=8,
                              prefill_chunk=100)
    with pytest.raises(NotImplementedError, match="plain decode step"):
        inference.make_engine(model, max_slots=2, n_pages=8,
                              prefill_chunk=CHUNK, speculative_k=3)
    with pytest.raises(ValueError, match="steps_per_call must be 1"):
        inference.make_engine(model, max_slots=2, n_pages=8,
                              prefill_chunk=CHUNK, steps_per_call=4)
    eng = inference.make_engine(model, max_slots=2, n_pages=8,
                                prefill_chunk=CHUNK)
    assert eng._prefix is None
    with pytest.raises(NotImplementedError, match="wire form"):
        eng.submit_handoff({}, None, None)


# ------------------------------------------------- the two forms of attention
def _latent_case(seed, b=3, h=4, rank=32, rope=8, page=128, pages=4):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f(b * pages + 1, rank + rope, page)
    table = jnp.arange(b * pages, dtype=jnp.int32).reshape(b, pages)
    return f(b, h, rank + rope), pool, f(b, rank + rope), table


@pytest.mark.parametrize("lengths", [[0, 5, 130], [127, 128, 129],
                                     [511, 300, 1]])
def test_absorbed_attend_kernel_against_its_oracle(lengths):
    q, pool, row, table = _latent_case(sum(lengths))
    lengths = jnp.asarray(lengths, jnp.int32)
    wpids = jnp.take_along_axis(table, (lengths // 128)[:, None], 1)[:, 0]
    before = np.array(pool)
    o, new = la.latent_append_attend(q, pool, row, table, wpids, lengths,
                                     32, 0.2)
    # the fresh row is column length % page of the write page, and the
    # only thing that changed
    new = np.asarray(new)
    for i, n in enumerate(np.asarray(lengths)):
        np.testing.assert_array_equal(new[int(wpids[i]), :, n % 128],
                                      np.asarray(row[i]))
        before[int(wpids[i]), :, n % 128] = np.asarray(row[i])
    np.testing.assert_array_equal(new, before)
    want = la.latent_attend_reference(q, jnp.asarray(new), table,
                                      lengths + 1, 32, 0.2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_absorbed_form_equals_expanded_form():
    """One query token: scores through the absorbed query against the
    cached rows, and the value half applied after, against every key and
    value made from its row."""
    rng = np.random.default_rng(11)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    h, nope, rope, v, rank, t = 4, 16, 8, 16, 32, 37
    q_nope, q_rope = f(1, h, nope), f(1, h, rope)
    c_kv, k_rope, w = f(t, rank), f(t, rope), f(rank, h, nope + v) * 0.2
    scale = (nope + rope) ** -0.5
    expanded = la.expanded_attention(q_nope, q_rope, c_kv, k_rope, w, scale,
                                     offset=t - 1)[0]
    q_lat = jnp.einsum("hd,chd->hc", q_nope[0], w[..., :nope])
    q = jnp.concatenate([q_lat, q_rope[0]], axis=-1)[None]
    pool = jnp.zeros((2, rank + rope, 128)).at[0, :, :t].set(
        jnp.concatenate([c_kv, k_rope], axis=-1).T)
    o_lat = la.latent_attend_reference(
        q, pool, jnp.zeros((1, 1), jnp.int32), jnp.asarray([t]), rank, scale)
    absorbed = jnp.einsum("hc,chd->hd", o_lat[0], w[..., nope:])
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pos0,n_valid", [(0, 128), (0, 3), (256, 77),
                                          (128, 128)])
def test_chunk_attend_writes_its_pages_and_sees_those_before(pos0, n_valid):
    rng = np.random.default_rng(pos0 + n_valid)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    h, nope, rope, v, rank, c, page = 4, 16, 8, 16, 32, 128, 128
    total = pos0 + c
    rows_all = f(total, rank + rope)
    w = f(rank, h, nope + v) * 0.2
    q_nope, q_rope = f(c, h, nope), f(c, h, rope)
    table_row = jnp.asarray([5, 2, 7, 0, 0, 0], jnp.int32)
    pool = jnp.zeros((10, rank + rope, page))
    for j in range(pos0 // page):       # what earlier chunks wrote
        pool = pool.at[1 + table_row[j]].set(
            rows_all[j * page:(j + 1) * page].T)
    scale = (nope + rope) ** -0.5
    o, pool = la.latent_chunk_attend(
        q_nope, q_rope, rows_all[pos0:], pool, w, table_row, 1, 9, pos0,
        n_valid, rank, scale, block_pages=2)
    want = la.expanded_attention(q_nope, q_rope, rows_all[:, :rank],
                                 rows_all[:, rank:], w, scale, pos0)
    np.testing.assert_allclose(np.asarray(o[:n_valid]),
                               np.asarray(want[:n_valid]), rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(pool[1 + table_row[pos0 // page]]),
        np.asarray(rows_all[pos0:].T))


# ------------------------------------------------------------ the expert layer
def _layer(bias=None, w_router=None, per_token=4):
    layer = expert_layer.ExpertLayer(
        64, 32, 16, per_token, jax.random.PRNGKey(5), scale=2.5,
        dtype=jnp.float32)
    return layer.merge_params({
        "w_router": layer.w_router if w_router is None else w_router,
        "router_bias": layer.router_bias if bias is None else bias})


def _reference_layer(layer, x):
    lp = {"experts.w_router": layer.w_router,
          "experts.router_bias": layer.router_bias,
          "experts.w_gate": layer.w_gate, "experts.w_up": layer.w_up,
          "experts.w_down": layer.w_down, "experts.ws_gate": layer.ws_gate,
          "experts.ws_up": layer.ws_up, "experts.ws_down": layer.ws_down}
    with jax.default_matmul_precision("highest"):
        return ref.expert_ffn(lp, x, {"e": 16}, layer.per_token, 2.5, "f32",
                              "float32")


def _sets(experts):
    return np.sort(np.asarray(experts), -1)


def _tokens(n, seed=0):
    return jnp.asarray(np.random.default_rng(seed).standard_normal((n, 64)),
                       jnp.float32)


@pytest.mark.parametrize("n_tokens", [1, 7, 40, 300])
def test_expert_layer_matches_the_loop_over_experts(n_tokens):
    layer, x = _layer(), _tokens(n_tokens)
    out, touched, chose = layer(x)
    want, experts = _reference_layer(layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert int(touched) == len(np.unique(np.asarray(experts)))
    np.testing.assert_array_equal(_sets(chose), _sets(experts))


def test_a_tie_at_the_last_place_goes_to_the_lower_index():
    """Experts 3 and 9 get the SAME column of the router, so every token
    scores them alike; wherever that score is the fourth largest, the
    place goes to 3, in the program and in the reference."""
    base = _layer()
    w = base.w_router.at[:, 9].set(base.w_router[:, 3])
    layer, x = _layer(w_router=w), _tokens(200, 1)
    experts, _ = expert_layer.route(x, layer.w_router, layer.router_bias, 4,
                                    2.5)
    theirs, _ = ref.choose_experts(x, layer.w_router, layer.router_bias, 4,
                                   2.5)
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(np.asarray(theirs), -1))
    has3 = np.any(np.asarray(experts) == 3, -1)
    has9 = np.any(np.asarray(experts) == 9, -1)
    assert np.any(has3 & ~has9)         # the tie was at the last place
    assert not np.any(has9 & ~has3)     # and never went to the higher
    out = layer(x)[0]
    want, _ = _reference_layer(layer, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_the_bias_selects_and_does_not_weigh():
    x = _tokens(50, 2)
    plain = _layer()
    bias = jnp.zeros((16,)).at[5].set(10.0)     # expert 5 always chosen
    biased = _layer(bias=bias)
    e0, w0 = expert_layer.route(x, plain.w_router, plain.router_bias, 4, 2.5)
    e1, w1 = expert_layer.route(x, biased.w_router, biased.router_bias, 4,
                                2.5)
    assert np.all(np.any(np.asarray(e1) == 5, -1))
    assert not np.all(np.any(np.asarray(e0) == 5, -1))
    # the weights are the chosen SCORES normalised: they sum to the
    # scaling, and a token whose set did not change keeps its weights
    np.testing.assert_allclose(np.asarray(w1.sum(-1)), 2.5, rtol=1e-5)
    same = np.all(np.sort(np.asarray(e0), -1) == np.sort(np.asarray(e1), -1),
                  -1)
    assert same.any() and not same.all()
    order = lambda e, w: np.take_along_axis(np.asarray(w),
                                            np.argsort(np.asarray(e), -1), -1)
    np.testing.assert_allclose(order(e0, w0)[same], order(e1, w1)[same],
                               rtol=1e-6)
    out = biased(x)[0]
    want, _ = _reference_layer(biased, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_an_expert_no_token_chose_is_not_touched():
    bias = jnp.zeros((16,)).at[11].set(-10.0)   # expert 11 never chosen
    layer, x = _layer(bias=bias), _tokens(64, 3)
    out, touched, _ = layer(x)
    want, experts = _reference_layer(layer, x)
    assert 11 not in np.asarray(experts)
    assert int(touched) == len(np.unique(np.asarray(experts))) <= 15
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    # the kernel's plan gives that expert no tile
    dest, tile_expert, used, counts = me.plan_tiles(
        jnp.asarray(experts, jnp.int32).reshape(-1), 16, 16)
    assert int(counts[11]) == 0
    assert 11 not in np.asarray(tile_expert)[:int(used)]


# (prompts, tokens asked): one request alone; three on two slots, so that
# the reported slot serves two of them in turn; a prompt of three chunks
@pytest.mark.parametrize("prompts,asked", [((70,), 9), ((90, 300, 140), 7),
                                           ((330,), 4)])
def test_on_routing_reports_what_one_slots_routers_saw_and_chose(
        model, weights, prompts, asked):
    """Every token that the reported slot's request fed to a decode step
    comes back with its position, what each expert layer's router saw
    and what it chose: the reference's own normed tokens and choices at
    that position (float32 weights: no near-tie flips)."""
    rng = np.random.default_rng(sum(prompts))
    prompts = [rng.integers(0, TINY["vocab_size"], n).tolist()
               for n in prompts]
    eng = inference.make_engine(model, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK)
    seen = {}
    eng.on_routing = lambda req, at, saw, chose: seen.setdefault(
        id(req), (req, []))[1].append((at, saw, chose))
    fe = serving.FrontEnd(eng)
    reqs = [fe.submit(p, max_new_tokens=asked) for p in prompts]
    fe.run()
    assert set(eng.state) == {"cp", "experts_touched"}
    assert seen and len(seen) == (2 if len(prompts) > 2 else 1)
    sparse = TINY["n_layers"] - TINY["leading_dense"]
    for req, rows in seen.values():
        n0, tokens = len(req.prompt), list(req.tokens)
        assert len(tokens) == asked
        # the prefill's token at n0, then every token but the last
        assert [at for at, *_ in rows] == list(range(n0, n0 + asked - 1))
        _, routed = ref.forward(weights, jnp.asarray(
            list(req.prompt) + tokens, jnp.int32), TINY)
        assert len(routed) == sparse
        for at, saw, chose in rows:
            assert saw.shape == (sparse, TINY["d_model"])
            assert chose.shape == (sparse, TINY["experts_per_token"])
            for layer, (normed, experts) in enumerate(routed):
                np.testing.assert_allclose(
                    saw[layer], np.asarray(normed[at]), rtol=1e-3,
                    atol=1e-4)
                np.testing.assert_array_equal(
                    _sets(chose[layer]), _sets(experts[at]))


def test_no_token_is_dropped_at_any_load():
    """Every token to ONE set of experts (the capacity form would drop
    most of them): each gets its whole sum."""
    bias = jnp.zeros((16,)).at[jnp.asarray([1, 2, 4, 8])].set(10.0)
    layer, x = _layer(bias=bias), _tokens(257, 5)
    out, touched, _ = layer(x)
    want, experts = _reference_layer(layer, x)
    assert int(touched) == 4
    assert set(np.unique(np.asarray(experts))) == {1, 2, 4, 8}
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-5)


def test_interleaved_rope_pairs_neighbours(model):
    blk = model.blocks[0]
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, 5, 2, 8)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    got = blk._rope_pairs(x, pos)
    want = ref.rope_interleaved(x[0], TINY["rope_theta"])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_config_counts_its_parameters(model):
    cfg = model_config(TINY)
    leaves = jax.tree_util.tree_leaves(model)
    assert cfg.num_params() == sum(int(np.prod(a.shape)) for a in leaves)
    assert cfg.latent_row == 40
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        type(cfg)(mixer="latent")


def _steps_and_chunks(events):
    """``serve/step`` attributes in order, each with the attributes of
    the ``serve/prefill_chunk`` dispatched inside it (None if none)."""
    chunk_of = {e[5]: e[6] for e in events if e[0] == "serve/prefill_chunk"}
    return [(e[6], chunk_of.get(e[4])) for e in events
            if e[0] == "serve/step"]


def test_step_span_counts_rows_pairs_and_experts(model, weights):
    """`serve/step`'s attributes for this kind: live latent rows,
    token-expert pairs dispatched, and the distinct experts the harvested
    dispatches touched, each step's own (not a running total). A step
    with a chunk dispatches ONE program: its ``prefill_tokens`` are the
    chunk's ``tokens``, its ``decode_tokens`` the decoding rows that
    ``rode`` in it, and that program's rows, chunk and decode alike,
    count each expert they chose once a layer."""
    from paddle_tpu.observability import trace
    rng = np.random.default_rng(9)
    eng = inference.make_engine(model, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK)
    fe = serving.FrontEnd(eng)
    for n in (150, 60):
        fe.submit(rng.integers(0, 512, n).tolist(), max_new_tokens=6)
    fe.step()                       # spans off: nothing may pile up
    trace.enable()
    try:
        fe.run()
        events = trace.events()[0]
    finally:
        trace.disable()
        trace.clear()
    steps = [a for name, *_, a in events if name == "serve/step"]
    sparse = TINY["n_layers"] - TINY["leading_dense"]
    k, e = TINY["experts_per_token"], TINY["n_experts"]
    assert steps and all("latent_rows" in a for a in steps)
    for a in steps:
        assert a["expert_tokens"] == k * (a["prefill_tokens"]
                                         + a["decode_tokens"])
        # at most every expert of every expert layer a program run, and
        # a step harvests at most its pipeline's depth of them
        assert 0 <= a["experts_touched"] <= eng.depth * sparse * e
        assert 0 <= a["experts_touched_prefill"] <= 3 * sparse * e
    decoded = [a for a in steps if a["experts_touched"]]
    assert decoded
    # one decoding slot chooses k distinct experts a layer
    assert min(a["experts_touched"] for a in decoded) >= sparse * k
    assert max(a["latent_rows"] for a in steps) >= 150
    rides = [(a, c) for a, c in _steps_and_chunks(events) if c]
    assert any(c["rode"] for _, c in rides)
    for a, c in rides:
        assert (a["prefill_tokens"], a["decode_tokens"]) == (
            c["tokens"], c["rode"])

    # every token of every layer to the same k experts: a program's rows
    # touch k experts a layer, however many rows ride in it; at depth 1
    # each step harvests the program it dispatched
    planted = jnp.zeros((sparse, e)).at[:, jnp.asarray([1, 4, 9, 30])].set(
        10.0)
    forced = build_model(TINY, dict(weights, layers=dict(
        weights["layers"], **{"experts.router_bias": planted})))
    eng = inference.make_engine(forced, max_slots=2, n_pages=16,
                                prefill_chunk=CHUNK, inflight=1)
    fe = serving.FrontEnd(eng)
    trace.enable()
    try:
        for n in (20, 300):
            fe.submit(rng.integers(0, 512, n).tolist(), max_new_tokens=6)
        fe.run()
        events = trace.events()[0]
    finally:
        trace.disable()
        trace.clear()
    pairs = _steps_and_chunks(events)
    assert sum(1 for _, c in pairs if c and c["rode"]) == 3
    for a, c in pairs:
        ran = a["prefill_tokens"] + a["decode_tokens"] > 0
        assert a["experts_touched"] == (sparse * k if ran else 0), (a, c)
        assert a["experts_touched_prefill"] == 0
