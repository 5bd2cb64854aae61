"""Paged continuous-batching engine: serving over a shared page pool.

The invariants: greedy output BIT-IDENTICAL to gpt.generate whatever the
page/chunk geometry; pages allocate on demand, free at retirement, and
get reused; a too-small pool fails loudly instead of wedging."""

import collections
import contextlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.inference.paged_engine import PagedDecodeEngine
from paddle_tpu.models import gpt
from paddle_tpu.testing import faults

# drafts of the prompt-lookup speculation actually accept on this one
REPETITIVE = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]


def _model(max_seq=512, heads=4, kv_heads=None, rope=False, layers=2):
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=max_seq, d_model=32,
                        n_layers=layers, n_heads=heads,
                        n_kv_heads=kv_heads, dtype=jnp.float32,
                        rope=rope)
    return gpt.GPT(cfg, seed=0)


def _assert_pool_drained(eng, n_pages):
    """After every request retires, each pool page is either on the
    allocator free list or warm in the prefix cache at refcount ZERO
    (reclaimable) — never still mapped into a slot."""
    cached = eng._prefix.cached_pages if eng._prefix is not None else 0
    shared = eng._prefix.shared_pages if eng._prefix is not None else 0
    assert eng.free_pages + cached == n_pages
    assert shared == 0


def _reference(model, prompt, n_new, eos=None):
    toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
    out = model.generate(toks, max_new_tokens=n_new,
                         max_len=len(prompt) + n_new, eos_id=eos)
    got = list(np.asarray(out)[0, len(prompt):])
    if eos is not None and eos in got:
        got = got[:got.index(eos) + 1]
    return got


def test_paged_parity_with_generate_mixed_lengths():
    model = _model()
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 96, size=n)) for n in (5, 170, 23)]
    eng = PagedDecodeEngine(model, n_pages=12, max_slots=2,
                            steps_per_call=4)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.step()
    eng.run()
    for req, p in zip(reqs, prompts):
        assert req.tokens == _reference(model, p, 9), len(p)
    # everything retired -> every page free or warm in the prefix
    # cache at refcount zero (nothing still mapped)
    _assert_pool_drained(eng, 12)


@pytest.mark.parametrize("rope,kv_heads", [(False, None), (True, 2)])
def test_streams_match_generate_rope_and_grouped_heads(rope, kv_heads):
    model = _model(rope=rope, kv_heads=kv_heads)
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 96, size=n)) for n in (5, 170, 23)]
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=3)
    reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    eng.run()
    for req, p in zip(reqs, prompts):
        assert req.tokens == _reference(model, p, 9), (rope, kv_heads)


def test_paged_spec_streams_match_generate():
    """Speculative decode on the paged path: prompt-lookup drafts +
    the per-layer verify must leave greedy streams bit-identical to
    gpt.generate."""
    model = _model()
    rs = np.random.RandomState(1)
    prompts = [REPETITIVE, list(rs.randint(0, 96, size=40))]
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=3, speculative_k=4)
    reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.run()
    for req, p in zip(reqs, prompts):
        assert req.tokens == _reference(model, p, 12)


def test_paged_spec_stops_at_eos_like_generate():
    """A speculative step accepts several tokens at once: the stream
    still ends at the first eos, as gpt.generate's does."""
    model = _model()
    rs = np.random.RandomState(3)
    prompt = list(rs.randint(0, 96, size=31))
    ref = _reference(model, prompt, 24, eos=7)
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=4, speculative_k=3)
    req = eng.submit(prompt, max_new_tokens=24, eos_id=7)
    eng.run()
    assert req.tokens == ref


def test_decode_step_lowers_to_the_pinned_program():
    """The decode step's program (`PagedDecodeEngine._multi_impl`, what
    `dispatch_fn_args` gives) lowers to the text pinned here: the digest
    is of the program before prompt chunks rode in decode steps, and the
    ride was to leave every decode-only step as it was. A change that
    alters the decode program on purpose pins its new digest."""
    import hashlib
    eng = PagedDecodeEngine(_model(), n_pages=8, max_slots=2,
                            steps_per_call=2)
    fn, args = eng.dispatch_fn_args()
    text = fn.lower(*args).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == "b9102bd2604170d7"


def test_paged_pages_allocated_on_demand_and_reused():
    model = _model()
    rs = np.random.RandomState(1)
    eng = PagedDecodeEngine(model, n_pages=4, max_slots=1,
                            steps_per_call=8)
    # 120-token prompt + 20 new tokens: 1 page -> grows to 2
    p1 = list(rs.randint(0, 96, size=120))
    r1 = eng.submit(p1, max_new_tokens=20)
    eng.run()
    assert r1.tokens == _reference(model, p1, 20)
    assert eng.free_pages == 4
    # the next sequence reuses the freed pages
    p2 = list(rs.randint(0, 96, size=100))
    r2 = eng.submit(p2, max_new_tokens=5)
    eng.run()
    assert r2.tokens == _reference(model, p2, 5)
    assert eng.free_pages == 4


def test_paged_eos_and_gqa():
    model = _model(heads=4)
    # GQA variant
    cfg = gpt.GPTConfig(vocab_size=96, max_seq_len=512, d_model=32,
                        n_layers=2, n_heads=4, n_kv_heads=2,
                        dtype=jnp.float32)
    gqa = gpt.GPT(cfg, seed=0)
    prompt = [3, 4] * 10
    ref = _reference(gqa, prompt, 12)
    eos = ref[3]
    want = _reference(gqa, prompt, 12, eos=eos)
    eng = PagedDecodeEngine(gqa, n_pages=6, max_slots=1,
                            steps_per_call=4)
    r = eng.submit(prompt, max_new_tokens=12, eos_id=eos)
    eng.run()
    assert r.done and r.tokens == want


def test_paged_pool_too_small_fails_loudly():
    model = _model()
    eng = PagedDecodeEngine(model, n_pages=1, max_slots=2,
                            page_size=128)
    eng.submit(list(range(90)) * 2, max_new_tokens=4)  # 180 tok: 2 pages
    with pytest.raises(MemoryError):
        eng.run()


def test_paged_admission_waits_for_pages():
    """Admission blocks on pool pressure and resumes after retirement
    instead of failing, as long as something is decoding."""
    model = _model()
    rs = np.random.RandomState(2)
    eng = PagedDecodeEngine(model, n_pages=3, max_slots=2,
                            steps_per_call=4)
    p1 = list(rs.randint(0, 96, size=200))   # 2 pages
    p2 = list(rs.randint(0, 96, size=120))   # needs 1+ page
    r1 = eng.submit(p1, max_new_tokens=6)
    r2 = eng.submit(p2, max_new_tokens=6)
    eng.run()
    assert r1.tokens == _reference(model, p1, 6)
    assert r2.tokens == _reference(model, p2, 6)
    _assert_pool_drained(eng, 3)


def test_idle_slot_never_corrupts_live_pages():
    """Code-review regression (confirmed by repro): an idle slot's
    padded page table points at pool page 0; its per-step write must go
    to the scratch page, not clobber the live sequence that owns page 0.
    One request in a 2-slot engine (slot 1 idle the whole run) must
    match gpt.generate exactly."""
    model = _model()
    rs = np.random.RandomState(9)
    prompt = list(rs.randint(0, 96, size=140))   # owns pages 0..1
    eng = PagedDecodeEngine(model, n_pages=6, max_slots=2,
                            steps_per_call=4)
    r = eng.submit(prompt, max_new_tokens=16)
    eng.run()
    assert r.tokens == _reference(model, prompt, 16)


def test_page_size_must_divide_buckets():
    model = _model()
    with pytest.raises(ValueError):
        PagedDecodeEngine(model, n_pages=4, max_slots=1, page_size=384)


@pytest.mark.parametrize("spec", [0, 4])
@pytest.mark.parametrize("depth", [2, 3])
def test_paged_pipelined_depths_bit_identical(depth, spec):
    """ISSUE 4: the pipelined paged engine (lag-one harvest, one packed
    transfer per dispatch) serves byte-identical streams to depth=1,
    plain and speculative, with every page back in the pool at
    drain."""
    model = _model()
    rs = np.random.RandomState(6)
    prompts = [list(rs.randint(0, 96, size=n)) for n in (5, 170, 23)]
    if spec:
        prompts[0] = REPETITIVE

    def run(d):
        eng = PagedDecodeEngine(model, n_pages=12, max_slots=2,
                                steps_per_call=4, inflight=d,
                                speculative_k=spec)
        reqs = [eng.submit(p, max_new_tokens=9) for p in prompts]
        eng.step()
        eng.run()
        _assert_pool_drained(eng, 12)
        assert all(r.done and not r.failed for r in reqs)
        return [list(r.tokens) for r in reqs]

    base = run(1)
    for got, p in zip(base, prompts):
        assert got == _reference(model, p, 9), len(p)
    assert run(depth) == base


def test_paged_warmup_pretraces():
    model = _model()
    eng = PagedDecodeEngine(model, n_pages=8, max_slots=2,
                            steps_per_call=2, buckets=(16, 32),
                            warmup=True)
    assert eng._prefill_fn._cache_size() == 2
    assert eng._multi_fn._cache_size() == 1
    rs = np.random.RandomState(7)
    p = list(rs.randint(0, 96, size=20))
    r = eng.submit(p, max_new_tokens=6)
    eng.run()
    assert r.tokens == _reference(model, p, 6)
    assert eng._prefill_fn._cache_size() == 2, "serving recompiled"
    assert eng._multi_fn._cache_size() == 1, "serving recompiled"


@contextlib.contextmanager
def _eager_primitives():
    """Counts, by name, every primitive JAX evaluates EAGERLY while the
    block runs: each is a device program of its own (an ``.at[].set``
    is five of them). A warmed ``jax.jit`` call takes the C++ fast path
    and does not come by here, one that has to trace does (as ``jit``);
    an upload (``device_put``) is no program and no primitive here."""
    from jax._src import core
    seen = collections.Counter()
    orig = core.EvalTrace.process_primitive

    def counting(self, primitive, args, params):
        seen[primitive.name] += 1
        return orig(self, primitive, args, params)

    core.EvalTrace.process_primitive = counting
    try:
        yield seen
    finally:
        core.EvalTrace.process_primitive = orig


def test_eager_primitive_counter_sees_a_slot_write():
    """The counter the tests below lean on: an eager slot write is
    seen, a warmed jitted call and an upload are not."""
    import jax
    x = jnp.zeros((4,), jnp.int32)
    bump = jax.jit(lambda a: a + 1)
    bump(x)
    with _eager_primitives() as eager:
        bump(x)
        jnp.asarray(np.zeros((3,), np.int32))
    assert not eager, dict(eager)
    with _eager_primitives() as eager:
        x.at[1].set(3)
    assert eager["scatter"] == 1


def _count_dispatches(eng):
    """Wraps every jitted program of ``eng``; returns the Counter of
    calls by attribute name."""
    calls = collections.Counter()
    for name in ("_prefill_fn", "_prefill_sfx_fn", "_ride_fn",
                 "_multi_fn", "_verify_fn"):
        def counted(*a, _fn=getattr(eng, name), _name=name):
            calls[_name] += 1
            return _fn(*a)
        setattr(eng, name, counted)
    return calls


@pytest.mark.parametrize("spec", [0, 3])
def test_admission_is_one_program_and_retirement_none(spec):
    """ISSUE 34: on a warmed engine an admission dispatches exactly ONE
    device program, the prefill that also installs the slot's decode
    state, cold or through the prefix cache's suffix path, and a
    retirement dispatches none: no eager program runs between two
    jitted dispatches."""
    model = _model()
    eng = PagedDecodeEngine(model, n_pages=8, max_slots=2,
                            buckets=(16, 256), warmup=True,
                            speculative_k=spec)
    live = [np.asarray(v).copy() for v in
            (eng.lengths, eng.last, eng.active, eng.remaining,
             eng.eos_ids)]
    assert not any(v.any() for v in live[:4]) and (live[4] == -1).all(), \
        "warm-up touched the live slot vectors"
    calls = _count_dispatches(eng)
    decode = "_verify_fn" if spec else "_multi_fn"
    rs = np.random.RandomState(11)
    shared = list(rs.randint(0, 96, size=128))          # one full page
    prompts = [list(rs.randint(0, 96, size=9)),          # cold, bucket 16
               shared + REPETITIVE,                      # cold, registers
               shared + list(rs.randint(0, 96, size=5))]  # warm: suffix
    want = ["_prefill_fn", "_prefill_fn", "_prefill_sfx_fn"]
    for prompt, prefill in zip(prompts, want):
        req = eng.submit(prompt, max_new_tokens=4)
        calls.clear()
        with _eager_primitives() as eager:
            eng._admit_waiting()
        assert not eager, dict(eager)
        assert calls == {prefill: 1}
        with _eager_primitives() as eager:
            eng.run()
        assert req.done and req.tokens == _reference(model, prompt, 4)
        assert not eager, dict(eager)                # the retirement
        assert set(calls) == {prefill, decode}
    assert not np.asarray(eng.active).any()


@pytest.mark.parametrize("spec", [0, 3])
@pytest.mark.parametrize("ends", ["budget_at_prefill", "eos_at_prefill",
                                  "budget_at_decode", "eos_at_decode"])
def test_device_clears_active_at_every_end(ends, spec):
    """However a request ends, the program that sampled its last token
    has already cleared the slot's ``active`` on the device: the host
    writes nothing at retirement (no eager primitive runs), and the
    neighbour slot goes on decoding."""
    model = _model()
    rs = np.random.RandomState(1)
    prompt = list(rs.randint(0, 96, size=40))
    other = list(rs.randint(0, 96, size=9))
    ref = _reference(model, prompt, 8)
    # the first token the stream had not held before: an eos to stop at
    k = next(i for i in range(1, 8) if ref[i] not in ref[:i])
    n_new, eos = {"budget_at_prefill": (1, None),
                  "eos_at_prefill": (8, ref[0]),
                  "budget_at_decode": (k + 1, None),
                  "eos_at_decode": (8, ref[k])}[ends]
    eng = PagedDecodeEngine(model, n_pages=8, max_slots=2, buckets=(64,),
                            warmup=True, speculative_k=spec)
    with _eager_primitives() as eager:
        long = eng.submit(other, max_new_tokens=60)
        req = eng.submit(prompt, max_new_tokens=n_new, eos_id=eos)
        eng._admit_waiting()
        slot, neighbour = (eng._slot_req.index(req),
                           eng._slot_req.index(long))
        while not req.done:
            eng.step()
        eng.drain()
    assert not eager, dict(eager)
    assert req.tokens == (ref[:1] if "prefill" in ends else ref[:k + 1])
    active = np.asarray(eng.active)
    assert not active[slot]
    assert not long.done and active[neighbour]
    eng.run()
    assert long.tokens == _reference(model, other, 60)
    assert not np.asarray(eng.active).any()
    _assert_pool_drained(eng, 8)


def _serve_admission_case(model, case, depth):
    """The generated tokens of ``case``'s requests at pipeline depth
    ``depth``, and what `gpt.generate` gives for them."""
    rs = np.random.RandomState(17)
    kw = dict(n_pages=16, max_slots=2, steps_per_call=2, inflight=depth)
    page = list(rs.randint(0, 96, size=128))
    if case == "budget_one":
        jobs = [(list(rs.randint(0, 96, size=n)), 1, None)
                for n in (5, 23)]
    elif case == "first_token_eos":
        prompt = list(rs.randint(0, 96, size=19))
        jobs = [(prompt, 6, _reference(model, prompt, 1)[0]),
                (list(rs.randint(0, 96, size=7)), 6, None)]
    elif case == "cow_prefix_hit":
        # an exact-page-multiple prompt served twice: the second is a
        # full match, its last page copied before the final row lands
        both = page + list(rs.randint(0, 96, size=128))
        jobs = [(both, 5, None), (both, 7, None)]
        kw["max_slots"] = 1
    elif case == "spec_prefix_hit":
        # the history row of a warm hit comes from the whole prompt,
        # not from the suffix the program prefills
        jobs = [(page + REPETITIVE, 9, None),
                (page + REPETITIVE[:7], 9, None)]
        kw.update(max_slots=1, speculative_k=4)
    else:
        assert case == "prefill_only"
        jobs = [(list(rs.randint(0, 96, size=n)), 6, None)
                for n in (40, 130)]
    want = [_reference(model, p, n, eos) for p, n, eos in jobs]
    if case != "prefill_only":
        eng = PagedDecodeEngine(model, **kw)
        reqs = [eng.submit(p, max_new_tokens=n, eos_id=eos)
                for p, n, eos in jobs]
        eng.run()
        assert not np.asarray(eng.active).any()
        _assert_pool_drained(eng, 16)
        return [list(r.tokens) for r in reqs], want
    # a prefill replica samples the first token and never decodes; the
    # stream continues on the replica that takes the pages over
    pe = PagedDecodeEngine(model, prefill_only=True, **kw)
    de = PagedDecodeEngine(model, **kw)
    outs = []
    for p, n, _ in jobs:
        r = pe.submit(p, max_new_tokens=n)
        while not r.tokens:
            pe.step()
        pe.drain()
        slot = pe._slot_req.index(r)
        assert not np.asarray(pe.active).any()
        assert int(np.asarray(pe.lengths)[slot]) == len(p)
        outs.append(de.submit_handoff(*pe.detach_handoff(r)))
    de.run()
    return [list(r.tokens) for r in outs], want


@pytest.mark.parametrize("case", ["budget_one", "first_token_eos",
                                  "cow_prefix_hit", "spec_prefix_hit",
                                  "prefill_only"])
def test_admission_cases_match_generate_at_both_depths(case):
    """What the prefill program now installs (a budget that ends at the
    first token, a first-token eos, a copy-on-write page under a warm
    hit, the speculative history, a prefill-only slot that must stay
    inactive) leaves every stream `gpt.generate`'s, synchronous and
    pipelined."""
    model = _model()
    got, want = _serve_admission_case(model, case, 1)
    assert got == want
    assert _serve_admission_case(model, case, 2)[0] == got


def _primitives(jaxpr, prefix):
    """(primitive name, operand shapes) of every equation of ``jaxpr``
    and the jaxprs inside it whose name starts with ``prefix``."""
    import jax
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith(prefix):
            found.append((eqn.primitive.name,
                          [tuple(v.aval.shape) for v in eqn.invars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_primitives(sub, prefix))
    return found


def test_decode_dispatch_scatters_into_no_pool():
    """The decode step writes the fresh KV row through the write
    launch of `paged_append_attend` and nowhere else: no ``scatter`` of
    the dispatch's jaxpr takes a pool (the CPU-verifiable proxy for the
    pool traffic a per-token scatter costs; on the chip
    tests/test_chip_compile.py::test_decode_dataflow_copies_no_pool
    holds the compiled program to it)."""
    import jax
    model = _model()
    eng = PagedDecodeEngine(model, n_pages=12, max_slots=2,
                            steps_per_call=4)
    fn, args = eng.dispatch_fn_args()
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    pool = tuple(eng.kp.shape)
    view = (pool[0] * pool[1],) + pool[2:]
    assert _primitives(jaxpr, "pallas_call")       # the walk sees them
    on_pool = [(name, shapes) for name, shapes
               in _primitives(jaxpr, "scatter")
               if pool in shapes or view in shapes]
    assert not on_pool, on_pool


@pytest.mark.parametrize("spec", [0, 3])
def test_launches_a_step_follow_the_layers(spec):
    """A decode step is `paged_append_attend` once a layer, two
    launches (row write, attend); a speculative verify is one read-only
    attend a layer. Counted from the dispatch's jaxpr (scan-trip
    weighted), so the assert holds on any backend; the AOT lowering's
    custom-call counter returns a number (0 in CPU interpret mode,
    where pallas lowers to inline HLO; one a launch on a TPU)."""
    from paddle_tpu.observability import devprof
    model = _model(layers=3)
    eng = PagedDecodeEngine(model, n_pages=20, max_slots=2,
                            steps_per_call=4, speculative_k=spec)
    fn, args = eng.dispatch_fn_args()
    per_step = devprof.count_pallas_launches(fn, *args) / eng.chunk
    assert per_step == (1 if spec else 2) * model.cfg.n_layers
    n = devprof.count_hlo_custom_calls(fn, *args)
    assert n is not None and n >= 0


@pytest.mark.parametrize("spec", [0, 3])
def test_poison_eviction_scrubs_and_isolates(spec):
    """Non-finite logits evict ONLY the poisoned slot; the survivor's
    stream is untouched and the retired slot's pages return to the pool
    (free or refcount-zero cached)."""
    model = _model()
    rs = np.random.RandomState(5)
    pa, pb = (list(rs.randint(0, 96, size=n)) for n in (5, 23))
    eng = PagedDecodeEngine(model, n_pages=14, max_slots=2,
                            steps_per_call=2, speculative_k=spec)
    ra = eng.submit(pa, max_new_tokens=8)
    rb = eng.submit(pb, max_new_tokens=8)
    with faults.inject("engine.poison_logits", "nan", slot=0):
        eng.run()
    assert ra.failed and "non-finite" in ra.error
    assert not rb.failed and rb.tokens == _reference(model, pb, 8)
    _assert_pool_drained(eng, 14)


@pytest.mark.parametrize("removed", [{"mega": True}, {"fused": False}])
def test_removed_decode_switches_are_type_errors(removed):
    """One decode step: the arguments that chose another are gone."""
    with pytest.raises(TypeError):
        PagedDecodeEngine(_model(), n_pages=4, **removed)
    assert not hasattr(PagedDecodeEngine, "autotune")


def test_warm_prefix_hit_prefills_only_suffix():
    """Acceptance: a warm shared-prefix submit must route through the
    SUFFIX prefill only (the full-prompt prefill is never dispatched)
    and account every cached token in serve/prefix_hit_tokens."""
    from paddle_tpu import stats

    model = _model()
    rs = np.random.RandomState(21)
    sys_prompt = list(rs.randint(0, 96, size=290))   # 2 full pages + 34
    tail_a = list(rs.randint(0, 96, size=11))
    tail_b = list(rs.randint(0, 96, size=17))
    eng = PagedDecodeEngine(model, n_pages=16, max_slots=1,
                            steps_per_call=4)
    calls = _count_dispatches(eng)
    prefills = lambda: (calls["_prefill_fn"], calls["_prefill_sfx_fn"])

    r1 = eng.submit(sys_prompt + tail_a, max_new_tokens=8)
    eng.run()
    assert prefills() == (1, 0)                # cold: full prefill
    h0 = stats.get("serve/prefix_hit_tokens")

    r2 = eng.submit(sys_prompt + tail_b, max_new_tokens=8)
    eng.run()
    assert prefills() == (1, 1)                # warm: suffix ONLY
    # both full pages (256 tokens) served from cache
    assert stats.get("serve/prefix_hit_tokens") - h0 == 256
    assert r1.tokens == _reference(model, sys_prompt + tail_a, 8)
    assert r2.tokens == _reference(model, sys_prompt + tail_b, 8)


def test_shared_prefix_pages_read_only_and_divergence():
    """Refcount/COW correctness: the cached prefix pages a second
    request maps must stay BIT-IDENTICAL to the cold prefill that wrote
    them (read-only mapping — the sharer's suffix and decode appends
    land in private pages), while the streams diverge after the shared
    point exactly as the dense reference does."""
    model = _model()
    rs = np.random.RandomState(22)
    shared = list(rs.randint(0, 96, size=256))       # exactly 2 pages
    pa = shared + list(rs.randint(0, 96, size=30))
    pb = shared + list(rs.randint(0, 96, size=45))
    eng = PagedDecodeEngine(model, n_pages=16, max_slots=1,
                            steps_per_call=4)
    ra = eng.submit(pa, max_new_tokens=8)
    eng.run()
    pids = [eng._prefix._nodes[d] for d in eng._prefix.chain(shared)]
    assert len(pids) == 2
    L, P = eng.cfg.n_layers, eng.P
    ids = np.add.outer(np.arange(L) * P, pids).ravel()
    kp_before = np.asarray(eng.kp[ids])
    vp_before = np.asarray(eng.vp[ids])

    rb = eng.submit(pb, max_new_tokens=8)
    eng.run()
    np.testing.assert_array_equal(np.asarray(eng.kp[ids]), kp_before)
    np.testing.assert_array_equal(np.asarray(eng.vp[ids]), vp_before)
    assert ra.tokens == _reference(model, pa, 8)
    assert rb.tokens == _reference(model, pb, 8)


def test_eviction_returns_only_refcount_zero_pages():
    """Retirement of ONE sharer must not free (or make reclaimable) the
    prefix pages the other sharer still maps; reclaim frees only
    refcount-zero pages, and only under explicit pressure."""
    model = _model()
    rs = np.random.RandomState(23)
    shared = list(rs.randint(0, 96, size=256))
    pa = shared + [1, 2, 3]
    pb = shared + [4, 5]
    eng = PagedDecodeEngine(model, n_pages=16, max_slots=2,
                            steps_per_call=2)
    ra = eng.submit(pa, max_new_tokens=24)   # long: retires last
    rb = eng.submit(pb, max_new_tokens=2)    # short: retires first
    while not rb.done:
        eng.step()
    eng.drain()
    pids = [eng._prefix._nodes[d] for d in eng._prefix.chain(shared)]
    assert not ra.done
    # b retired: the shared pages are still mapped by a (refcount 1) —
    # neither free nor reclaimable
    assert eng._prefix._refs[pids[0]] == 1
    assert eng._prefix.reclaimable_pages == 0
    assert all(p not in eng._alloc._free for p in pids)
    assert eng._prefix.reclaim(8) == 0       # nothing at refcount zero

    eng.run()
    assert ra.done and ra.tokens == _reference(model, pa, 24)
    # a retired too: refcount zero, reclaimable, but still warm (NOT on
    # the allocator free list) until reclaim is asked for them
    assert eng._prefix._refs[pids[0]] == 0
    assert all(p not in eng._alloc._free for p in pids)
    free0 = eng.free_pages
    assert eng._prefix.reclaim(1) == 1       # LRU-oldest only
    assert eng.free_pages == free0 + 1


def test_stale_invalidate_keeps_reregistered_chain():
    """A dead page's SECOND invalidation (a late sharer failing after
    the poisoned prompt was already re-registered with healthy pages)
    must not de-canonicalize the new copy's trie node, and a later
    reclaim of the healthy page must not crash on the missing node."""
    from paddle_tpu.inference.prefix_cache import PrefixCache
    from paddle_tpu.ops.pallas.paged_attention import PageAllocator

    alloc = PageAllocator(8, 128)
    pc = PrefixCache(alloc, 128)
    toks = list(range(128))
    tab = alloc.reserve([], 128)
    pc.register(toks, tab)             # slot A registers: refs=1
    old = tab[0]
    pc.ref(old)                        # slot B maps it too: refs=2
    assert pc.invalidate(old) is None  # A nan-fails: node gone, dead
    assert pc.lookup(toks) == []       # no longer canonical
    assert pc.unref(old) is None       # A releases: refs=1 (B holds)
    tab2 = alloc.reserve([], 128)
    pc.register(toks, tab2)            # healthy re-registration
    new = tab2[0]
    assert pc.invalidate(old) is None  # B fails later: STALE pid
    got = pc.lookup(toks)
    assert got == [new], "stale invalidate de-canonicalized the chain"
    pc.unref(new)                      # drop lookup's ref
    pc.unref(new)                      # registrant retires: warm LRU
    assert pc.unref(old) == old        # B releases: dead page freed
    assert old in alloc._free
    assert pc.reclaim(8) == 1          # healthy page reclaims cleanly
    assert new in alloc._free
    assert pc.lookup(toks) == []


def test_poisoned_shared_page_fails_every_sharer_loudly():
    """Blast-radius probe for prefix sharing: one poisoned shared page
    must fail EVERY request that has it mapped via the non-finite-logit
    guard (failed=True, never silent corruption), while a request that
    shares nothing decodes normally. The poison must NOT outlive its
    sharers: the eviction drops the prefix's trie nodes and scrubs the
    freed pages, so the next submit of the same (popular) prompt
    prefills cold into clean pages and succeeds — one bad page is a
    loud transient, not a permanent DoS of that prompt."""
    from paddle_tpu import stats
    from paddle_tpu.testing import faults

    model = _model()
    rs = np.random.RandomState(24)
    shared = list(rs.randint(0, 96, size=256))
    cold = list(rs.randint(0, 96, size=40))
    eng = PagedDecodeEngine(model, n_pages=24, max_slots=2,
                            steps_per_call=2)
    r0 = eng.submit(shared + [7], max_new_tokens=4)
    eng.run()                                # establishes the cache
    assert not r0.failed

    with faults.inject("paged.shared_page", "nan", n=64):
        # two slots: rb and rc BOTH map the poisoned shared pages
        # before either harvest detects the damage
        rb = eng.submit(shared + [8, 9], max_new_tokens=6)
        rc = eng.submit(shared + [10], max_new_tokens=6)
        rd = eng.submit(cold, max_new_tokens=6)
        eng.run()
    assert rb.failed and rc.failed           # every sharer fails LOUDLY
    assert rb.error and "non-finite" in rb.error
    assert rc.error and "non-finite" in rc.error
    assert not rd.failed                     # non-sharer unaffected
    assert rd.tokens == _reference(model, cold, 6)

    # self-heal: the fault is gone, the poisoned trie nodes are
    # invalidated and their pages scrubbed — the SAME prompt recovers
    # after one cold prefill (no hit) ...
    h0 = stats.get("serve/prefix_hit_tokens")
    re_ = eng.submit(shared + [11], max_new_tokens=4)
    eng.run()
    assert not re_.failed
    assert re_.tokens == _reference(model, shared + [11], 4)
    assert stats.get("serve/prefix_hit_tokens") == h0   # cold re-prefill
    # ... and its healthy copy is canonical again: the next sharer hits
    rf = eng.submit(shared + [12], max_new_tokens=4)
    eng.run()
    assert not rf.failed
    assert rf.tokens == _reference(model, shared + [12], 4)
    assert stats.get("serve/prefix_hit_tokens") - h0 == 256


def test_bitflip_on_shared_page_corrupts_visibly():
    """The bitflip payload variant of the blast-radius probe: a single
    flipped bit in a shared K page must visibly corrupt the sharer's
    stream (diverging from the clean reference) — shared-prefix KV is
    load-bearing state, not a soft hint."""
    from paddle_tpu.testing import faults

    model = _model()
    rs = np.random.RandomState(25)
    shared = list(rs.randint(0, 96, size=256))
    eng = PagedDecodeEngine(model, n_pages=16, max_slots=1,
                            steps_per_call=2)
    eng.submit(shared + [7], max_new_tokens=4)
    eng.run()
    pids = [eng._prefix._nodes[d] for d in eng._prefix.chain(shared)]
    before = np.asarray(eng.kp[pids[0]])

    # flip the sign/exponent bit of a mid-page element on every layer's
    # view of the first shared page
    with faults.inject("paged.shared_page", "bitflip", offset=2048,
                       bit=7):
        eng.submit(shared + [8], max_new_tokens=4)
        eng.run()
    after = np.asarray(eng.kp[pids[0]])
    assert (before != after).any(), "bitflip never landed in the pool"


def test_prefix_cache_off_restores_free_everything():
    """PT_PAGED_PREFIX=0 restores the pre-ISSUE-6 lifecycle: no trie,
    retirement frees every page straight back to the allocator."""
    model = _model()
    rs = np.random.RandomState(26)
    p = list(rs.randint(0, 96, size=200))
    eng = PagedDecodeEngine(model, n_pages=6, max_slots=1,
                            steps_per_call=4, prefix=False)
    assert eng._prefix is None
    r1 = eng.submit(p, max_new_tokens=6)
    eng.run()
    assert eng.free_pages == 6
    r2 = eng.submit(p, max_new_tokens=6)
    eng.run()
    assert r1.tokens == r2.tokens == _reference(model, p, 6)
    assert eng.free_pages == 6


def test_pool_pressure_reclaims_warm_prefix_pages():
    """Admission under pool pressure reclaims LRU refcount-zero prefix
    pages instead of failing: a pool exactly big enough for one
    resident request must still serve a second, different prompt after
    the first retires (its warm pages get reclaimed)."""
    model = _model()
    rs = np.random.RandomState(27)
    pa = list(rs.randint(0, 96, size=256))
    pb = list(rs.randint(0, 96, size=256))
    eng = PagedDecodeEngine(model, n_pages=3, max_slots=1,
                            steps_per_call=2)
    ra = eng.submit(pa, max_new_tokens=4)
    eng.run()
    assert eng._prefix.cached_pages == 2     # pa's pages warm
    rb = eng.submit(pb, max_new_tokens=4)    # needs reclaim to fit
    eng.run()
    assert ra.tokens == _reference(model, pa, 4)
    assert rb.tokens == _reference(model, pb, 4)
    # and a warm resubmit of pb still hits whatever stayed cached
    r2 = eng.submit(pb, max_new_tokens=4)
    eng.run()
    assert r2.tokens == rb.tokens


def test_paged_share_weights_with_decode_engine_donor():
    """The bench path: a PagedDecodeEngine built from a DecodeEngine's
    stacked weights (no model, no duplicate copy) serves identically."""
    from paddle_tpu.inference.decode_engine import DecodeEngine

    model = _model()
    rs = np.random.RandomState(4)
    prompts = [list(rs.randint(0, 96, size=n)) for n in (9, 130)]
    donor = DecodeEngine(model, max_slots=2, max_len=192)
    r_ref = [donor.submit(p, max_new_tokens=8) for p in prompts]
    donor.run()

    eng = PagedDecodeEngine(None, n_pages=8, max_slots=2,
                            steps_per_call=3, share_weights_with=donor)
    assert eng._stacked is donor._stacked
    r = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run()
    for a, b in zip(r_ref, r):
        assert a.tokens == b.tokens
