"""AOT compiles of the main-path Pallas kernels at GPT-3 1.3B widths for a
DESCRIBED TPU v5e (no chip attached): what interpret mode cannot see —
tiling rules, accumulator types, VMEM limits — the chip's own compiler
refuses here, at no chip time (on-chip-measurement guide, section 2).

Every kernel a default path of ``chip_smoke.py`` reaches is here: the
train step's flash attention and fused CE (forward and backward), the
paged engine's ``paged_append_attend`` (the decode step) and
``paged_decode_attention`` (suffix prefill), plus ``decode_attention``
and ``int8_matmul`` (the contiguous engine) and, at Brumby-14B's widths,
``retention_step`` (the decode step of retention layers) with the
dataflow that keeps its 4.4 GB state pool in place, and at
JoyAI-LLM-Flash's ``latent_append_attend`` / ``latent_chunk_attend`` (the
latent pages' decode step and prompt chunk) and ``moe_experts`` (the
grouped product over the experts' stacks, which must stay where they
are).

The topology is described inside a module-scoped fixture of THIS file —
only the xdist worker that is handed the file loads libtpu — and the
compiles run in the test's own process with the persistent compilation
cache off (a described-device executable is written to the cache but
cannot be read back without a chip).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest

DM, LAYERS, HEADS, HEAD_DIM, VOCAB, SEQ = 2048, 24, 16, 128, 50304, 2048
BATCH, SLOTS, PAGE, POOL_PAGES = 4, 8, 128, 40
# the paged kernels as the benchmark's dense serving cells run them: 16
# slots, a table of SEQ // PAGE = 16 columns, the default geometry
PAGED_SLOTS = 16
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, shapes, sharding, donate=()):
    """Compile ``fn`` for the described chip from shapes alone and
    return the compiled program's text."""
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)
    return jax.jit(fn, donate_argnums=donate).lower(
        *args).compile().as_text()


def _compile(fn, shapes, sharding):
    """The lines of the Pallas kernels in ``fn``'s compiled program."""
    return [ln for ln in _compiled_text(fn, shapes, sharding).splitlines()
            if "tpu_custom_call" in ln]


def _sds(shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _named(calls, name):
    return sum(name in ln for ln in calls)


@pytest.mark.parametrize("shape,kernels", [
    # the training cell, the parked head-64 shape, an 8k context: float32
    # dQ for the sequence stays in VMEM and the backward is one kernel
    ((BATCH, SEQ, HEADS, HEAD_DIM), ("flash_attention_bwd",)),
    ((8, SEQ, HEADS, 64), ("flash_attention_bwd",)),
    ((1, 8192, HEADS, HEAD_DIM), ("flash_attention_bwd",)),
    # 16k: it cannot (a head of 64 is padded to the lanes there), and the
    # dK/dV and dQ kernels run
    ((1, 16384, 8, HEAD_DIM),
     ("flash_attention_bwd_dkdv", "flash_attention_bwd_dq")),
    ((1, 16384, HEADS, 64),
     ("flash_attention_bwd_dkdv", "flash_attention_bwd_dq")),
], ids=["cell", "head64", "8k", "16k-two-kernels", "16k-head64"])
def test_flash_attention_fwd_bwd(one_chip, shape, kernels):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    q = _sds(shape)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               interpret=False).astype(jnp.float32).sum()

    calls = _compile(jax.grad(loss, argnums=(0, 1, 2)), (q, q, q),
                     one_chip)
    # a call's line holds the operation's name and then the kernel's own
    names = sorted(re.findall(r"flash_attention_\w+", ln)[-1]
                   for ln in calls)
    assert names == sorted(("flash_attention_fwd",) + kernels)


def test_fused_ce_fwd_bwd(one_chip):
    from paddle_tpu.ops.pallas.fused_ce import fused_softmax_cross_entropy
    n = BATCH * (SEQ - 1)            # the shifted-LM row count of the step
    shapes = (_sds((n, DM)), _sds((VOCAB, DM)), _sds((n,), jnp.int32))

    def loss(x, w, labels):
        return fused_softmax_cross_entropy(x, w, labels,
                                           interpret=False).sum()

    calls = _compile(jax.grad(loss, argnums=(0, 1)), shapes, one_chip)
    for name in ("fused_ce_fwd", "fused_ce_bwd_dx", "fused_ce_bwd_dw"):
        assert _named(calls, name) == 1, name


def _paged_shapes(pool_pages=POOL_PAGES):
    pool = _sds((LAYERS * pool_pages + 1, HEADS, PAGE, HEAD_DIM))
    q = _sds((PAGED_SLOTS, HEADS, HEAD_DIM))
    table = _sds((PAGED_SLOTS, SEQ // PAGE), jnp.int32)
    vec = _sds((PAGED_SLOTS,), jnp.int32)
    return pool, q, table, vec


def test_paged_append_attend(one_chip):
    """Two launches: the in-place row write, then the read-only attend
    (both carry the ``paged_append_attend`` family name that the
    benchmark's roofline share sums), at 16 slots x 16 columns with the
    default geometry: all 16 heads of a page a program."""
    from paddle_tpu.ops.pallas.paged_attention import (
        _default_head_block, paged_append_attend)
    pool, q, table, vec = _paged_shapes()
    assert _default_head_block(PAGE, HEADS, HEAD_DIM, BF16, 1) == HEADS
    calls = _compile(
        functools.partial(paged_append_attend, interpret=False),
        (q, pool, pool, q, q, table, vec, vec), one_chip)
    assert _named(calls, "paged_append_attend_write") == 1
    assert _named(calls, "paged_append_attend") == 2


@pytest.mark.parametrize("pool_pages", [40, 256])
def test_decode_dataflow_copies_no_pool(one_chip, pool_pages):
    """The engine's decode dataflow (`PagedDecodeEngine._multi_impl`
    over `_one_token`: a scan over layers of `paged_append_attend` with
    the donated pools as carry, inside a scan over tokens) at XL widths:
    the compiled program may hold no ``copy`` whose result is a whole
    pool, in the 4-D shape or the kernels' (N*Hkv, page, d) view. Each
    such copy cost 2.45 ms a layer at 64 pages (PERF.md section 5)."""
    from paddle_tpu.ops.pallas.paged_attention import paged_append_attend
    pool, q, table, vec = _paged_shapes(pool_pages)

    def step(kp, vp, q, k_row, v_row, table, lengths):
        base = table[:, 0]

        def layer(carry, i):
            h, kp, vp = carry
            o, kp, vp = paged_append_attend(
                q + h, kp, vp, k_row + h, v_row + h,
                i * pool_pages + table, i * pool_pages + base, lengths,
                interpret=False)
            return (o, kp, vp), None

        def token(carry, _):
            h, kp, vp, lengths = carry
            (h, kp, vp), _ = jax.lax.scan(layer, (h, kp, vp),
                                          jnp.arange(LAYERS))
            return (h, kp, vp, lengths + 1), None

        (h, kp, vp, _), _ = jax.lax.scan(
            token, (jnp.zeros_like(q), kp, vp, lengths), None, length=2)
        return h, kp, vp

    text = _compiled_text(step, (pool, pool, q, q, q, table, vec),
                          one_chip, donate=(0, 1))
    assert "paged_append_attend_write" in text
    n = pool.shape[0]
    pool_shapes = (f"bf16[{n},{HEADS},{PAGE},{HEAD_DIM}]",
                   f"bf16[{n * HEADS},{PAGE},{HEAD_DIM}]")
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)
              and any(f"= {sh}" in ln for sh in pool_shapes)]
    assert not copies, copies


def test_prefill_installs_the_slot_in_place(one_chip):
    """An admission's one program (`PagedDecodeEngine._prefill_impl`,
    the one-pass prefill at a chat-sized bucket) at XL widths: the two
    pools AND the five slot vectors it installs the slot's decode state
    into are aliased in to out, and the compiled program holds no
    ``copy`` of a slot vector or of a whole pool. The engine is made of
    shapes alone (built under ``eval_shape``: no weight, no pool); the
    jitted body reads only its configuration."""
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=VOCAB, max_seq_len=SEQ, d_model=DM,
                        n_layers=LAYERS, n_heads=HEADS, dtype=BF16)
    made = []

    def build():
        eng = PagedDecodeEngine(gpt.GPT(cfg), n_pages=POOL_PAGES,
                                max_slots=PAGED_SLOTS, page_size=PAGE)
        made.append(eng)
        return (eng._head, eng._stacked, eng.kp, eng.vp,
                (eng.lengths, eng.last, eng.active, eng.remaining,
                 eng.eos_ids), eng.toks)

    state = jax.eval_shape(build)
    bucket, scalar = 32, _sds((), jnp.int32)
    text = _compiled_text(
        made[0]._prefill_impl,
        (*state, _sds((1, bucket), jnp.int32), scalar,
         _sds((bucket // PAGE + 1, LAYERS, 3), jnp.int32),
         scalar, scalar, scalar),
        one_chip, donate=(2, 3, 4, 5))
    header = text.splitlines()[0]
    aliased = {int(m) for m in re.findall(
        r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)", header)}
    entry, = [ln for ln in text.splitlines() if ln.startswith("ENTRY")]
    params = re.findall(r"[\w.]+: (\w+\[[\d,]*\])",
                        entry.split(") -> ")[0])
    pool = f"bf16[{LAYERS * POOL_PAGES + 1},{HEADS},{PAGE},{HEAD_DIM}]"
    vec, flag = f"s32[{PAGED_SLOTS}]", f"pred[{PAGED_SLOTS}]"
    assert sorted(params[n] for n in aliased) \
        == sorted([pool] * 2 + [vec] * 4 + [flag])
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)
              and any(f"= {sh}" in ln for sh in (pool, vec, flag))]
    assert not copies, copies


def test_paged_decode_attention(one_chip):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention)
    pool, q, table, vec = _paged_shapes()
    calls = _compile(
        functools.partial(paged_decode_attention, interpret=False,
                          return_stats=True),
        (q, pool, pool, table, vec), one_chip)
    assert _named(calls, "paged_decode_attention") == 1


def test_decode_attention(one_chip):
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    cache = _sds((SLOTS, HEADS, 640, HEAD_DIM))
    calls = _compile(
        functools.partial(decode_attention, interpret=False,
                          return_stats=True),
        (_sds((SLOTS, HEADS, HEAD_DIM)), cache, cache,
         _sds((SLOTS,), jnp.int32)), one_chip)
    assert _named(calls, "decode_attention") == 1


def test_int8_matmul(one_chip):
    from paddle_tpu.ops.pallas.quant_matmul import int8_matmul
    calls = _compile(
        functools.partial(int8_matmul, interpret=False),
        (_sds((SLOTS, DM)), _sds((DM, 4 * DM), jnp.int8),
         _sds((1, 4 * DM), jnp.float32)), one_chip)
    assert _named(calls, "int8_matmul") == 1


# Brumby-14B-Base's retention layers at their published widths (40 query
# heads and 8 key/value heads of 128), 16 slots, the benchmark's 8 layers
R_LAYERS, R_SLOTS, R_HEADS, R_KV_HEADS = 8, 16, 40, 8


def _retention_shapes():
    from paddle_tpu.ops.pallas.retention import state_shapes
    pools = state_shapes(R_LAYERS, R_SLOTS, R_KV_HEADS, HEAD_DIM)
    return (pools["S"], pools["z"], _sds((R_SLOTS, R_HEADS, HEAD_DIM)),
            _sds((R_SLOTS, R_KV_HEADS, HEAD_DIM)),
            _sds((R_SLOTS, R_KV_HEADS), jnp.float32),
            _sds((R_SLOTS,), jnp.bool_))


def test_retention_step(one_chip):
    from paddle_tpu.ops.pallas.retention import retention_step
    S, z, q, k, g, active = _retention_shapes()

    def step(S, z, q, k, g, active):
        return retention_step(q, k, k, g, S, z, 3, active, interpret=False)

    calls = _compile(step, (S, z, q, k, g, active), one_chip)
    assert _named(calls, "retention_step") == 1


def test_retention_decode_dataflow_copies_no_pool(one_chip):
    """The engine's decode dataflow over retention layers (a scan over
    layers of `retention_step` with the donated state pools as carry,
    inside a scan over tokens) at Brumby's widths: the compiled program
    may hold no ``copy`` whose result is a whole state pool. The ``S``
    pool is 4.36 GB: a second copy does not fit beside the weights."""
    from paddle_tpu.ops.pallas.retention import retention_step
    S, z, q, k, g, active = _retention_shapes()

    def step(S, z, q, k, g, active):
        def layer(carry, i):
            h, S, z = carry
            o, S, z = retention_step(q + h, k, k, g, S, z, i, active,
                                     interpret=False)
            return (o.astype(q.dtype), S, z), None

        def token(carry, _):
            (h, S, z), _ = jax.lax.scan(layer, carry, jnp.arange(R_LAYERS))
            return (h, S, z), None

        return jax.lax.scan(token, (jnp.zeros_like(q), S, z), None,
                            length=2)[0]

    text = _compiled_text(step, (S, z, q, k, g, active), one_chip,
                          donate=(0, 1))
    assert "retention_step" in text
    pools = tuple("f32[" + ",".join(map(str, p.shape)) + "]"
                  for p in (S, z))
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)
              and any(f"= {sh}" in ln for sh in pools)]
    assert not copies, copies


# ------ latent attention over sparse experts at JoyAI-LLM-Flash's widths
# (32 heads over rows of 512 + 64, 256 experts of 2048 x 768), the
# benchmark's 32 slots x 72 pages over 5 layers and a 16k context
L_LAYERS, L_SLOTS, L_HEADS, L_RANK, L_ROPE = 5, 32, 32, 512, 64
L_PAGES, L_COLUMNS = 32 * 72, 128
E_EXPERTS, E_WIDTH, E_LAYERS = 256, 768, 4


def _latent_shapes():
    w = L_RANK + L_ROPE
    return (_sds((L_SLOTS, L_HEADS, w)),
            _sds((L_LAYERS * L_PAGES + 1, w, PAGE)), _sds((L_SLOTS, w)),
            _sds((L_SLOTS, L_COLUMNS), jnp.int32),
            _sds((L_SLOTS,), jnp.int32), _sds((L_SLOTS,), jnp.int32))


def test_latent_append_attend_copies_no_pool(one_chip):
    """The absorbed decode step's two launches inside a scan over layers
    with the donated pool of latent rows as carry: both kernels are
    there, and no ``copy`` of the 1.7 GB pool."""
    from paddle_tpu.ops.pallas.latent_attention import latent_append_attend
    q, pool, row, table, wpids, lengths = _latent_shapes()

    def step(pool, q, row, table, wpids, lengths):
        def layer(carry, i):
            h, pool = carry
            o, pool = latent_append_attend(
                q + h, pool, row, i * L_PAGES + table, wpids, lengths,
                L_RANK, 192 ** -0.5, interpret=False)
            return (jnp.pad(o, ((0, 0), (0, 0), (0, L_ROPE))), pool), None

        return jax.lax.scan(layer, (jnp.zeros_like(q), pool),
                            jnp.arange(L_LAYERS))[0]

    text = _compiled_text(step, (pool, q, row, table, wpids, lengths),
                          one_chip, donate=(0,))
    assert "latent_attend_write" in text and "latent_attend" in text
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln) and f"= {shape}" in ln]
    assert not copies, copies


def test_latent_chunk_attend(one_chip):
    """A 512-token chunk's expanded attention over the pages, and its
    rows' way into them, at the served widths: it compiles, and keeps
    the pool in place."""
    from paddle_tpu.ops.pallas.latent_attention import latent_chunk_attend
    _, pool, _, _, _, _ = _latent_shapes()
    shapes = (pool, _sds((512, L_HEADS, 128)), _sds((512, L_HEADS, L_ROPE)),
              _sds((512, L_RANK + L_ROPE)), _sds((L_RANK, L_HEADS, 256)),
              _sds((L_COLUMNS,), jnp.int32), _sds((), jnp.int32),
              _sds((), jnp.int32))

    def chunk(pool, qn, qr, rows, w, table_row, pos0, n_valid):
        return latent_chunk_attend(qn, qr, rows, pool, w, table_row,
                                   2 * L_PAGES, L_LAYERS * L_PAGES, pos0,
                                   n_valid, L_RANK, 192 ** -0.5)

    text = _compiled_text(chunk, shapes, one_chip, donate=(0,))
    shape = "bf16[" + ",".join(map(str, pool.shape)) + "]"
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln) and f"= {shape}" in ln]
    assert not copies, copies


@pytest.mark.parametrize("tokens,tile", [(32, 16), (512, 32)])
def test_moe_experts_reads_the_stacks_in_place(one_chip, tokens, tile):
    """The grouped expert product for a decode step's 32 tokens and a
    chunk's 512, inside a scan over the four expert layers that hands it
    the 3 GB stacks whole with the layer's number: one kernel, and no
    ``copy`` of a stack or of a layer of it."""
    from paddle_tpu.ops.pallas import moe_experts as me
    tiles = me.n_tiles(tokens * 8, E_EXPERTS, tile)
    shapes = (_sds((tiles * tile, DM)),
              _sds((E_LAYERS, E_EXPERTS, DM, E_WIDTH)),
              _sds((E_LAYERS, E_EXPERTS, DM, E_WIDTH)),
              _sds((E_LAYERS, E_EXPERTS, E_WIDTH, DM)),
              _sds((tiles,), jnp.int32), _sds((), jnp.int32))

    def layers(x, wg, wu, wd, tile_expert, used):
        def layer(h, i):
            return me.moe_experts(h, wg, wu, wd, tile_expert, used, tile,
                                  layer=i, interpret=False), None

        return jax.lax.scan(layer, x, jnp.arange(E_LAYERS))[0]

    text = _compiled_text(layers, shapes, one_chip)
    assert "moe_experts" in text
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)
              and re.search(rf"= bf16\[(?:{E_LAYERS},)?{E_EXPERTS},", ln)]
    assert not copies, copies


@pytest.mark.parametrize("config,engine", [
    ("brumby-14b", dict(n_pages=0, max_slots=16)),
    ("joyai-llm-flash", dict(n_pages=2304, max_slots=32))],
    ids=["retention", "latent-moe"])
def test_ride_keeps_the_pools_in_place(one_chip, monkeypatch, config,
                                       engine):
    """The ride (`PagedDecodeEngine._ride_impl`: a 512-token prompt chunk
    and the decode rows of every slot in ONE program) at the benchmark
    cells' sizes, with the chip's kernels: it compiles beside the weights
    and the pools, every pool is aliased in to out, and no ``copy`` of a
    whole pool is made (Brumby's 4.4 GB state would not fit twice). The
    engine is made of shapes alone."""
    import json
    from paddle_tpu.inference.paged_engine import PagedDecodeEngine
    from paddle_tpu.models import gpt
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           f"{config}.json")) as f:
        model = json.load(f)["model"]
    if config == "brumby-14b":
        from benchmark.program_retention import model_config
    else:
        from benchmark.program_latent_moe import model_config
    # the kernels choose interpret mode by the default backend at trace
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    made = []

    def build():
        eng = PagedDecodeEngine(gpt.GPT(model_config(model)),
                                prefill_chunk=512, **engine)
        made.append(eng)
        return (eng._head, eng._stacked, eng.state, eng.lengths, eng.last,
                eng.active, eng.remaining, eng.eos_ids, eng._table_array(),
                eng.active)

    state = jax.eval_shape(build)
    eng, scalar = made[0], _sds((), jnp.int32)
    row = (_sds((state[8].shape[1],), jnp.int32) if eng.kind.pages
           else None)
    text = _compiled_text(
        eng._ride_impl,
        (*state, _sds((1, 512), jnp.int32), scalar, scalar, scalar,
         _sds((), jnp.bool_), scalar, scalar, row),
        one_chip, donate=(2, 3, 4, 5, 6, 7))
    kernels = (("retention_step",) if config == "brumby-14b"
               else ("latent_attend", "moe_experts"))
    assert all(k in text for k in kernels)
    pools = [f"{'f32' if a.dtype == jnp.float32 else 'bf16'}["
             + ",".join(map(str, a.shape)) + "]"
             for a in jax.tree_util.tree_leaves(state[2]) if a.ndim > 2]
    header = text.splitlines()[0]
    aliased = re.findall(r"\{\d+\}: \((\d+), \{\}, (?:may|must)-alias\)",
                         header)
    assert len(aliased) >= len(pools) + 5
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r"= \S+ copy\(", ln)
              and any(f"= {p}" in ln for p in pools)]
    assert not copies, copies
    if config == "joyai-llm-flash":
        # what the ride reports of ROUTING_SLOT's routing is a row of the
        # array the router read, not the norm made again from the
        # residual (at another precision than the router's copy)
        saw = [b for b in re.findall(r"\n(%fused_computation[^\n]*\n.*?\n})",
                                     text, flags=re.S)
               if f"bf16[{E_LAYERS},{DM}]" in b.split("\n")[0]
               and b.split("\n")[0].endswith(f"-> bf16[1,{DM}] {{")]
        assert saw and not any("multiply(" in b for b in saw), saw
