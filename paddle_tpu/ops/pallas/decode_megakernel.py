"""Layer-folded single-launch paged decode: the whole transformer stack
(per-layer norm -> QKV -> fused paged append+attend -> MLP residual)
runs as ONE Pallas kernel with the grid's outer dimension over layers,
followed by ONE fused final-norm -> logits -> greedy-argmax epilogue
kernel — two launches per decode step instead of O(layers) (ISSUE 19;
PAPERS "LLM Inference Acceleration via Efficient Operation Fusion").

Why: the per-layer fused path (`paged_append_attend` inside a
`lax.scan`) pays one kernel dispatch per layer per step; folding the
layer loop INTO the grid amortizes the dispatch to one program launch
riding PR 8's stacked-block weights ((L, ...) leaves — the grid index IS
the layer index, weight slabs stream per grid step via their BlockSpec
index maps) and the PR 6 layer-folded pools (page p of layer l at row
l*P + p; ONE scratch row at L*P catches inactive slots' writes). Whether
launches bound decode on the chip: not measured.

Status (PR 21, v5e compiler, JAX 0.9.0): `mega_logits_sample` compiles
at 1.3B widths; `mega_decode_layers` does NOT — its row indexing uses
`lax.dynamic_slice` on in-kernel values, which Mosaic does not lower,
and behind that one layer's weight slab is 192 MiB double-buffered at
1.3B against 128 MiB of VMEM (the compiler's own count). So the stack
kernel runs in interpret mode only, `PagedDecodeEngine` defaults to the
per-layer fused path, and asking for ``mega=True`` on a chip raises the
compiler's error. Tiling the stack over d_model is a perf change of its
own.

Kernel shape:

- ``mega_decode_layers`` — grid (L,), ``dimension_semantics
  ("arbitrary",)`` (layer l+1 reads layer l's hidden state from the
  revisited output block, which stays resident in VMEM across
  sequential grid steps). The KV pools ride in ``ANY`` memory space
  (they are far too big to block into VMEM whole) and are
  input/output-aliased, so fresh-row writes are in place and the
  attention loop reads the just-written rows of earlier draft
  positions directly. Numerics reuse the ONE shared online-softmax
  definition (`decode_attention.online_softmax_step`); pages past a
  row's bound are fully masked, which the running-max clamp turns into
  an exact no-op — so no per-page predication is needed for parity.
- ``mega_logits_sample`` — grid over vocab tiles of the logits matmul,
  streaming the (dm, vb) weight slabs; a running blockwise argmax
  (strict-greater update + min-index tie-break = jnp.argmax's
  first-max semantics) and a non-finite flag accumulate in VMEM
  scratch, and the LAST tile writes one packed (B, 128) int32 output:
  column 0 = greedy token, column 1 = non-finite flag. The (S, V)
  logits never materialize in HBM.

Rows are FLAT (B = slots, or slots*K for speculative verify): each row
carries its own (slot, position, write?) coordinates via scalar
prefetch, so the plain step and the speculative K-row verify are the
SAME program at different row counts — verify/accept rides the same
single-dispatch geometry (satellite: revive spec decode on paged).

Bit-parity contract: greedy token STREAMS are bit-identical to the
per-layer fused path (`PagedDecodeEngine` with ``mega=False``) — the
per-layer path stays as the interpret-mode-asserted reference. Logits
may differ in the last ulp (different accumulation order folding the
fresh row), which greedy argmax absorbs; the engine's parity tests
assert the stream, the same contract the paged engine already holds
against ``gpt.generate``.

Forward-only (decode never differentiates through the pools).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.decode_attention import (
    _LANES, _NEG_INF, online_softmax_init, online_softmax_step)

__all__ = ["mega_decode_layers", "mega_logits_sample",
           "tune_mega_epilogue"]

# fallback vocab-tile width when the autotune cache has no entry for
# the folded shape family
_DEFAULT_VB = 512

# stacked-weight streaming order (the kernel ABI); optional biases are
# simply absent from the operand list when the model has none
_WEIGHT_ORDER = ("ln1_scale", "ln1_bias", "wqkv", "bqkv", "wo", "bo",
                 "ln2_scale", "ln2_bias", "wup", "bup", "wdown",
                 "bdown")


def _mega_tune_key(dm, vocab, dtype, layers, page):
    """Autotune key over the FOLDED geometry: the epilogue tile width
    depends on the logits matmul family (dm, vocab, dtype), and
    distinct layer-fold/page geometries tune separately (their VMEM
    budget differs)."""
    from paddle_tpu.ops.pallas.autotune import AutotuneCache
    return AutotuneCache.key("paged_mega", dm=dm, vocab=vocab,
                             dtype=str(dtype), layers=layers, page=page)


def _resolve_vb(vb, dm, vocab, dtype, layers, page):
    if vb is None:
        from paddle_tpu.ops.pallas.autotune import get_cache
        hit = get_cache().get(_mega_tune_key(dm, vocab, dtype, layers,
                                             page))
        if isinstance(hit, (tuple, list)):
            hit = hit[0]
        vb = hit if hit is not None else _DEFAULT_VB
    # ptlint: disable=PT001 -- vb is a static Python config knob
    # (autotune-cache hit or explicit kwarg), never a device value
    vb = max(_LANES, int(vb) // _LANES * _LANES)
    # PT006 clamp (ISSUE 20): the epilogue streams a double-buffered
    # (dm, vb) weight slab through VMEM — cap vb so that slab can never
    # exceed half the static budget (the other half covers the hidden
    # state, accumulators, and the packed output), no matter what the
    # autotune cache or an explicit kwarg asked for at large vocab.
    from paddle_tpu.analysis.kernelmodel import (itemsize,
                                                 vmem_budget_bytes)
    cap = vmem_budget_bytes() // 2 // (2 * dm * itemsize(dtype))
    cap = max(_LANES, cap // _LANES * _LANES)
    return min(vb, cap)


def _mm(a, b):
    """MXU matmul with the f32 accumulator Mosaic requires (a bare bf16
    ``@`` asks for a bf16 accumulator, which the TPU compiler refuses),
    rounded back to the activation dtype."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


def _const_map(n):
    def index(l, *prefetch):
        return (0,) * n
    return index


def _layer_map(n):
    def index(l, *prefetch):
        return (l,) + (0,) * (n - 1)
    return index


def _mega_kernel(*refs, wnames, L, B, dm, hq, hkv, d, page, P, mx,
                 group, gp, scale, rope, theta, moved=None):
    # ABI: | pos, slot, write, table (SMEM scalar prefetch)
    #      | x, pos_v, <stacked weight slabs>, kp, vp  (inputs)
    #      | x_out, kp_out, vp_out                     (outputs)
    #      | o_scratch, acc, m, l                      (VMEM scratch)
    # kp/vp (inputs) are consumed by the aliasing, not the body — the
    # pool state is read and written through the ALIASED output refs,
    # so earlier rows' fresh writes are visible to later reads.
    pos_s, slot_s, write_s, tab_s = refs[:4]
    i = 4
    x_ref, posv_ref = refs[i], refs[i + 1]
    i += 2
    w = {}
    for name in wnames:
        w[name] = refs[i]
        i += 1
    i += 2                                   # kp_in, vp_in (aliased)
    xo_ref, kpo_ref, vpo_ref = refs[i:i + 3]
    os_ref, acc_ref, m_ref, l_ref = refs[i + 3:i + 7]
    pool_dt = kpo_ref.dtype
    li = pl.program_id(0)

    @pl.when(li == 0)
    def _seed():
        xo_ref[...] = x_ref[...]

    x = xo_ref[...]                                        # (B, dm)

    # --- LN1 + fused QKV (+ rope), mirrors GPTBlock._qkv ------------
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    h = ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * w["ln1_scale"][0]
         + w["ln1_bias"][0]).astype(x.dtype)
    qkv = _mm(h, w["wqkv"][0])
    if "bqkv" in w:
        qkv = qkv + w["bqkv"][0]
    q = qkv[:, :hq * d].reshape(B, hq, d)
    k = qkv[:, hq * d:(hq + hkv) * d].reshape(B, hkv, d)
    v = qkv[:, (hq + hkv) * d:].reshape(B, hkv, d)
    if rope:
        half = d // 2
        posf = posv_ref[...].astype(jnp.float32)           # (B, 1)
        freqs = theta ** (-jax.lax.broadcasted_iota(
            jnp.float32, (1, half), 1) / half)
        ang = posf * freqs                                 # (B, half)
        cos = jnp.cos(ang)[:, None, :]
        sin = jnp.sin(ang)[:, None, :]

        def rot(t):
            t32 = t.astype(jnp.float32)
            t1, t2 = t32[..., :half], t32[..., half:]
            return jnp.concatenate(
                [t1 * cos - t2 * sin, t1 * sin + t2 * cos],
                axis=-1).astype(t.dtype)

        q, k = rot(q), rot(k)
    krow = k.astype(pool_dt)                               # (B, hkv, d)
    vrow = v.astype(pool_dt)
    qg = q.astype(pool_dt).reshape(B * hkv, group, d)
    if gp > group:
        qg = jnp.concatenate(
            [qg, jnp.zeros((B * hkv, gp - group, d), pool_dt)], axis=1)

    # --- fresh-row writes, ALL rows before any attend ---------------
    # Row r's KV lands at page table[slot, pos//page] offset pos%page
    # of THIS layer's pool slab; masked-out rows write the shared
    # scratch row L*P instead (same convention as the per-layer fused
    # path's wpids). Writing every row first is causal because the
    # attend bound pos+1 masks any column at a LATER draft position.
    def write_row(r, _):
        s = slot_s[r]
        p = pos_s[r]
        pid = tab_s[s * mx + jnp.minimum(p // page, mx - 1)]
        g = jnp.where(write_s[r] == 1, li * P + pid, L * P)
        off = p % page
        for hh in range(hkv):
            kpo_ref[g, hh, pl.ds(off, 1), :] = jax.lax.dynamic_slice(
                krow, (r, hh, 0), (1, 1, d)).reshape(1, d)
            vpo_ref[g, hh, pl.ds(off, 1), :] = jax.lax.dynamic_slice(
                vrow, (r, hh, 0), (1, 1, d)).reshape(1, d)
        return 0

    jax.lax.fori_loop(0, B, write_row, 0)

    # --- paged attention per (row, kv head) -------------------------
    # Pages past the bound are fully masked; online_softmax_step's
    # running-max clamp makes a fully-masked block an exact no-op
    # (alpha == 1, p == 0), so unconditional stepping over the fixed
    # mx-wide table is bit-identical to the per-layer kernel's
    # pl.when-guarded stream.
    def attend(rh, _):
        r = rh // hkv
        hh = rh % hkv
        s = slot_s[r]
        bound = pos_s[r] + 1
        online_softmax_init(acc_ref, m_ref, l_ref)
        qt = jax.lax.dynamic_slice(qg, (rh, 0, 0),
                                   (1, gp, d)).reshape(gp, d)

        def one_page(j, _):
            g = li * P + tab_s[s * mx + j]
            online_softmax_step(qt, kpo_ref[g, hh], vpo_ref[g, hh],
                                j * page, bound, acc_ref, m_ref, l_ref,
                                scale)
            return 0

        jax.lax.fori_loop(0, mx, one_page, 0)
        lv = l_ref[:, :1]
        os_ref[rh] = (acc_ref[...]
                      / jnp.where(lv == 0.0, 1.0, lv)).astype(pool_dt)
        return 0

    jax.lax.fori_loop(0, B * hkv, attend, 0)

    # --- out-proj + MLP residual, mirrors GPTBlock._block_tail ------
    attn = os_ref[...][:, :group, :].reshape(B, hq * d).astype(x.dtype)
    o = _mm(attn, w["wo"][0])
    if "bo" in w:
        o = o + w["bo"][0]
    x = x + o
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    h = ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * w["ln2_scale"][0]
         + w["ln2_bias"][0]).astype(x.dtype)
    h = jax.nn.gelu(_mm(h, w["wup"][0])
                    + (w["bup"][0] if "bup" in w else 0.0))
    h = _mm(h, w["wdown"][0])
    if "bdown" in w:
        h = h + w["bdown"][0]
    xo_ref[...] = x + h


def mega_decode_layers(x, weights, k_pages, v_pages, page_table,
                       positions, row_slot, row_write, *, page, n_pages,
                       n_heads, kv_heads, head_dim, rope=False,
                       rope_theta=10000.0, scale=None, interpret=None):
    """Run the WHOLE layer stack of one decode step in one launch.

    Args:
      x: (B, dm) embedded input rows (token + positional embedding
        already applied). B is flat: one row per slot for the plain
        step, slots*K rows (slot-major) for speculative verify.
      weights: dict of scan-stacked block leaves — ``ln1_scale``,
        ``ln1_bias``, ``wqkv``, ``wo``, ``ln2_scale``, ``ln2_bias``,
        ``wup``, ``wdown`` each (L, ...), plus the optional biases
        (``bqkv``/``bo``/``bup``/``bdown``) or None.
      k_pages, v_pages: (L*n_pages+1, Hkv, page, D) layer-folded pools
        (DONATED — aliased into the returned pools). Row L*n_pages is
        the scratch page for masked-out rows.
      page_table: (S, max_pages) int32, UNFOLDED local page ids (the
        kernel folds in the layer offset l*n_pages itself).
      positions: (B,) int32 — row r's absolute position; its fresh KV
        row lands there and it attends over [0, positions[r]].
      row_slot: (B,) int32 — row r's slot (page-table row).
      row_write: (B,) int32 — 1: write the fresh row into the slot's
        page, 0: divert to the scratch page (inactive slot).

    Returns (x_out, k_pages, v_pages): x_out (B, dm) is the final
    hidden state after all L blocks (pre final-norm — feed it to
    `mega_logits_sample`).
    """
    x = jnp.asarray(x)
    k_pages, v_pages = jnp.asarray(k_pages), jnp.asarray(v_pages)
    B, dm = x.shape
    # ptlint: disable=PT001 -- geometry kwargs are static Python ints
    hq, hkv, d = int(n_heads), int(kv_heads), int(head_dim)
    page = int(page)  # ptlint: disable=PT001 -- static config knob
    P = int(n_pages)  # ptlint: disable=PT001 -- static config knob
    L = weights["wqkv"].shape[0]
    S, mx = page_table.shape
    if k_pages.shape[0] != L * P + 1:
        raise ValueError(
            f"layer-folded pool expects {L}*{P}+1 rows, got "
            f"{k_pages.shape[0]}")
    if page % _LANES:
        raise ValueError(f"page_size {page} must be a multiple of "
                         f"{_LANES}")
    if hq % hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {hq} vs {hkv}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    pool_dt = k_pages.dtype
    sub = 16 if pool_dt in (jnp.bfloat16, jnp.float16) else 8
    gp = max(sub, (group + sub - 1) // sub * sub)

    prefetch = (jnp.asarray(positions, jnp.int32),
                jnp.asarray(row_slot, jnp.int32),
                jnp.asarray(row_write, jnp.int32),
                jnp.asarray(page_table, jnp.int32).reshape(-1))
    posv = jnp.asarray(positions, jnp.int32).reshape(B, 1)

    wnames = tuple(n for n in _WEIGHT_ORDER
                   if weights.get(n) is not None)
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [pl.BlockSpec((B, dm), _const_map(2)),
                pl.BlockSpec((B, 1), _const_map(2))]
    operands = [x, posv]
    for n in wnames:
        wa = jnp.asarray(weights[n])
        if wa.ndim == 2:
            # stacked LN/bias vectors ride as (L, 1, n): a (1, n) block
            # of an (L, n) array breaks Mosaic's (8, 128) tiling rule,
            # a (1, 1, n) block whose last two dims ARE the array's
            # does not
            wa = wa[:, None, :]
        in_specs.append(pl.BlockSpec((1,) + wa.shape[1:],
                                     _layer_map(wa.ndim)))
        operands.append(wa)
    in_specs += [any_spec, any_spec]
    operands += [k_pages, v_pages]
    out_specs = [pl.BlockSpec((B, dm), _const_map(2)), any_spec,
                 any_spec]
    out_shape = [jax.ShapeDtypeStruct((B, dm), x.dtype),
                 jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                 jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)]
    # operand numbering counts the scalar-prefetch refs: 4 prefetch +
    # x + pos_v + the weight slabs, then the two pools
    nw = len(wnames)
    aliases = {4 + 2 + nw: 1, 4 + 2 + nw + 1: 2}

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(L,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((B * hkv, gp, d), pool_dt),
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
            pltpu.VMEM((gp, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_mega_kernel, wnames=wnames, L=L, B=B, dm=dm,
                          hq=hq, hkv=hkv, d=d, page=page, P=P, mx=mx,
                          group=group, gp=gp,
                          # ptlint: disable=PT001 -- static float kwarg
                          scale=float(scale),
                          # ptlint: disable=PT001 -- static knobs
                          rope=bool(rope), theta=float(rope_theta)),
        grid_spec=grid_spec,
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mega_decode_layers",
        interpret=interpret,
    )(*prefetch, *operands)


def _epilogue_kernel(x_ref, s_ref, b_ref, w_ref, p_ref, out_ref,
                     hs_ref, best_ref, arg_ref, nf_ref, *, B, vb,
                     vocab):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        xs = x_ref[...]
        x32 = xs.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        y = ((x32 - mu) * jax.lax.rsqrt(var + 1e-5) * s_ref[0]
             + b_ref[0])
        hs_ref[...] = y.astype(xs.dtype)
        best_ref[...] = jnp.full_like(best_ref, _NEG_INF)
        arg_ref[...] = jnp.zeros_like(arg_ref)
        nf_ref[...] = jnp.zeros_like(nf_ref)

    lg = _mm(hs_ref[...], w_ref[...])                 # (B, vb)
    lg = jnp.where(p_ref[...] > 0, jnp.nan, lg)
    lgf = lg.astype(jnp.float32)
    col = (j * vb
           + jax.lax.broadcasted_iota(jnp.int32, (B, vb), 1))
    valid = col < vocab
    nfb = jnp.any(valid & ~jnp.isfinite(lgf), axis=1, keepdims=True)
    lgm = jnp.where(valid, lgf, _NEG_INF)
    bm = jnp.max(lgm, axis=1, keepdims=True)          # (B, 1)
    first = jnp.min(jnp.where((lgm == bm) & valid, col,
                              jnp.int32(2 ** 30)),
                    axis=1, keepdims=True)
    # strict > keeps the FIRST max across tiles (jnp.argmax semantics);
    # a NaN bm compares False, so poisoned rows keep arg 0 — they are
    # flagged non-finite and the engine discards their token anyway
    upd = bm > best_ref[:, :1]
    best_ref[...] = jnp.where(upd, bm, best_ref[...])
    arg_ref[...] = jnp.where(upd, first, arg_ref[...])
    nf_ref[...] = nf_ref[...] | nfb.astype(jnp.int32)

    @pl.when(j == pl.num_programs(0) - 1)
    def _emit():
        out_ref[...] = jnp.concatenate(
            [arg_ref[:, :1], nf_ref[:, :1],
             jnp.zeros((B, _LANES - 2), jnp.int32)], axis=1)


def mega_logits_sample(x, lnf_scale, lnf_bias, w, poison, *, vb=None,
                       layers=0, page=0, interpret=None):
    """Fused final-norm -> logits -> greedy sampling epilogue.

    Streams the (dm, V) unembedding in (dm, vb) tiles with a running
    blockwise argmax, so the logits never land in HBM and sampling
    costs ONE launch. x: (B, dm) post-stack hidden rows; w: (dm, V)
    unembedding (pass ``head["wte"].T`` or ``head["lm_head"]``);
    poison: (B,) bool/int — rows to force non-finite (the engine's
    fault-injection contract: poisoned rows flag, never emit).

    Returns (tok, nonfin): (B,) int32 greedy tokens (first-max index,
    jnp.argmax parity) and (B,) int32 non-finite flags (1 where any
    true-vocab logit is NaN/inf — the engine's ``bad`` source).

    ``vb`` (vocab tile width) defaults from the autotune cache keyed by
    the folded geometry (`tune_mega_epilogue` fills it), else 512.
    """
    x = jnp.asarray(x)
    w = jnp.asarray(w)
    B, dm = x.shape
    vocab = w.shape[1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    vb = _resolve_vb(vb, dm, vocab, x.dtype, layers, page)
    vb = min(vb, (vocab + _LANES - 1) // _LANES * _LANES)
    nj = (vocab + vb - 1) // vb
    wp = jnp.pad(w, ((0, 0), (0, nj * vb - vocab)))
    pois = jnp.asarray(poison).astype(jnp.int32).reshape(B, 1)

    out = pl.pallas_call(
        functools.partial(_epilogue_kernel, B=B, vb=vb, vocab=vocab),
        grid=(nj,),
        in_specs=[
            pl.BlockSpec((B, dm), _const_map(2)),
            pl.BlockSpec((1, dm), _const_map(2)),
            pl.BlockSpec((1, dm), _const_map(2)),
            pl.BlockSpec((dm, vb), lambda j: (0, j)),
            pl.BlockSpec((B, 1), _const_map(2)),
        ],
        out_specs=pl.BlockSpec((B, _LANES), _const_map(2)),
        out_shape=jax.ShapeDtypeStruct((B, _LANES), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((B, dm), x.dtype),
            pltpu.VMEM((B, _LANES), jnp.float32),
            pltpu.VMEM((B, _LANES), jnp.int32),
            pltpu.VMEM((B, _LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="mega_logits_sample",
        interpret=interpret,
    )(x, jnp.asarray(lnf_scale).reshape(1, dm),
      jnp.asarray(lnf_bias).reshape(1, dm), wp, pois)
    return out[:, 0], out[:, 1]


def tune_mega_epilogue(x, lnf_scale, lnf_bias, w, *, layers=0, page=0,
                       candidates=None, iters=3):
    """Measure epilogue vocab-tile candidates on the REAL head shapes
    and persist the winner keyed by the folded geometry (see
    `autotune.tune`; run before the engine traces — Pallas grids are
    trace-time constants)."""
    from paddle_tpu.ops.pallas import autotune as at

    x = jnp.asarray(x)
    w = jnp.asarray(w)
    vocab = w.shape[1]
    key = _mega_tune_key(x.shape[1], vocab, x.dtype, layers, page)
    if candidates is None:
        candidates = [c for c in (256, 512, 1024, 2048)
                      if c <= (vocab + _LANES - 1) // _LANES * _LANES
                      ] or [_LANES]
    poison = jnp.zeros((x.shape[0],), bool)
    jitted = {}

    def build_and_run(vb):
        if vb not in jitted:
            def fn(x, w, _vb=int(vb)):
                tok, nf = mega_logits_sample(
                    x, lnf_scale, lnf_bias, w, poison, vb=_vb,
                    layers=layers, page=page)
                return tok.sum() + nf.sum()
            jitted[vb] = jax.jit(fn)
        int(jitted[vb](x, w))  # sync — timing must see the kernel end

    def geom_check(vb):
        # refuse before spending chip time: a candidate the PT006
        # budget clamp would coerce is a duplicate of the clamped
        # width, and an over-budget harvest can never fit
        from paddle_tpu.analysis import kernelmodel as km
        rvb = _resolve_vb(int(vb), x.shape[1], vocab, x.dtype, layers,
                          page)
        if rvb != int(vb):
            return (f"vb={int(vb)} infeasible: PT006 VMEM budget "
                    f"clamps the epilogue tile to {rvb}")

        def dry():
            jax.eval_shape(
                lambda x, s, b, w, p: mega_logits_sample(
                    x, s, b, w, p, vb=int(vb), layers=layers,
                    page=page),
                x, jnp.asarray(lnf_scale), jnp.asarray(lnf_bias), w,
                poison)
        return km.budget_reason(dry)

    return at.tune("paged_mega", key, candidates, build_and_run,
                   iters=iters, geom_check=geom_check)


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): drive both
    megakernel launches under ``jax.eval_shape`` across the bench
    ladder and the epilogue's autotune vb candidates, so PT006-PT009
    can price every launch without executing a kernel."""
    from paddle_tpu.analysis import kernelmodel as km

    def stack_case(geom, L=None):
        p = km.LADDER[geom]
        dm, hq, hkv = p["dm"], p["heads"], p["kv_heads"]
        d = dm // hq
        dt = p["dtype"]
        layers = p["layers"] if L is None else L
        page = p["page"]
        P = max(1, p["seq"] // page)
        B = 8
        weights = {
            "ln1_scale": km.sds((layers, dm), dt),
            "ln1_bias": km.sds((layers, dm), dt),
            "wqkv": km.sds((layers, dm, (hq + 2 * hkv) * d), dt),
            "wo": km.sds((layers, hq * d, dm), dt),
            "ln2_scale": km.sds((layers, dm), dt),
            "ln2_bias": km.sds((layers, dm), dt),
            "wup": km.sds((layers, dm, 4 * dm), dt),
            "wdown": km.sds((layers, 4 * dm, dm), dt),
        }
        x = km.sds((B, dm), dt)
        pool = km.sds((layers * P + 1, hkv, page, d), dt)
        table = km.sds((B, P), "int32")
        rows = km.sds((B,), "int32")

        def run():
            jax.eval_shape(
                functools.partial(mega_decode_layers, page=page,
                                  n_pages=P, n_heads=hq,
                                  kv_heads=hkv, head_dim=d),
                x, weights, pool, pool, table, rows, rows, rows)
        return km.GeomCase(kernel="mega_decode_layers", geometry=geom,
                           config=f"L{layers}.page{page}", run=run)

    def epi_case(geom, vb):
        p = km.LADDER[geom]
        dm, vocab, dt = p["dm"], p["vocab"], p["dtype"]
        B = 8
        x = km.sds((B, dm), dt)
        vec = km.sds((dm,), dt)
        w = km.sds((dm, vocab), dt)
        pois = km.sds((B,), "int32")

        def run():
            jax.eval_shape(
                functools.partial(mega_logits_sample, vb=vb),
                x, vec, vec, w, pois)
        return km.GeomCase(kernel="mega_logits_sample", geometry=geom,
                           config=f"vb{vb}", run=run)

    # the stack kernel is swept at the one rung a default-reachable
    # path could launch it at: from 350m widths up its per-layer weight
    # slab is over the VMEM model (3.1x at 350m, 12.4x at r06) and the
    # v5e compiler refuses it outright (192 MiB of 128 at 1.3B, PR 21),
    # so the engine no longer selects it by default and no hand-written
    # PT006 suppression is carried for those rungs
    cases = [stack_case("tiny")]
    for g in ("tiny", "350m", "r06"):
        for vb in (256, 512, 2048):
            cases.append(epi_case(g, vb))
    return cases
