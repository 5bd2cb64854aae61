"""Multi-head latent attention (MLA, DeepSeek-V2/V3's attention) over a
paged pool of LATENT rows: one row a token and layer, shared by every
head, ``[c_kv | k_rope]`` (``rank`` compressed numbers and the rotary
part of the key, already rotated), in place of per-head keys and values.

The pool is ``(pages, rank + rope, page)``: a page keeps its tokens along
the LANES, one row of 576 numbers a column. Token-major pages
``(page, 576)`` would be padded to 640 lanes in a TPU's tiled HBM layout
(a ninth more bytes to hold and to read, and a page no copy can address
whole); with the tokens on the lanes a page is exactly its 576 x 128
numbers, the scores are a plain ``q @ page`` and the weighted sums
contract the lanes.

Two exact forms of the same mathematics (`models/layer_kinds.py` LATENT):

- the ABSORBED form, for one token a slot (`latent_append_attend`): the
  key half of the up-projection is folded into the query, so a head's
  query is ``[q_nope W_uk^T | q_rope]`` (``rank + rope`` numbers) and its
  scores are plain dot products with the cached rows; the weighted sum
  is taken over the rows' first ``rank`` numbers and the value half of
  the up-projection is applied after, by the caller. No cached token is
  ever expanded. Two launches, as `paged_append_attend` has them: a
  write kernel (`latent_attend_write` in a trace) merges each slot's
  fresh row into its pool page in place (a column of the page, so the
  page goes in and out; the pool is that call's one pool operand,
  aliased to its output), then the read-only attend
  (`latent_attend`) walks the pages the slot holds, ONE copy a page (the
  row is key and value at once: two pools would read it twice), the
  next page's copy in flight while the current one is folded, and the
  next program's first page started under the last fold;
- the EXPANDED form, for a chunk of one slot's prompt
  (`latent_chunk_attend`): the chunk's rows are written into the slot's
  pages, then the chunk attends, block of pages by block of pages, to
  everything the slot holds up to itself; each block's rows are expanded
  to per-head keys and values (``c_kv W_kvb``) on the way, which for
  hundreds of query rows is the cheaper of the two forms. Plain
  `jax.numpy` under a loop whose trip count follows the live pages.

Forward-only.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["latent_append_attend", "latent_attend_reference",
           "latent_chunk_attend", "expanded_attention"]

_LANES = 128
_NEG_INF = float("-inf")
_NBUF = 2                   # landing buffers: the page folded, the next


def _attend_kernel(len_ref, table_ref, q_ref, pool_hbm, o_ref, buf, sems,
                   base_ref, acc_ref, m_ref, l_ref, *, scale, page,
                   max_pages, rank):
    # one program a slot: q (H, rank + rope) against the slot's live
    # pages, each (rank + rope, page), copied once. The buffers rotate
    # across programs (base_ref: the buffer that holds THIS program's
    # first page, started by the program before), so the grid runs in
    # order.
    b = pl.program_id(0)

    def live_pages(prog):
        return jnp.minimum((len_ref[prog] + page - 1) // page, max_pages)

    def copy(prog, j, slot):
        return pltpu.make_async_copy(
            pool_hbm.at[table_ref[prog * max_pages + j]], buf.at[slot],
            sems.at[slot])

    length = len_ref[b]
    n_live = live_pages(b)

    @pl.when(b == 0)
    def _first_program():
        base_ref[0] = 0

        @pl.when(n_live > 0)
        def _own_first_page():
            copy(b, 0, 0).start()

    base = base_ref[0]

    def fetch_next_program():
        @pl.when(b + 1 < pl.num_programs(0))
        def _in_grid():
            @pl.when(live_pages(b + 1) > 0)
            def _start():
                copy(b + 1, 0, (base + n_live) % _NBUF).start()

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[...]

    def fold(j, carry):
        @pl.when(j + 1 < n_live)
        def _ahead():
            copy(b, j + 1, (base + j + 1) % _NBUF).start()

        @pl.when(j == n_live - 1)
        def _last_page():
            fetch_next_program()

        slot = (base + j) % _NBUF
        copy(b, j, slot).wait()
        rows = buf[slot]                               # (rank+rope, page)
        s = jnp.dot(q, rows, preferred_element_type=jnp.float32) * scale
        col = j * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < length, s, _NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + lax.dot_general(
            p.astype(rows.dtype), rows[:rank],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_cur
        return carry

    lax.fori_loop(0, n_live, fold, 0)

    @pl.when(n_live == 0)
    def _empty_row():
        fetch_next_program()

    base_ref[0] = (base + n_live) % _NBUF
    l = l_ref[:, :1]
    o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
        o_ref.dtype)


def _write_kernel(len_ref, _wpid_ref, row_ref, page_ref, out_ref, *, page):
    # one program a slot: the slot's write page; column ``length % page``
    # is replaced by the fresh row, the others pass
    off = len_ref[pl.program_id(0)] % page
    sel = lax.broadcasted_iota(jnp.int32, (1, page), 1) == off
    out_ref[...] = jnp.where(sel, row_ref[...], page_ref[...])


def _write_rows(pool, rows, write_pids, lengths, interpret):
    b, w = rows.shape
    page = pool.shape[2]
    # the fresh row across the lanes: the kernel keeps one column of it
    spread = jnp.broadcast_to(rows.astype(pool.dtype)[:, :, None],
                              (b, w, _LANES))

    def row_index(i, lens, wpids):
        return (i, 0, 0)

    def page_index(i, lens, wpids):
        return (wpids[i], 0, lens[i] % page // _LANES)

    tile = pl.BlockSpec((None, w, _LANES), page_index)
    return pl.pallas_call(
        functools.partial(_write_kernel, page=_LANES),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b,),
            in_specs=[pl.BlockSpec((None, w, _LANES), row_index), tile],
            out_specs=tile),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operands: two scalar-prefetch refs, the rows, then the pool,
        # aliased to the output: what no program writes keeps its values
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="latent_attend_write",
        interpret=interpret,
    )(lengths, jnp.asarray(write_pids, jnp.int32), spread, pool)


def _attend(q, pool, page_table, lengths, rank, scale, interpret):
    b, h, w = q.shape
    page = pool.shape[2]
    max_pages = page_table.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b,),
        in_specs=[pl.BlockSpec((None, h, w), lambda i, lens, tab: (i, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, h, rank),
                               lambda i, lens, tab: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((_NBUF, w, page), pool.dtype),
            pltpu.SemaphoreType.DMA((_NBUF,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((h, rank), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
            pltpu.VMEM((h, _LANES), jnp.float32),
        ])
    return pl.pallas_call(
        # ptlint: disable=PT001 -- scale is a static Python float kwarg
        functools.partial(_attend_kernel, scale=float(scale), page=page,
                          max_pages=max_pages, rank=rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_attend",
        interpret=interpret,
    )(lengths, jnp.asarray(page_table, jnp.int32).reshape(-1), q, pool)


def latent_append_attend(q, pool, row, page_table, write_pids, lengths,
                         rank, scale, interpret=None):
    """The absorbed decode step over a pool of latent rows.

    Args:
      q: (B, H, rank + rope) each slot's absorbed queries, pool dtype.
      pool: (N, rank + rope, page) latent rows, a page's tokens along
        the lanes (DONATED: aliased into the returned pool).
      row: (B, rank + rope) each slot's fresh row ``[c_kv | k_rope]``.
      page_table: (B, max_pages) int32 pool rows of each slot's pages.
      write_pids: (B,) int32 the pool row that takes slot b's fresh row
        (a scratch page for a slot that is not active).
      lengths: (B,) int32 tokens cached; the fresh row lands at
        ``lengths[b]`` and the slot attends to ``lengths[b] + 1`` rows.

    Returns (o, pool): o (B, H, rank), the weighted sums of the rows'
    first ``rank`` numbers (the caller applies ``W_uv``).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    page = pool.shape[2]
    if page % _LANES:
        raise ValueError(f"page_size {page} must be a multiple of "
                         f"{_LANES}")
    lengths = jnp.asarray(lengths, jnp.int32)
    pool = _write_rows(pool, row, write_pids, lengths, interpret)
    o = _attend(q.astype(pool.dtype), pool, page_table,
                jnp.minimum(lengths + 1, page_table.shape[1] * page),
                rank, scale, interpret)
    return o, pool


def latent_attend_reference(q, pool, page_table, lengths, rank, scale):
    """XLA oracle of the attend: every slot's pages gathered, a full
    masked softmax. Shapes as in `latent_append_attend`; ``lengths`` the
    rows attended to."""
    b = q.shape[0]
    rows = jnp.swapaxes(pool[page_table], 2, 3).reshape(
        b, -1, pool.shape[1])
    s = jnp.einsum("bhw,btw->bht", q.astype(jnp.float32),
                   rows.astype(jnp.float32)) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, _NEG_INF), axis=-1)
    return jnp.einsum("bht,btc->bhc", p, rows[..., :rank].astype(
        jnp.float32))


def expanded_attention(q_nope, q_rope, c_kv, k_rope, wkv_b, scale, offset):
    """The expanded form, all keys at once: queries (T, H, nope) and
    (T, H, rope) at positions ``offset + t`` against cached rows
    ``c_kv`` (S, rank) and ``k_rope`` (S, rope) at positions ``s``;
    ``wkv_b`` (rank, H, nope + v). Returns (T, H, v) float32."""
    nope = q_nope.shape[-1]
    kv = jnp.einsum("sc,chd->shd", c_kv, wkv_b,
                    preferred_element_type=jnp.float32).astype(c_kv.dtype)
    s = (jnp.einsum("thd,shd->hts", q_nope, kv[..., :nope],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope,
                      preferred_element_type=jnp.float32)) * scale
    seen = (jnp.arange(c_kv.shape[0])[None, :]
            <= offset + jnp.arange(q_nope.shape[0])[:, None])
    p = jax.nn.softmax(jnp.where(seen[None], s, _NEG_INF), axis=-1)
    return jnp.einsum("hts,shd->thd", p.astype(kv.dtype), kv[..., nope:],
                      preferred_element_type=jnp.float32)


def latent_chunk_attend(q_nope, q_rope, rows, pool, wkv_b, table_row,
                        fold, scratch, pos0, n_valid, rank, scale,
                        block_pages=4):
    """One chunk of one slot's prompt in the expanded form.

    ``rows`` (C, rank + rope), the chunk's latent rows at positions
    ``pos0 + t`` (``pos0`` a multiple of the page, C a whole number of
    pages; the first ``n_valid`` real), are written into the slot's pages
    (``fold + table_row[...]``; a page that holds no real row goes to
    the ``scratch`` row of the pool), then queries (C, H, nope) and
    (C, H, rope) attend to the slot's rows ``0 .. pos0 + t``, a block of
    ``block_pages`` pages at a time with a running softmax, each block
    expanded through ``wkv_b`` (rank, H, nope + v). Returns
    ((C, H, v) float32, pool)."""
    c, h, nope = q_nope.shape
    page = pool.shape[2]
    v_dim = wkv_b.shape[-1] - nope
    first = pos0 // page
    for j in range(c // page):
        dst = jnp.where(j * page < n_valid, fold + table_row[first + j],
                        scratch)
        pool = lax.dynamic_update_slice(
            pool, rows[j * page:(j + 1) * page].T[None].astype(pool.dtype),
            (dst, 0, 0))
    bp = min(block_pages, table_row.shape[0])
    kb = bp * page
    # the table is walked in whole blocks: pad it so that the last
    # block's window stays inside (entries past a slot's pages are never
    # attended to: their keys lie after every query of the chunk)
    table_row = jnp.pad(table_row, (0, -table_row.shape[0] % bp))
    qpos = pos0 + jnp.arange(c)

    def block(i, carry):
        m, l, acc = carry
        ids = fold + lax.dynamic_slice_in_dim(table_row, i * bp, bp)
        blk = jnp.swapaxes(jnp.take(pool, ids, axis=0), 1, 2).reshape(
            kb, -1)
        kv = jnp.einsum("sc,chd->shd", blk[:, :rank], wkv_b,
                        preferred_element_type=jnp.float32
                        ).astype(blk.dtype)
        s = (jnp.einsum("thd,shd->hts", q_nope, kv[..., :nope],
                        preferred_element_type=jnp.float32)
             + jnp.einsum("thd,sd->hts", q_rope, blk[:, rank:],
                          preferred_element_type=jnp.float32)) * scale
        seen = (i * kb + jnp.arange(kb))[None, :] <= qpos[:, None]
        s = jnp.where(seen[None], s, _NEG_INF)
        m_cur = jnp.maximum(m, jnp.max(s, axis=-1))
        # a query row sees key 0 from the first block on, so m_cur is
        # finite wherever it is used
        alpha = jnp.exp(m - m_cur)
        p = jnp.exp(s - m_cur[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "hts,shd->htd", p.astype(kv.dtype), kv[..., nope:],
            preferred_element_type=jnp.float32)
        return m_cur, l, acc

    n_blocks = (pos0 + jnp.maximum(n_valid, 1) + kb - 1) // kb
    init = (jnp.full((h, c), _NEG_INF, jnp.float32),
            jnp.zeros((h, c), jnp.float32),
            jnp.zeros((h, c, v_dim), jnp.float32))
    _, l, acc = lax.fori_loop(0, n_blocks, block, init)
    return jnp.swapaxes(acc / l[..., None], 0, 1), pool
