"""Paged flash-decode attention: block-table KV cache as a Pallas TPU
kernel (the vLLM-style serving memory model — no reference analog; the
reference's fused_multi_transformer serves one contiguous CacheKV per
sequence).

Why paged: a slot-contiguous cache must reserve max_len for every slot,
so HBM bounds in-flight sequences by the WORST length. A paged pool
shares fixed-size pages across sequences; a sequence holds
ceil(len/page) pages and frees them at retirement — memory scales with
the sum of actual lengths, not slots x max_len.

TPU mapping: one program a (sequence, head block). The pools stay in
HBM (memory space ANY); lengths and the page table ride as
scalar-prefetch operands, and the program walks ITS row of the table
for ``ceil(length / page)`` pages and no further: each page's heads are
copied into VMEM (`pltpu.make_async_copy`), the next page's copy in
flight while the current page is folded, eight heads at a time, with
the same online-softmax step as decode_attention; while a program
folds its last page it starts the copy of the NEXT program's first, so
the copies are one pipeline across the grid (which runs in order). A
call's device time follows the pages the sequences hold, not the
table's width: the grid does not know it.

Two entry points:

- `paged_decode_attention` — read-only pools, optional (m, l) stats so
  the caller can fold extra columns analytically (the pre-fusion
  engine formulation).
- `paged_append_attend` — the decode step's append+attend, as TWO
  launches: a small write kernel (`paged_append_attend_write`) merges
  the current token's fresh K/V row into its pool page in place (it
  moves the sublane tile that holds the row, not the page), then
  the read-only attend runs over the pools the write RETURNED, with
  ``lengths + 1``. Each pool reaches the write kernel as that call's
  only use of it (one operand, aliased to its output), so XLA keeps the
  pools in place through the engine's layer and token scans. Two
  shortcuts each cost whole-pool copies per layer (PERF.md section 5):
  one launch that takes a pool both as read streams and as the aliased
  write view (XLA copies an operand that one instruction reads and
  overwrites: 84% of the GPT-3 XL decode step), and an XLA scatter in
  the write's place (it lays the pool out differently from what the
  kernel reads, so the compiler converts it both ways). The data
  dependence (attend reads what the write returned) orders the two.

One geometry rule for both. ``head_block`` is how many consecutive KV
heads of a page one program takes (their rows ARE contiguous in the
head-major pool view, so a page's head block is one copy): the largest
divisor of Hkv whose blocks fit VMEM (`_default_head_block`: all 16
heads at GPT-3 XL), so the fewest programs and the longest copies,
unless the caller names one. A program keeps `_PAGES_IN_FLIGHT` pages
ahead of the one it folds (1: double buffering; a second bought nothing
on a v5e, PERF.md section 6, PR 30). Nothing outside the call's own
arguments and shapes decides the geometry.

Forward-only (generation never differentiates through the cache).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention", "paged_decode_attention_reference",
           "paged_append_attend", "PagedKVCache"]

_LANES = 128
_NEG_INF = float("-inf")

# pages a program keeps in flight ahead of the fold: the next page's
# copy runs while the current page is folded (double buffering)
_PAGES_IN_FLIGHT = 1

# heads of a page folded at once (the head block's common divisor with
# it): one batched `online_softmax_step`, whose vector work then covers
# the heads' independent chains. On a v5e 8 folds as fast as 16 to 4%
# and 1.5-2x faster than heads one at a time, unrolled or not, and
# compiles sooner than 16 (PERF.md section 6, PR 30)
_HEAD_CHUNK = 8


def _group_rows(group, dtype):
    """A KV head's query rows, padded to the dtype's sublane tile."""
    sub = _sublanes(dtype)
    return max(sub, (group + sub - 1) // sub * sub)


def _vmem_bytes(hb, page, d, dtype, group):
    """VMEM one program of the attend holds at head block ``hb``, as
    `analysis/kernelmodel` counts it (pipelined blocks twice, scratch
    once): K and V landing buffers for ``_PAGES_IN_FLIGHT + 1`` pages;
    the q and o blocks; the float32 accumulator, (m, l) and the stats
    block. The write launch shares the head block and holds far less
    (six sublane tiles of ``hb`` heads)."""
    from paddle_tpu.analysis import kernelmodel as km
    isz = km.itemsize(jnp.dtype(dtype))
    rows = hb * _group_rows(group, dtype)
    return (2 * (_PAGES_IN_FLIGHT + 1) * hb * page * d * isz
            + 2 * km.DOUBLE_BUFFER * rows * d * isz
            + rows * d * 4
            + (2 + km.DOUBLE_BUFFER) * rows * _LANES * 4)


def _default_head_block(page, hkv, d, dtype, group):
    """The largest divisor of ``hkv`` whose blocks fit the VMEM budget
    (`kernelmodel.vmem_budget_bytes`: 16 MiB less the compiler's
    reserve): a page of the pool is contiguous over its heads, so the
    more heads a program takes, the fewer programs and the longer
    copies. A wide ``Hkv x page x D`` gets a smaller block, never a
    failed compile; 1 is the floor whatever the budget says."""
    from paddle_tpu.analysis import kernelmodel as km
    budget = km.vmem_budget_bytes()
    for hb in range(hkv, 1, -1):
        if hkv % hb == 0 and _vmem_bytes(hb, page, d, dtype,
                                         group) <= budget:
            return hb
    return 1


def _head_block(hb, page, hkv, d, dtype, group):
    """The head block a call runs at: the caller's, lowered to a
    divisor of Hkv, or else the one derived from the shapes
    (`_default_head_block`)."""
    if hb is None:
        return _default_head_block(page, hkv, d, dtype, group)
    hb = max(1, int(hb))  # ptlint: disable=PT001 -- static config knob
    while hkv % hb:
        hb -= 1
    return hb


def _kernel(*refs, scale, page, hkv, max_pages, hb, with_stats):
    # Ref layout (the stats output exists only when requested, so the
    # trailing refs shift, same convention as the contiguous decode
    # kernel):
    #   len, table, q, k_pool, v_pool | o, [ml] |
    #   kbuf, vbuf, sems, base, acc, m, l
    # The pools stay where they are (memory space ANY); kbuf/vbuf are
    # the ppp + 1 landing buffers of a page's hb heads, and base (SMEM)
    # is the buffer that holds THIS program's first page: the program
    # before it started that copy, so the buffers rotate across
    # programs and the grid runs in order ("arbitrary").
    len_ref, table_ref, q_ref, k_hbm, v_hbm = refs[:5]
    rest = refs[5:]
    ml_ref = None
    if with_stats:
        o_ref, ml_ref = rest[:2]
        rest = rest[2:]
    else:
        o_ref, rest = rest[0], rest[1:]
    kbuf, vbuf, sems, base_ref, acc_ref, m_ref, l_ref = rest
    nhb = hkv // hb
    ppp = _PAGES_IN_FLIGHT
    nbuf = ppp + 1
    chunk = math.gcd(hb, _HEAD_CHUNK)
    bh = pl.program_id(0)

    from paddle_tpu.ops.pallas.decode_attention import (
        online_softmax_finalize, online_softmax_init,
        online_softmax_step, online_softmax_write_stats)

    def live_pages(prog):
        # a row's live pages: the walk ends at the last of them,
        # whatever the table's width (and inside the table, whatever
        # the length)
        return jnp.minimum((len_ref[prog // nhb] + page - 1) // page,
                           max_pages)

    def copies(prog, j, slot):
        # page j of program prog's row: its hb heads are hb consecutive
        # rows of the pools' (N*Hkv, page, D) view
        row0 = (table_ref[(prog // nhb) * max_pages + j] * hkv
                + (prog % nhb) * hb)
        return (pltpu.make_async_copy(k_hbm.at[pl.ds(row0, hb)],
                                      kbuf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pl.ds(row0, hb)],
                                      vbuf.at[slot], sems.at[1, slot]))

    def fetch(prog, j, slot):
        for c in copies(prog, j, slot):
            c.start()

    length = len_ref[bh // nhb]
    n_live = live_pages(bh)

    @pl.when(bh == 0)
    def _first_program():
        base_ref[0] = 0

        @pl.when(n_live > 0)
        def _own_first_page():
            fetch(bh, 0, 0)

    base = base_ref[0]

    def fetch_next_program():
        # the next program's first page lands while this one folds its
        # last, in the buffer after it (free: its page is folded)
        @pl.when(bh + 1 < pl.num_programs(0))
        def _in_grid():
            @pl.when(live_pages(bh + 1) > 0)
            def _start():
                fetch(bh + 1, 0, (base + n_live) % nbuf)

    online_softmax_init(acc_ref, m_ref, l_ref)
    for j in range(1, ppp):
        @pl.when(j < n_live)
        def _prologue(j=j):
            fetch(bh, j, (base + j) % nbuf)

    def fold(j, carry):
        @pl.when(j + ppp < n_live)
        def _ahead():
            fetch(bh, j + ppp, (base + j + ppp) % nbuf)

        @pl.when(j == n_live - 1)
        def _last_page():
            fetch_next_program()

        slot = (base + j) % nbuf
        for c in copies(bh, j, slot):
            c.wait()

        def fold_heads(g, carry):
            hs = pl.ds(g * chunk, chunk)
            online_softmax_step(q_ref[hs], kbuf[slot, hs], vbuf[slot, hs],
                                j * page, length, acc_ref.at[hs],
                                m_ref.at[hs], l_ref.at[hs], scale)
            return carry

        return jax.lax.fori_loop(0, hb // chunk, fold_heads, carry)

    jax.lax.fori_loop(0, n_live, fold, 0)

    @pl.when(n_live == 0)
    def _empty_row():
        fetch_next_program()

    base_ref[0] = (base + n_live) % nbuf

    online_softmax_finalize(o_ref, acc_ref, l_ref)
    if with_stats:
        online_softmax_write_stats(ml_ref, m_ref, l_ref)


def _write_kernel(len_ref, _wpid_ref, krow_ref, vrow_ref, kin_ref,
                  vin_ref, kout_ref, vout_ref, *, page, nhb, hb, sub):
    # one program per (row, head block): the block is the sublane tile
    # of the row's write page that holds row ``length % page`` (the
    # index maps read wpid and the length); that row is replaced by the
    # fresh row, the tile's other rows pass through
    off = len_ref[pl.program_id(0) // nhb] % page % sub
    sel = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0) == off
    for h in range(hb):
        kout_ref[h] = jnp.where(sel, krow_ref[h][:1], kin_ref[h])
        vout_ref[h] = jnp.where(sel, vrow_ref[h][:1], vin_ref[h])


def paged_decode_attention_reference(q, k_pages, v_pages, page_table,
                                     lengths, scale=None):
    """XLA oracle: gather each row's pages contiguous, then full masked
    softmax. q: (B, Hq, D); pools (P, Hkv, page, D); page_table
    (B, max_pages) int32; lengths (B,)."""
    b, hq, d = q.shape
    hkv, page = k_pages.shape[1], k_pages.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # (B, max_pages, Hkv, page, D) -> (B, Hkv, max_pages*page, D)
    kg = jnp.swapaxes(k_pages[page_table], 1, 2)
    vg = jnp.swapaxes(v_pages[page_table], 1, 2)
    kc = kg.reshape(b, hkv, -1, d)
    vc = vg.reshape(b, hkv, -1, d)
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bhtd->bhgt", qg, kc).astype(jnp.float32) * scale
    T = kc.shape[2]
    mask = jnp.arange(T)[None, None, None, :] < lengths[:, None, None,
                                                        None]
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bhtd->bhgd", p.astype(vc.dtype), vc)
    return o.reshape(b, hq, d).astype(q.dtype)


def _sublanes(dtype):
    return 16 if dtype in (jnp.bfloat16, jnp.float16) else 8


def _paged_call(q, k_pages, v_pages, page_table, lengths, scale,
                interpret, return_stats, head_block,
                name="paged_decode_attention"):
    """Call-site builder of the read-only paged attend; ``name`` is the
    launch's name in a device trace."""
    q = jnp.asarray(q)
    k_pages, v_pages = jnp.asarray(k_pages), jnp.asarray(v_pages)
    b, hq, d = q.shape
    hkv, page = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    if hq % hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {hq} vs {hkv}")
    if page % _LANES:
        raise ValueError(f"page_size {page} must be a multiple of "
                         f"{_LANES}")
    group = hq // hkv
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    hb = _head_block(head_block, page, hkv, d, q.dtype, group)
    nhb = hkv // hb
    nbuf = _PAGES_IN_FLIGHT + 1

    gp = _group_rows(group, q.dtype)
    qg = q.reshape(b * hkv, group, d)
    qg = jnp.pad(qg, ((0, 0), (0, gp - group), (0, 0)))

    lengths = jnp.asarray(lengths, jnp.int32)
    table_flat = jnp.asarray(page_table, jnp.int32).reshape(-1)
    # the pools' head-major view (P, Hkv, page, D) -> (P*Hkv, page, D):
    # rows p*Hkv + h, so the hb heads of one page are hb consecutive
    # rows, one contiguous copy
    kp = k_pages.reshape(-1, page, d)
    vp = v_pages.reshape(-1, page, d)

    def bh_index(bh, lens, table):
        return (bh, 0, 0)

    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    out_specs = [pl.BlockSpec((hb, gp, d), bh_index)]
    out_shape = [jax.ShapeDtypeStruct((b * hkv, gp, d), q.dtype)]
    if return_stats:  # stats output only exists when asked for
        out_specs.append(pl.BlockSpec((hb, gp, _LANES), bh_index))
        out_shape.append(
            jax.ShapeDtypeStruct((b * hkv, gp, _LANES), jnp.float32))

    # one program a (row, head block): the page walk is the program's
    # own loop, so the grid does not know the table's width. In order
    # ("arbitrary"): a program starts the copy of the next one's first
    # page
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * nhb,),
        in_specs=[pl.BlockSpec((hb, gp, d), bh_index), pool_spec,
                  pool_spec],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((nbuf, hb, page, d), kp.dtype),
            pltpu.VMEM((nbuf, hb, page, d), vp.dtype),
            pltpu.SemaphoreType.DMA((2, nbuf)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((hb, gp, d), jnp.float32),
            pltpu.VMEM((hb, gp, _LANES), jnp.float32),
            pltpu.VMEM((hb, gp, _LANES), jnp.float32),
        ],
    )
    res = pl.pallas_call(
        # ptlint: disable=PT001 -- scale is a static Python float kwarg
        # (a tracer here would already fail partial-binding)
        functools.partial(_kernel, scale=float(scale), page=page,
                          hkv=hkv, max_pages=max_pages, hb=hb,
                          with_stats=return_stats),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=name,
        interpret=interpret,
    )(lengths, table_flat, qg, kp, vp)
    o = res[0][:, :group, :].reshape(b, hq, d)
    if not return_stats:
        return o
    ml = res[1]
    m = ml[:, :group, 0].reshape(b, hq)
    l = ml[:, :group, 1].reshape(b, hq)
    return o, m, l


def _write_rows(k_pages, v_pages, k_row, v_row, write_pids, lengths, hb,
                interpret):
    """The append half of `paged_append_attend`: row b's fresh K/V row
    replaces row ``lengths[b] % page`` of pool page ``write_pids[b]``,
    K and V in one launch: a program moves the sublane tile that holds
    the row (16 rows of bf16, 8 of float32) in, replaces the row and
    moves the tile out. Each pool is passed ONCE, its write block
    aliased in to out — the call's only use of the pool, so XLA leaves
    the pool where it is."""
    b, hkv, d = k_row.shape
    page = k_pages.shape[2]
    nhb = hkv // hb
    sub = _sublanes(k_pages.dtype)

    def rows(r):            # (B, Hkv, D) -> sublane-padded row blocks
        r = jnp.asarray(r).reshape(b * hkv, 1, d)
        return jnp.pad(r, ((0, 0), (0, sub - 1), (0, 0)))

    def row_index(bh, lens, wpids):
        return (bh, 0, 0)

    def tile_index(bh, lens, wpids):
        b_ = bh // nhb
        return (wpids[b_] * nhb + bh % nhb, lens[b_] % page // sub, 0)

    row_spec = pl.BlockSpec((hb, sub, d), row_index)
    tile_spec = pl.BlockSpec((hb, sub, d), tile_index)
    kp = k_pages.reshape(-1, page, d)
    vp = v_pages.reshape(-1, page, d)
    kp, vp = pl.pallas_call(
        functools.partial(_write_kernel, page=page, nhb=nhb, hb=hb,
                          sub=sub),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * nhb,),
            in_specs=[row_spec, row_spec, tile_spec, tile_spec],
            out_specs=[tile_spec, tile_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        # operand numbering counts the two scalar-prefetch refs and the
        # two row operands: pools 4 and 5 alias outputs 0 and 1, so
        # what no program writes keeps its input values
        input_output_aliases={4: 0, 5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="paged_append_attend_write",
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(write_pids, jnp.int32),
      rows(k_row), rows(v_row), kp, vp)
    return kp.reshape(k_pages.shape), vp.reshape(v_pages.shape)


def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           scale=None, interpret=None,
                           return_stats=False, head_block=None):
    """One decode step of cached attention over a PAGED KV pool.

    Args:
      q: (B, Hq, D) — each sequence's current-position query.
      k_pages, v_pages: (P, Hkv, page_size, D) shared page pools;
        page_size must be a multiple of 128.
      page_table: (B, max_pages) int32 — row b's i-th page id in the
        pool; entries beyond ceil(lengths[b]/page_size) are ignored.
      lengths: (B,) int32 — row b attends to its first lengths[b]
        tokens (at most the table's ``max_pages * page_size``). Pages
        beyond a row's length are neither fetched nor visited: the
        program's page loop ends at the row's last live page.
      scale: softmax scale, default 1/sqrt(D).
      interpret: defaults to True off-TPU so tests run on CPU.
      return_stats: also return the online-softmax running max ``m``
        and denominator ``l`` (each (B, Hq) f32) so the caller can
        fold extra attention columns in analytically — the paged
        engine's pre-fusion formulation added the current token's
        fresh KV row this way, keeping the pools READ-ONLY inside its
        layer scan.
      head_block: KV heads a program takes. Default (None): the
        largest divisor of Hkv whose blocks fit VMEM.

    Returns (B, Hq, D) in q's dtype; with return_stats, (o, m, l).
    """
    return _paged_call(q, k_pages, v_pages, page_table, lengths, scale,
                       interpret, return_stats, head_block)


def paged_append_attend(q, k_pages, v_pages, k_row, v_row, page_table,
                        write_pids, lengths, scale=None, interpret=None,
                        head_block=None):
    """Append+attend decode step over a paged KV pool, in two launches.

    First the write kernel (`paged_append_attend_write` in a trace)
    merges each row's fresh KV row (``k_row``/``v_row``, the current
    token's key/value) into pool page ``write_pids[b]`` at row offset
    ``lengths[b] % page_size``, in place: each pool is that call's one
    pool operand, aliased to its output, so the write touches exactly
    one sublane tile per (row, KV-head) and nothing copies the pool. Then the
    read-only attend (`paged_append_attend` in a trace) runs over the
    pools the write returned, each row over ``lengths[b] + 1`` tokens:
    its prefix plus the row just written, folded in page order.

    Args:
      q: (B, Hq, D) current-position queries.
      k_pages, v_pages: (P, Hkv, page, D) pools (DONATED — aliased into
        the returned pools; do not reuse the inputs).
      k_row, v_row: (B, Hkv, D) fresh rows in pool dtype.
      page_table: (B, max_pages) int32 as in `paged_decode_attention`.
      write_pids: (B,) int32 — the pool page receiving row b's fresh KV
        (callers derive it from the block table + per-slot length, and
        point masked-out rows at a scratch page).
      lengths: (B,) int32 prefix lengths; the fresh row lands at
        position lengths[b].

    Returns (o, k_pages, v_pages): o (B, Hq, D) is a softmax over
    [prefix + fresh row] for every row whose ``write_pids[b]`` is the
    table's page at position lengths[b]. A masked-out row (written to
    a scratch page) attends over its prefix plus whatever its own page
    holds at that position: finite for a finite pool, and the caller's
    to discard.
    """
    q = jnp.asarray(q)
    k_pages, v_pages = jnp.asarray(k_pages), jnp.asarray(v_pages)
    hkv, page, d = k_pages.shape[1:]
    max_pages = page_table.shape[1]
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # one geometry for both launches: the write's block is the attend's
    hb = _head_block(head_block, page, hkv, d, q.dtype,
                     q.shape[1] // hkv)
    lengths = jnp.asarray(lengths, jnp.int32)
    k_pages, v_pages = _write_rows(k_pages, v_pages, k_row, v_row,
                                   write_pids, lengths, hb, interpret)
    # a row that is full has no page to take a fresh row, so the clamp
    # only keeps such a (masked-out) row's page walk inside its table
    o = _paged_call(q, k_pages, v_pages, page_table,
                    jnp.minimum(lengths + 1, max_pages * page), scale,
                    interpret, False, hb, name="paged_append_attend")
    return o, k_pages, v_pages


class PageAllocator:
    """LIFO free-list page allocator: the ONE reserve/release
    implementation shared by `PagedKVCache` and the paged serving
    engine."""

    def __init__(self, n_pages: int, page_size: int,
                 max_pages_per_seq: int = 0):
        self.page = int(page_size)
        self.n_pages = int(n_pages)
        self.max_pages = int(max_pages_per_seq)
        self._free = list(range(n_pages - 1, -1, -1))

    @property
    def free_pages(self) -> int:
        return len(self._free)

    def reserve(self, table, n_tokens):
        """Grow ``table`` (a list of page ids) to cover ``n_tokens``."""
        need = (n_tokens + self.page - 1) // self.page
        while len(table) < need:
            if not self._free:
                raise MemoryError("page pool exhausted")
            if self.max_pages and len(table) >= self.max_pages:
                raise MemoryError(
                    f"sequence exceeds max_pages_per_seq="
                    f"{self.max_pages}")
            table.append(self._free.pop())
        return table

    def release(self, table):
        self._free.extend(reversed(table))
        table.clear()


class PagedKVCache:
    """Host-side page pool + tables (the allocator half of paged
    serving; the kernel half is `paged_decode_attention`).

    One pool per model: k/v pages (P, Hkv, page, D) per layer stacked
    as (L, P, Hkv, page, D). Sequences allocate pages on demand and
    free them at retirement; `write_rows` places one decode step's new
    KV rows at each sequence's current position (page id + offset
    resolved host-side, written with per-sequence dynamic updates).
    """

    def __init__(self, n_layers, n_pages, kv_heads, page_size, head_dim,
                 dtype=jnp.bfloat16, max_pages_per_seq=None):
        if page_size % _LANES:
            raise ValueError(f"page_size {page_size} must be a multiple "
                             f"of {_LANES}")
        self.page = int(page_size)
        self.n_pages = int(n_pages)
        shape = (n_layers, n_pages, kv_heads, page_size, head_dim)
        self.kp = jnp.zeros(shape, dtype)
        self.vp = jnp.zeros(shape, dtype)
        self._alloc = PageAllocator(n_pages, page_size,
                                    max_pages_per_seq or 0)
        self.tables = {}        # seq id -> [page ids]
        self.lengths = {}       # seq id -> tokens written

    @property
    def free_pages(self):
        return self._alloc.free_pages

    def alloc_seq(self, seq_id, n_tokens=0):
        if seq_id in self.tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        self.tables[seq_id] = []
        self.lengths[seq_id] = 0
        if n_tokens:
            self.reserve(seq_id, n_tokens)

    def reserve(self, seq_id, n_tokens):
        """Ensure capacity for ``n_tokens`` total tokens."""
        self._alloc.reserve(self.tables[seq_id], n_tokens)

    def free_seq(self, seq_id):
        self._alloc.release(self.tables[seq_id])
        self.tables.pop(seq_id)
        self.lengths.pop(seq_id)

    def write_rows(self, seq_id, k_rows, v_rows):
        """Append one step's KV rows for every layer: k_rows/v_rows
        (L, Hkv, K, D) land at the sequence's current length. Writes
        go per touched PAGE RUN (rows within one page are contiguous),
        not per token — ceil(K/page)+1 updates instead of K."""
        K = k_rows.shape[2]
        pos = self.lengths[seq_id]
        self.reserve(seq_id, pos + K)
        tab = self.tables[seq_id]
        t = 0
        while t < K:
            pid = tab[(pos + t) // self.page]
            off = (pos + t) % self.page
            run = min(K - t, self.page - off)
            self.kp = jax.lax.dynamic_update_slice(
                self.kp, k_rows[:, None, :, t:t + run, :],
                (0, pid, 0, off, 0))
            self.vp = jax.lax.dynamic_update_slice(
                self.vp, v_rows[:, None, :, t:t + run, :],
                (0, pid, 0, off, 0))
            t += run
        self.lengths[seq_id] = pos + K

    def gather_args(self, seq_ids, layer):
        """(page_table, lengths) padded over ``seq_ids`` plus the
        layer's pools — the kernel-call operands for one layer."""
        import numpy as np
        mx = max(1, max(len(self.tables[s]) for s in seq_ids))
        table = np.zeros((len(seq_ids), mx), np.int32)
        lens = np.zeros((len(seq_ids),), np.int32)
        for i, s in enumerate(seq_ids):
            tab = self.tables[s]
            table[i, :len(tab)] = tab
            lens[i] = self.lengths[s]
        return (jnp.asarray(table), jnp.asarray(lens),
                self.kp[layer], self.vp[layer])


def ptgeom_cases():
    """Geometry registry for tools/ptgeom.py (ISSUE 20): the read-only
    attend and the append+attend at the derived head block on every
    rung, and at explicit head blocks on two, under jax.eval_shape."""
    from paddle_tpu.analysis import kernelmodel as km

    def case(geom, hb, append):
        p = km.LADDER[geom]
        d = p["dm"] // p["heads"]
        hkv = p["kv_heads"]
        page = p["page"]
        B = 16
        mx = max(1, p["seq"] // page)
        q = km.sds((B, p["heads"], d), p["dtype"])
        pool = km.sds((B * mx + 1, hkv, page, d), p["dtype"])
        table = km.sds((B, mx), "int32")
        vec = km.sds((B,), "int32")
        row = km.sds((B, hkv, d), p["dtype"])

        def run():
            import jax as _jax
            if append:
                _jax.eval_shape(
                    lambda q, kp, vp, kr, vr, tab, wp, ln:
                    paged_append_attend(q, kp, vp, kr, vr, tab, wp,
                                        ln, head_block=hb),
                    q, pool, pool, row, row, table, vec, vec)
            else:
                _jax.eval_shape(
                    lambda q, kp, vp, tab, ln: paged_decode_attention(
                        q, kp, vp, tab, ln, head_block=hb),
                    q, pool, pool, table, vec)
        tag = "fused" if append else "plain"
        config = "default" if hb is None else f"hb{hb}"
        return km.GeomCase(kernel=f"paged_{tag}", geometry=geom,
                           config=config, run=run)

    cases = [case("tiny", 1, True)]
    # the geometry a call gets when it names none (what the benchmark's
    # serving cells and every engine run), at every rung
    for geom in km.LADDER:
        for append in (False, True):
            cases.append(case(geom, None, append))
    for geom in ("350m", "r06"):
        for hb in (1, 2, 4):
            cases.append(case(geom, hb, False))
        for hb in (1, 2):
            cases.append(case(geom, hb, True))
    return cases
