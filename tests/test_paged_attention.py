"""Paged (block-table) flash-decode attention + the page-pool allocator.

The serving memory model the slot-contiguous DecodeEngine cache cannot
express: pages shared across sequences, allocated on demand, freed at
retirement — memory scales with the sum of live lengths. No reference
analog (fused_multi_transformer serves one contiguous CacheKV per
sequence)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.paged_attention import (
    PagedKVCache, paged_append_attend, paged_decode_attention,
    paged_decode_attention_reference)


def _pool(rs, P, hkv, page, d, dtype=jnp.float32):
    k = jnp.asarray(rs.randn(P, hkv, page, d), dtype)
    v = jnp.asarray(rs.randn(P, hkv, page, d), dtype)
    return k, v


def test_kernel_matches_gather_oracle():
    rs = np.random.RandomState(0)
    P, hkv, page, d = 12, 4, 128, 32
    b, max_pages = 3, 3
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    # rows own disjoint page lists with ragged lengths
    table = jnp.asarray([[0, 5, 2], [7, 1, 3], [9, 4, 11]], jnp.int32)
    lengths = jnp.asarray([300, 140, 17], jnp.int32)
    got = paged_decode_attention(q, k, v, table, lengths)
    want = paged_decode_attention_reference(q, k, v, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_kernel_stats_fold_fresh_row():
    """return_stats lets a caller fold one extra KV column analytically:
    folding the fresh row into (o, m, l) must equal re-running the
    kernel with the row already written into the pool (lengths + 1) —
    the read-only-pool decode formulation the paged engine uses."""
    rs = np.random.RandomState(3)
    P, hkv, page, d = 10, 2, 128, 32
    group = 3
    hq = hkv * group
    b, max_pages = 3, 2
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hq, d), jnp.float32)
    table = jnp.asarray([[0, 5], [7, 1], [9, 4]], jnp.int32)
    lengths = jnp.asarray([130, 128, 0], jnp.int32)  # incl. page edge + empty
    k_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    v_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    o, m, l = paged_decode_attention(q, k, v, table, lengths,
                                     return_stats=True)
    qg = q.reshape(b, hkv, group, d)
    s_new = jnp.einsum("bhgd,bhd->bhg", qg, k_row).reshape(b, hq) * scale
    m2 = jnp.maximum(m, s_new)
    w_pre = l * jnp.exp(m - m2)
    w_new = jnp.exp(s_new - m2)
    v_exp = jnp.repeat(v_row, group, axis=1)
    folded = ((o * w_pre[..., None] + v_exp * w_new[..., None])
              / (w_pre + w_new)[..., None])

    # oracle: write each row at its position, re-run over lengths + 1
    k2, v2 = k, v
    for i in range(b):
        pid = int(table[i, int(lengths[i]) // page])
        off = int(lengths[i]) % page
        k2 = k2.at[pid, :, off, :].set(k_row[i])
        v2 = v2.at[pid, :, off, :].set(v_row[i])
    want = paged_decode_attention(q, k2, v2, table, lengths + 1)
    np.testing.assert_allclose(np.asarray(folded), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _scatter_oracle(k, v, k_row, v_row, table, write_pids, lengths,
                    page):
    """The pre-fusion formulation: write each row's fresh KV at its
    position with an XLA scatter, then attend over lengths + 1."""
    k2, v2 = k, v
    for i in range(k_row.shape[0]):
        pid = int(write_pids[i])
        off = int(lengths[i]) % page
        k2 = k2.at[pid, :, off, :].set(k_row[i])
        v2 = v2.at[pid, :, off, :].set(v_row[i])
    return k2, v2


@pytest.mark.parametrize("group,head_block", [(1, None), (4, None),
                                              (1, 2)])
def test_fused_append_attend_matches_scatter_then_attend(group,
                                                         head_block):
    """ISSUE 6 tentpole parity: `paged_append_attend` (fresh KV row
    merged into its pool page by the write launch, then attended with
    the prefix) must be bit-compatible with the scatter-then-attend
    formulation it replaces — both the attention output and the ENTIRE
    pool (the in-place write lands exactly one row; untouched pages
    identical). Covers page-edge lengths (write lands in a fresh page),
    an empty row (length 0), GQA, and a non-default head block."""
    rs = np.random.RandomState(11)
    P, hkv, page, d = 10, 2, 128, 32
    b, max_pages = 3, 3
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv * group, d), jnp.float32)
    table = jnp.asarray([[0, 5, 2], [7, 1, 3], [9, 4, 6]], jnp.int32)
    # page edge (write opens page 5), mid-page, empty row
    lengths = jnp.asarray([128, 140, 0], jnp.int32)
    k_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    v_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    wpids = jnp.asarray(
        [int(table[i, int(lengths[i]) // page]) for i in range(b)],
        jnp.int32)

    o, k_out, v_out = paged_append_attend(
        q, k, v, k_row, v_row, table, wpids, lengths,
        head_block=head_block)

    k2, v2 = _scatter_oracle(k, v, k_row, v_row, table, wpids, lengths,
                             page)
    want = paged_decode_attention(q, k2, v2, table, lengths + 1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v2))


def test_fused_append_attend_jit_and_scratch_page():
    """Under jit (the engine's layer scan) with masked rows pointed at
    a scratch page: the scratch page absorbs the write, every pool page
    a live row owns stays byte-identical to the scatter oracle."""
    rs = np.random.RandomState(12)
    P, hkv, page, d = 6, 2, 128, 16
    b = 2
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, 4 * hkv, d), jnp.float32)
    table = jnp.asarray([[1, 3], [2, 4]], jnp.int32)
    lengths = jnp.asarray([130, 70], jnp.int32)
    k_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    v_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    scratch = P - 1                        # row 1 "inactive": write there
    wpids = jnp.asarray([3, scratch], jnp.int32)

    @jax.jit
    def f(q, k, v, k_row, v_row, table, wpids, lengths):
        return paged_append_attend(q, k, v, k_row, v_row, table, wpids,
                                   lengths)

    o, k_out, v_out = f(q, k, v, k_row, v_row, table, wpids, lengths)
    k2, v2 = _scatter_oracle(k, v, k_row, v_row, table, wpids, lengths,
                             page)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v2))
    # the attend reads the pools the write returned, over lengths + 1:
    # the live row sees its fresh row; the masked row (whose write went
    # to scratch) sees its own page's stale row at that position — the
    # engine discards it, but it must still be well-defined
    want = paged_decode_attention(q, k2, v2, table, lengths + 1)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert np.isfinite(np.asarray(o)).all()
    # row 1's own pages untouched (its write went to scratch)
    for pid in (2, 4):
        np.testing.assert_array_equal(np.asarray(k_out[pid]),
                                      np.asarray(k[pid]))


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("rem", [0, 1, 127])
def test_append_attend_page_offsets_and_idle_slot(rem, group, d):
    """The write launch at the offsets that matter (``lengths % page``
    0: the row opens a new page; 1; page - 1: the page's last row),
    beside an IDLE slot as the engine presents one: its table row is
    zeros (pool page 0, which the first live row owns) and its write is
    pointed at the scratch page. Pools bit for bit against the scatter;
    page 0 unchanged; the two write pages and the scratch page the only
    pages touched; the live rows' outputs equal scatter-then-attend."""
    rs = np.random.RandomState(100 + rem + group + d)
    P, hkv, page = 9, 2, 128
    scratch = P - 1
    k, v = _pool(rs, P, hkv, page, d)
    b = 3
    q = jnp.asarray(rs.randn(b, hkv * group, d), jnp.float32)
    table = jnp.asarray([[0, 5], [7, 1], [0, 0]], jnp.int32)
    # row 0: a full page 0, then ``rem`` rows of page 5; row 1: ``rem``
    # rows of page 7 (rem 0: an empty row); row 2 idle
    lengths = jnp.asarray([page + rem, rem, page + rem], jnp.int32)
    k_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    v_row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    wpids = jnp.asarray([5, 7, scratch], jnp.int32)

    o, k_out, v_out = jax.jit(paged_append_attend)(
        q, k, v, k_row, v_row, table, wpids, lengths)

    k2, v2 = _scatter_oracle(k, v, k_row, v_row, table, wpids, lengths,
                             page)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(k2))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(v2))
    for got, was in ((k_out, k), (v_out, v)):
        touched = {pid for pid in range(P)
                   if not np.array_equal(np.asarray(got[pid]),
                                         np.asarray(was[pid]))}
        assert touched == {5, 7, scratch}
        # exactly one row of each written page changed
        for pid in (5, 7, scratch):
            diff = np.any(np.asarray(got[pid]) != np.asarray(was[pid]),
                          axis=(0, 2))
            assert np.flatnonzero(diff).tolist() == [rem]
    want = paged_decode_attention(q, k2, v2, table, lengths + 1)
    np.testing.assert_allclose(np.asarray(o[:2]), np.asarray(want[:2]),
                               atol=1e-5, rtol=1e-5)
    # the idle slot's output is thrown away by the engine, and finite
    assert np.isfinite(np.asarray(o)).all()


@pytest.mark.parametrize("family", ["paged_append", "paged_attention"])
def test_planted_autotune_entry_does_not_move_the_geometry(
        tmp_path, monkeypatch, family):
    """The paged kernels' geometry is the call's own (its head block,
    or the one derived from the shapes): an entry under the key the
    removed paged tuner wrote, in the cache flash attention still
    reads, changes neither launch's grid."""
    import paddle_tpu.ops.pallas.autotune as at

    monkeypatch.setattr(at, "_GLOBAL", None)
    monkeypatch.setenv("PT_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    rs = np.random.RandomState(13)
    P, hkv, page, d, b = 8, 4, 128, 16, 2
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    table = jnp.asarray([[0, 5], [7, 1]], jnp.int32)
    lengths = jnp.asarray([200, 140], jnp.int32)
    if family == "paged_append":
        fn, args = paged_append_attend, (q, k, v, row, row, table,
                                         table[:, 1], lengths)
    else:
        fn, args = paged_decode_attention, (q, k, v, table, lengths)
    before = _attend_programs(fn, *args)
    at.get_cache().put(at.AutotuneCache.key(
        family, page=page, hkv=hkv, d=d, dtype=str(q.dtype), group=1),
        (2, 1))
    assert (tmp_path / "autotune.json").exists()
    assert _attend_programs(fn, *args) == before
    # all four heads a program: one program a row, in every launch
    assert set(before) == {(b,)}
    # an explicit head block is the only way to another grid
    assert _attend_programs(
        lambda *a: fn(*a, head_block=1), *args) == [(b * hkv,)] * len(before)


def test_kernel_gqa_and_jit_traced_operands():
    rs = np.random.RandomState(1)
    P, hkv, page, d = 8, 2, 128, 16
    hq = 8                                   # GQA group = 4
    b, max_pages = 2, 2
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hq, d), jnp.float32)
    table = jnp.asarray([[3, 6], [0, 2]], jnp.int32)
    lengths = jnp.asarray([129, 256], jnp.int32)

    @jax.jit
    def f(q, k, v, table, lengths):
        return paged_decode_attention(q, k, v, table, lengths)

    got = f(q, k, v, table, lengths)
    want = paged_decode_attention_reference(q, k, v, table, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_pool_allocator_lifecycle():
    pool = PagedKVCache(n_layers=2, n_pages=6, kv_heads=2, page_size=128,
                        head_dim=8, dtype=jnp.float32)
    pool.alloc_seq("a", n_tokens=200)       # 2 pages
    pool.alloc_seq("b", n_tokens=100)       # 1 page
    assert pool.free_pages == 3
    # appending across a page boundary allocates on demand
    rows = jnp.ones((2, 2, 30, 8), jnp.float32)
    pool.lengths["b"] = 100
    pool.write_rows("b", rows, rows)
    assert pool.lengths["b"] == 130 and len(pool.tables["b"]) == 2
    pool.free_seq("a")
    assert pool.free_pages == 4              # a's 2 back; b holds 2
    # exhaustion raises; the partial allocation frees cleanly
    with pytest.raises(MemoryError):
        pool.alloc_seq("c", n_tokens=128 * 5)
    pool.free_seq("c")
    pool.free_seq("b")
    assert pool.free_pages == 6              # everything back


def test_pool_write_then_attend_matches_contiguous():
    """Write per-token rows through the allocator, attend via the paged
    kernel, compare against contiguous attention over the same rows."""
    from paddle_tpu.ops.pallas.decode_attention import (
        decode_attention_reference)

    rs = np.random.RandomState(2)
    L, hkv, page, d = 1, 2, 128, 16
    pool = PagedKVCache(n_layers=L, n_pages=5, kv_heads=hkv,
                        page_size=page, head_dim=d, dtype=jnp.float32)
    n_tok = 150                               # straddles two pages
    pool.alloc_seq("s")
    krows = rs.randn(L, hkv, n_tok, d).astype(np.float32)
    vrows = rs.randn(L, hkv, n_tok, d).astype(np.float32)
    pool.write_rows("s", jnp.asarray(krows), jnp.asarray(vrows))

    q = jnp.asarray(rs.randn(1, hkv, d), jnp.float32)
    table, lens, kp, vp = pool.gather_args(["s"], layer=0)
    got = paged_decode_attention(q, kp, vp, table, lens)

    kc = np.zeros((1, hkv, 256, d), np.float32)
    vc = np.zeros((1, hkv, 256, d), np.float32)
    kc[0, :, :n_tok] = krows[0]
    vc[0, :, :n_tok] = vrows[0]
    want = decode_attention_reference(q, jnp.asarray(kc),
                                      jnp.asarray(vc),
                                      jnp.asarray([n_tok], jnp.int32))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_shared_pool_two_sequences_interleaved():
    """Two sequences interleave appends into one pool; each attends only
    to its own pages."""
    rs = np.random.RandomState(3)
    hkv, page, d = 2, 128, 16
    pool = PagedKVCache(n_layers=1, n_pages=4, kv_heads=hkv,
                        page_size=page, head_dim=d, dtype=jnp.float32)
    pool.alloc_seq("x")
    pool.alloc_seq("y")
    kx = rs.randn(1, hkv, 140, d).astype(np.float32)
    ky = rs.randn(1, hkv, 40, d).astype(np.float32)
    # interleaved appends
    pool.write_rows("x", jnp.asarray(kx[:, :, :70]),
                    jnp.asarray(kx[:, :, :70]))
    pool.write_rows("y", jnp.asarray(ky), jnp.asarray(ky))
    pool.write_rows("x", jnp.asarray(kx[:, :, 70:]),
                    jnp.asarray(kx[:, :, 70:]))

    q = jnp.asarray(rs.randn(2, hkv, d), jnp.float32)
    table, lens, kp, vp = pool.gather_args(["x", "y"], layer=0)
    got = paged_decode_attention(q, kp, vp, table, lens)
    want = paged_decode_attention_reference(q, kp, vp, table, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    assert list(np.asarray(lens)) == [140, 40]


def _live_table(rs, b, columns, live, P):
    """A table ``columns`` wide whose rows own ``live`` distinct pages
    each; the columns past them hold page ids OUTSIDE the pool, so a
    kernel that so much as fetched one would fault (or read garbage the
    oracle does not)."""
    table = np.full((b, columns), P + 7, np.int32)
    table[:, :live] = rs.permutation(P)[:b * live].reshape(b, live)
    return table


@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("hkv,group,d", [(2, 1, 64), (2, 4, 128),
                                         (16, 1, 128), (16, 4, 64)])
@pytest.mark.parametrize("columns", [16, 32])
def test_default_geometry_wide_table_one_live_page(columns, hkv, group, d,
                                                   stats):
    """ISSUE 30: at the DEFAULT geometry (no tuner, no kwargs), under
    jit, a table far wider than what is live: one live page of 16 and of
    32 columns, beside a length-0 row. The oracle gathers through a
    table whose dead columns are clamped into the pool; the kernel gets
    them out of range and must never touch them."""
    rs = np.random.RandomState(columns + hkv + group + d)
    P, page, b = 5, 128, 3
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv * group, d), jnp.float32)
    table = _live_table(rs, b, columns, 1, P)
    lengths = jnp.asarray([page, 0, 37], jnp.int32)

    f = jax.jit(lambda *a: paged_decode_attention(*a, return_stats=stats))
    got = f(q, k, v, jnp.asarray(table), lengths)
    want = paged_decode_attention_reference(
        q, k, v, jnp.asarray(np.minimum(table, P - 1)), lengths)
    o = got[0] if stats else got
    # the length-0 row: the kernel writes zeros (l == 0), the oracle's
    # all-masked softmax is NaN
    np.testing.assert_allclose(np.asarray(o)[[0, 2]],
                               np.asarray(want)[[0, 2]],
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(o)[1].any()
    if stats:
        _, m, l = got
        assert np.isneginf(np.asarray(m)[1]).all()
        assert not np.asarray(l)[1].any()
        assert (np.asarray(l)[[0, 2]] > 0).all()


@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("hkv,group,d", [(2, 4, 64), (16, 1, 128)])
def test_default_geometry_full_table_row(hkv, group, d, stats):
    """A row that FILLS its table (every column live, the last page
    full) beside a one-token row, default geometry under jit; and past
    the table: a length beyond ``columns * page`` walks the table's
    columns and no further."""
    rs = np.random.RandomState(7 + hkv + d)
    P, page, b, columns = 9, 128, 2, 4
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv * group, d), jnp.float32)
    table = jnp.asarray(_live_table(rs, b, columns, columns, P))
    f = jax.jit(lambda *a: paged_decode_attention(*a, return_stats=stats))
    full = jnp.asarray([columns * page, 1], jnp.int32)
    want = paged_decode_attention_reference(q, k, v, table, full)
    for lengths in (full, full + jnp.asarray([300, 0], jnp.int32)):
        got = f(q, k, v, table, lengths)
        np.testing.assert_allclose(
            np.asarray(got[0] if stats else got), np.asarray(want),
            atol=1e-5, rtol=1e-5)


def _attend_programs(fn, *args):
    """The grid of every pallas_call in ``fn``'s jaxpr, in order."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(tuple(eqn.params["grid_mapping"].grid))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("fused", [False, True])
def test_programs_do_not_follow_the_table_width(fused):
    """The structural half of ISSUE 30: at fixed lengths the attend's
    `pallas_call` has the same number of programs for a 4-column and a
    32-column table (one a slot and head block; the page walk is the
    program's own loop), and at the default geometry that is one a
    slot where the heads fit VMEM."""
    rs = np.random.RandomState(5)
    P, hkv, page, d, b = 6, 4, 128, 64, 3
    k, v = _pool(rs, P, hkv, page, d)
    q = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    row = jnp.asarray(rs.randn(b, hkv, d), jnp.float32)
    lengths = jnp.asarray([130, 0, 77], jnp.int32)
    grids = {}
    for columns in (4, 32):
        table = jnp.asarray(_live_table(rs, b, columns, 2, P))
        if fused:
            grids[columns] = _attend_programs(
                paged_append_attend, q, k, v, row, row, table,
                table[:, 0], lengths)
        else:
            grids[columns] = _attend_programs(
                paged_decode_attention, q, k, v, table, lengths)
    assert grids[4] == grids[32]
    # write launch (fused only) and attend: one program a slot each
    assert grids[4] == [(b,)] * (2 if fused else 1)


@pytest.mark.parametrize("budget_mb,want", [("16", 16), ("1.5", 4),
                                            ("0.6", 1)])
def test_default_head_block_follows_the_vmem_budget(monkeypatch,
                                                    budget_mb, want):
    """The default head block is the largest divisor of Hkv whose
    blocks fit `kernelmodel.vmem_budget_bytes()`: all 16 heads at GPT-3
    XL's shapes under the 16 MiB default, fewer under a smaller budget,
    one at the floor (never a refusal)."""
    from paddle_tpu.ops.pallas.paged_attention import (_head_block,
                                                       _vmem_bytes)
    from paddle_tpu.analysis import kernelmodel as km
    monkeypatch.setenv("PT_VMEM_BUDGET_MB", budget_mb)
    assert _head_block(None, 128, 16, 128, jnp.bfloat16, 1) == want
    if want > 1:
        assert _vmem_bytes(want, 128, 128, jnp.bfloat16,
                           1) <= km.vmem_budget_bytes()
    # an explicit head block is the caller's, clamped to a divisor
    assert _head_block(6, 128, 16, 128, jnp.bfloat16, 1) == 4


@pytest.mark.parametrize("rem", [15, 16, 17, 100])
def test_append_attend_bf16_pool_tile_offsets(rem):
    """The write launch moves the sublane tile that holds the fresh row
    (16 rows of a bf16 pool), not the page: offsets at a tile's last
    row, its first, its second and mid-page, pools bit for bit against
    the scatter, one row changed."""
    rs = np.random.RandomState(40 + rem)
    P, hkv, page, d, b = 5, 4, 128, 64, 2
    k, v = _pool(rs, P, hkv, page, d, jnp.bfloat16)
    q = jnp.asarray(rs.randn(b, hkv, d), jnp.bfloat16)
    table = jnp.asarray([[0, 3], [2, 1]], jnp.int32)
    lengths = jnp.asarray([page + rem, rem], jnp.int32)
    k_row = jnp.asarray(rs.randn(b, hkv, d), jnp.bfloat16)
    v_row = jnp.asarray(rs.randn(b, hkv, d), jnp.bfloat16)
    wpids = jnp.asarray([3, 2], jnp.int32)
    o, k_out, v_out = jax.jit(paged_append_attend)(
        q, k, v, k_row, v_row, table, wpids, lengths)
    k2, v2 = _scatter_oracle(k, v, k_row, v_row, table, wpids, lengths,
                             page)
    for got, want, was in ((k_out, k2, k), (v_out, v2, v)):
        got, want, was = (np.asarray(a.astype(jnp.float32))
                          for a in (got, want, was))
        np.testing.assert_array_equal(got, want)
        assert np.flatnonzero(np.any(got != was,
                                     axis=(0, 1, 3))).tolist() == [rem]
    want = paged_decode_attention_reference(q, k2, v2, table, lengths + 1)
    np.testing.assert_allclose(np.asarray(o.astype(jnp.float32)),
                               np.asarray(want.astype(jnp.float32)),
                               atol=2e-2, rtol=2e-2)
