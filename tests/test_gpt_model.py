"""Flagship GPT model family tests (SURVEY §4 OpTest idea: one numpy/dense
oracle, checked across execution modes — here dense vs pipelined-SPMD)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
import paddle_tpu.distributed as dist
from paddle_tpu import optimizer as optim
from paddle_tpu.models import gpt


def _tiny(**kw):
    d = dict(vocab_size=64, max_seq_len=16, d_model=32, n_layers=4,
             n_heads=2, dtype=jnp.float32)
    d.update(kw)
    return gpt.GPTConfig(**d)


def _tokens(cfg, b=4, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, cfg.vocab_size, (b, cfg.max_seq_len)), jnp.int32)


class TestForward:
    def test_logits_shape(self):
        cfg = _tiny()
        model = gpt.GPT(cfg, seed=0)
        logits = model(_tokens(cfg))
        assert logits.shape == (4, cfg.max_seq_len, cfg.vocab_size)

    def test_loss_near_uniform_at_init(self):
        cfg = _tiny()
        model = gpt.GPT(cfg, seed=0)
        loss = gpt.lm_loss(model(_tokens(cfg)), _tokens(cfg))
        assert abs(float(loss) - np.log(cfg.vocab_size)) < 0.5

    def test_remat_matches_plain(self):
        cfg = _tiny()
        toks = _tokens(cfg)
        out_plain = gpt.GPT(cfg, seed=0)(toks)
        out_remat = gpt.GPT(_tiny(remat=True), seed=0)(toks)
        np.testing.assert_allclose(np.asarray(out_plain),
                                   np.asarray(out_remat), rtol=1e-5)

    def test_param_count_formula(self):
        cfg = _tiny()
        model = gpt.GPT(cfg, seed=0)
        params, _ = model.split_params()
        total = sum(int(np.prod(v.shape)) for v in params.values())
        assert total == cfg.num_params()


class TestTrainStep:
    def test_loss_decreases(self):
        cfg = _tiny(n_layers=2)
        model = gpt.GPT(cfg, seed=0)
        opt = optim.AdamW(learning_rate=1e-3)
        params, opt_state = gpt.init_train_state(model, opt)
        step = gpt.build_train_step(model, opt)
        toks = _tokens(cfg)
        rng = jax.random.PRNGKey(0)
        losses = []
        for i in range(8):
            params, opt_state, loss = step(params, opt_state, toks, rng)
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.3, losses

    def test_scopes_name_the_compiled_operations(self):
        """The step's two halves are named in the operations' metadata,
        which is where a profile reads them: every matrix product of the
        forward and backward passes under ``train/loss_and_grad``, the
        update under ``train/optimizer``."""
        cfg = _tiny(n_layers=2)
        model = gpt.GPT(cfg, seed=0)
        opt = optim.AdamW(learning_rate=1e-3)
        params, opt_state = gpt.init_train_state(model, opt)
        step = gpt.build_train_step(model, opt, donate=False)
        hlo = step.lower(params, opt_state, _tokens(cfg),
                         jax.random.PRNGKey(0)).compile().as_text()
        named = [ln for ln in hlo.splitlines() if "op_name=" in ln]
        dots = [ln for ln in named if " dot(" in ln or "convolution(" in ln]
        assert dots and all("train/loss_and_grad" in ln for ln in dots)
        assert any("train/optimizer" in ln for ln in named)
        assert not any("train/optimizer" in ln
                       and "train/loss_and_grad" in ln for ln in named)


class TestPipeline:
    def test_stack_unstack_roundtrip(self):
        cfg = _tiny(n_layers=4)
        model = gpt.GPT(cfg, seed=0)
        stacked = gpt.stack_blocks(model, 2)
        blocks = gpt.unstack_blocks(stacked, 4)
        orig = model.blocks[1]
        np.testing.assert_array_equal(np.asarray(blocks[1].wqkv),
                                      np.asarray(orig.wqkv))

    def test_pipelined_matches_dense(self, mesh8):
        """GPipe-in-SPMD output == plain layer loop (same weights)."""
        # mesh8: dp=2, tp=2, fsdp=2 — reinit with pp for this test
        topo = dist.init_mesh(pp=2, dp=2, tp=2)
        cfg = _tiny(n_layers=4)
        model = gpt.GPT(cfg, seed=0)
        n_micro, mb = 4, 2
        toks = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)

        # dense oracle
        dense = jax.vmap(lambda t: model(t))(toks)

        x = model.embed(toks.reshape(n_micro * mb, cfg.max_seq_len))
        x = x.reshape(n_micro, mb, cfg.max_seq_len, -1)
        stacked = gpt.stack_blocks(model, 2)
        y = gpt.pipelined_apply(stacked, x, 2)
        piped = model.head(
            y.reshape(n_micro * mb, cfg.max_seq_len, -1)).reshape(
            dense.shape)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                                   rtol=2e-4, atol=2e-4)

    def test_pipelined_train_step_runs(self):
        topo = dist.init_mesh(pp=2, dp=2, fsdp=2)
        cfg = _tiny(n_layers=4)
        model = gpt.GPT(cfg, seed=0)
        opt = optim.AdamW(learning_rate=1e-3)
        emb_p, stacked, opt_state = gpt.init_pipelined_state(
            model, opt, topo.mesh, 2)
        step = gpt.build_pipelined_train_step(model, opt, topo.mesh, 2, 4)
        toks = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (4, 2, cfg.max_seq_len)), jnp.int32)
        rng = jax.random.PRNGKey(0)
        l0 = None
        for i in range(4):
            emb_p, stacked, opt_state, loss = step(emb_p, stacked,
                                                   opt_state, toks, rng)
            if i == 0:
                l0 = float(loss)
        assert float(loss) < l0, (float(loss), l0)
        assert np.isfinite(float(loss))


class TestPartitionRules:
    def test_specs(self):
        from jax.sharding import PartitionSpec as P
        assert gpt.partition_spec("blocks.item_0.wqkv") == P("fsdp", "tp")
        assert gpt.partition_spec("blocks.item_3.wo") == P("tp", "fsdp")
        assert gpt.partition_spec("wte") == P("tp", "fsdp")
        assert gpt.partition_spec("lnf_scale") == P(None)

    def test_pipeline_spec(self):
        from jax.sharding import PartitionSpec as P
        assert gpt.pipeline_partition_spec("wqkv") == \
            P("pp", None, "fsdp", "tp")


class TestShardedTrainStep:
    def test_tp_fsdp_matches_single(self):
        """Same seed/data: sharded GSPMD step == single-device step."""
        cfg = _tiny(n_layers=2)
        model = gpt.GPT(cfg, seed=0)
        opt = optim.AdamW(learning_rate=1e-3)
        toks = _tokens(cfg)
        rng = jax.random.PRNGKey(0)

        params1, st1 = gpt.init_train_state(model, opt)
        step1 = gpt.build_train_step(model, opt)
        _, _, loss_single = step1(params1, st1, toks, rng)

        topo = dist.init_mesh(dp=2, tp=2, fsdp=2)
        params2, st2 = gpt.init_train_state(model, opt, topo.mesh)
        step2 = gpt.build_train_step(model, opt, topo.mesh)
        _, _, loss_sharded = step2(params2, st2, toks, rng)
        np.testing.assert_allclose(float(loss_single),
                                   float(loss_sharded), rtol=1e-5)


def test_pipelined_remat_stages_matches_no_remat():
    """remat_stages changes memory, not math: identical loss trajectory."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as optim
    from paddle_tpu.models import gpt

    topo = dist.init_mesh(pp=2, dp=4)
    cfg = gpt.gpt_tiny(max_seq_len=16, n_layers=4, dtype=jnp.float32)
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 4, 16)), jnp.int32)
    rng = jax.random.PRNGKey(0)

    losses = {}
    for remat in (False, True):
        model = gpt.GPT(cfg, seed=0)
        opt = optim.AdamW(learning_rate=1e-3)
        emb_p, stacked, st = gpt.init_pipelined_state(model, opt,
                                                      topo.mesh, 2)
        step = gpt.build_pipelined_train_step(model, opt, topo.mesh, 2, 4,
                                              remat_stages=remat)
        for i in range(2):
            emb_p, stacked, st, loss = step(emb_p, stacked, st, tokens,
                                            jax.random.fold_in(rng, i))
        losses[remat] = float(loss)
    np.testing.assert_allclose(losses[False], losses[True], rtol=1e-6)


def test_pipelined_uneven_stages_matches_dense():
    """L % n_stages != 0 → padded slots masked off; output must still equal
    the dense layer loop (VERDICT r1 item 9: uneven stage support)."""
    import paddle_tpu.distributed as dist
    topo = dist.init_mesh(pp=2, dp=2, tp=2)
    cfg = _tiny(n_layers=5)
    model = gpt.GPT(cfg, seed=0)
    n_micro, mb = 4, 2
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)
    dense = jax.vmap(lambda t: model(t))(toks)

    x = model.embed(toks.reshape(n_micro * mb, cfg.max_seq_len))
    x = x.reshape(n_micro, mb, cfg.max_seq_len, -1)
    stacked, mask = gpt.stack_blocks_uneven(model, 2)
    assert mask is not None and mask.shape == (2, 3)
    y = gpt.pipelined_apply(stacked, x, 2, layer_mask=mask)
    piped = model.head(
        y.reshape(n_micro * mb, cfg.max_seq_len, -1)).reshape(dense.shape)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                               rtol=2e-4, atol=2e-4)
    # stack_blocks (even-only API) must refuse
    with pytest.raises(ValueError, match="not divisible"):
        gpt.stack_blocks(model, 2)


def test_moe_pipeline_trains():
    """MoE×PP lifted restriction (VERDICT r1 item 5): all-MoE stack over
    pp×ep×dp trains with finite loss and the aux loss reaches the total."""
    import paddle_tpu.distributed as dist
    topo = dist.init_mesh(pp=2, ep=2, dp=2)
    cfg = _tiny(n_layers=4, moe_experts=4, moe_every=1)
    model = gpt.GPT(cfg, seed=0)
    opt = optim.AdamW(learning_rate=1e-3)
    n_micro, mb = 4, 2
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)
    emb_p, stacked, opt_state = gpt.init_pipelined_state(
        model, opt, topo.mesh, 2)
    step = gpt.build_pipelined_train_step(model, opt, topo.mesh, 2, n_micro)
    rng = jax.random.PRNGKey(0)
    losses = []
    for _ in range(3):
        emb_p, stacked, opt_state, loss = step(emb_p, stacked, opt_state,
                                               toks, rng)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], losses


def test_moe_mixed_stack_rejected():
    cfg = _tiny(n_layers=4, moe_experts=2, moe_every=2)  # alternating
    model = gpt.GPT(cfg, seed=0)
    with pytest.raises(ValueError, match="homogeneous"):
        gpt.stack_blocks_uneven(model, 2)


def test_pipeline_moe_aux_masked_in_bubble():
    """The accumulated aux must equal the per-microbatch dense aux sum —
    i.e. bubble rows contribute nothing."""
    import paddle_tpu.distributed as dist
    dist.mesh.set_topology(None)
    cfg = _tiny(n_layers=2, moe_experts=2, moe_every=1)
    model = gpt.GPT(cfg, seed=0)
    n_micro, mb = 3, 2
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)
    x = model.embed(toks.reshape(n_micro * mb, cfg.max_seq_len))
    x = x.reshape(n_micro, mb, cfg.max_seq_len, -1)
    stacked, _ = gpt.stack_blocks_uneven(model, 2)
    y, aux = gpt.pipelined_apply(stacked, x, 2, collect_aux=True)
    # dense oracle: sum of per-microbatch aux
    ref = 0.0
    for i in range(n_micro):
        _, a = model(toks[i], return_aux=True)
        ref += float(a)
    np.testing.assert_allclose(float(aux), ref, rtol=1e-4)


def test_pipeline_skip_dead_rows_parity():
    """Dead-row skip (lax.cond per stage row; VERDICT r2 item 9) must be
    bit-compatible with the vmapped SPMD schedule, for values AND grads."""
    cfg = _tiny(n_layers=4)
    model = gpt.GPT(cfg, seed=0)
    n_micro, mb = 3, 2
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)
    x = model.embed(toks.reshape(n_micro * mb, cfg.max_seq_len))
    x = x.reshape(n_micro, mb, cfg.max_seq_len, -1)
    stacked = gpt.stack_blocks(model, 2)

    y_skip = gpt.pipelined_apply(stacked, x, 2, skip_dead_rows=True)
    y_vmap = gpt.pipelined_apply(stacked, x, 2, skip_dead_rows=False)
    np.testing.assert_allclose(np.asarray(y_skip), np.asarray(y_vmap),
                               rtol=1e-5, atol=1e-5)

    def loss(stacked, skip):
        return jnp.sum(gpt.pipelined_apply(stacked, x, 2,
                                           skip_dead_rows=skip) ** 2)

    g_skip = jax.grad(lambda s: loss(s, True))(stacked)
    g_vmap = jax.grad(lambda s: loss(s, False))(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_skip),
                    jax.tree_util.tree_leaves(g_vmap)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Interleaved (virtual-stage) pipeline (VERDICT r3 item 6;
# ≙ PipelineParallelWithInterleave, pipeline_parallel.py:457)
# ---------------------------------------------------------------------------

def test_interleaved_stacking_covers_all_layers():
    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=16, d_model=32,
                        n_layers=8, n_heads=2, dtype=jnp.float32)
    model = gpt.GPT(cfg, seed=0)
    stacked, mask = gpt.stack_blocks_interleaved(model, 2, 2)
    leaf = jax.tree_util.tree_leaves(stacked)[0]
    assert leaf.shape[:3] == (2, 2, 2)  # (V, S, layers_per_global_stage)
    assert mask is None  # 8 layers / 4 global stages divide evenly
    # chunk (v, r) holds global stage v*S+r's layers: check weight identity
    w0 = dict(model.blocks[0].named_parameters())["wqkv"]
    got = getattr(stacked, "wqkv")[0, 0, 0]
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(got))
    w_last = dict(model.blocks[7].named_parameters())["wqkv"]
    got_last = getattr(stacked, "wqkv")[1, 1, 1]
    np.testing.assert_array_equal(np.asarray(w_last), np.asarray(got_last))


def test_interleaved_matches_dense(mesh8):
    """vpp=2 output == dense layer loop (same weights), even + uneven."""
    topo = dist.init_mesh(pp=2, dp=2, tp=2)
    for n_layers in (8, 6):  # 6 over 4 global stages → uneven, masked
        cfg = _tiny(n_layers=n_layers)
        model = gpt.GPT(cfg, seed=0)
        n_micro, mb = 4, 2
        toks = jnp.asarray(np.random.RandomState(0).randint(
            0, cfg.vocab_size, (n_micro, mb, cfg.max_seq_len)), jnp.int32)
        dense = jax.vmap(lambda t: model(t))(toks)
        x = model.embed(toks.reshape(n_micro * mb, cfg.max_seq_len))
        x = x.reshape(n_micro, mb, cfg.max_seq_len, -1)
        stacked, mask = gpt.stack_blocks_interleaved(model, 2, 2)
        y = gpt.pipelined_apply_interleaved(stacked, x, 2, 2,
                                            layer_mask=mask)
        piped = model.head(
            y.reshape(n_micro * mb, cfg.max_seq_len, -1)).reshape(
            dense.shape)
        np.testing.assert_allclose(np.asarray(dense), np.asarray(piped),
                                   rtol=2e-4, atol=2e-4)


def test_interleaved_train_step_runs(mesh8):
    topo = dist.init_mesh(pp=2, tp=2, fsdp=2)
    cfg = _tiny(n_layers=8)
    model = gpt.GPT(cfg, seed=0)
    from paddle_tpu import optimizer as optim
    opt = optim.AdamW(learning_rate=1e-3)
    emb_p, stacked, opt_state = gpt.init_pipelined_state(
        model, opt, topo.mesh, 2, n_virtual=2)
    leaf = jax.tree_util.tree_leaves(stacked)[0]
    assert leaf.shape[0] == 2 and leaf.shape[1] == 2
    step = gpt.build_pipelined_train_step(model, opt, topo.mesh, 2, 4,
                                          n_virtual=2)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 2, cfg.max_seq_len)), jnp.int32)
    emb_p, stacked, opt_state, loss = step(emb_p, stacked, opt_state, toks,
                                           jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))


def test_interleaved_grads_match_dense(mesh8):
    """Gradients through the virtual-stage schedule equal the dense-loop
    gradients for the same loss (the adjoint of the interleaved roll)."""
    topo = dist.init_mesh(pp=2, dp=4)
    cfg = _tiny(n_layers=4)
    model = gpt.GPT(cfg, seed=0)
    n_micro, mb = 4, 2
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(n_micro, mb, cfg.max_seq_len, cfg.d_model),
                    jnp.float32)
    stacked, _ = gpt.stack_blocks_interleaved(model, 2, 2)

    def loss_vpp(blocks):
        y = gpt.pipelined_apply_interleaved(blocks, x, 2, 2)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    def loss_dense(blocks):
        h = x.reshape(n_micro * mb, cfg.max_seq_len, -1)
        flat = jax.tree_util.tree_map(
            lambda a: a.reshape((4,) + a.shape[3:]), blocks)

        def body(hh, blk):
            return blk(hh), None
        h, _ = jax.lax.scan(body, h, flat)
        return jnp.sum(h.astype(jnp.float32) ** 2)

    g_vpp = jax.grad(loss_vpp)(stacked)
    g_dense = jax.grad(loss_dense)(stacked)
    for a, b in zip(jax.tree_util.tree_leaves(g_vpp),
                    jax.tree_util.tree_leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_interleaved_moe_pipeline_trains(mesh8):
    """MoE stack through the virtual-stage pipeline: aux loss collected
    across chunks, bubble rows contribute zero, step trains finite."""
    topo = dist.init_mesh(pp=2, ep=2, dp=2)
    cfg = gpt.GPTConfig(vocab_size=64, max_seq_len=16, d_model=32,
                        n_layers=4, n_heads=2, dtype=jnp.float32,
                        moe_experts=2, moe_every=1)
    model = gpt.GPT(cfg, seed=0)
    from paddle_tpu import optimizer as optim
    opt = optim.AdamW(learning_rate=1e-3)
    emb_p, stacked, opt_state = gpt.init_pipelined_state(
        model, opt, topo.mesh, 2, n_virtual=2)
    step = gpt.build_pipelined_train_step(model, opt, topo.mesh, 2, 4,
                                          n_virtual=2)
    toks = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 2, cfg.max_seq_len)), jnp.int32)
    emb_p, stacked, opt_state, loss = step(emb_p, stacked, opt_state, toks,
                                           jax.random.PRNGKey(0))
    assert np.isfinite(float(loss))

    # aux parity vs the dense (unpipelined) MoE aux on identical inputs
    x = model.embed(toks.reshape(8, cfg.max_seq_len)).reshape(
        4, 2, cfg.max_seq_len, -1)
    stacked_v, mask = gpt.stack_blocks_interleaved(model, 2, 2)
    y, aux_vpp = gpt.pipelined_apply_interleaved(
        stacked_v, x, 2, 2, layer_mask=mask, collect_aux=True)
    stacked_p, mask_p = gpt.stack_blocks_uneven(model, 2)
    y_p, aux_p = gpt.pipelined_apply(stacked_p, x, 2, layer_mask=mask_p,
                                     collect_aux=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_p),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(aux_vpp), float(aux_p),
                               rtol=1e-4, atol=1e-5)


def test_scan_layers_matches_unrolled_loop():
    """The default lax.scan layer loop and the scan_layers=False
    unrolled escape hatch must train identically — including remat and
    per-layer dropout rng (fold_in by layer index in both paths)."""
    from paddle_tpu import flags, optimizer as optim

    for remat, dropout in ((False, 0.0), (True, 0.0), (False, 0.1)):
        cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=16, d_model=32,
                            n_layers=3, n_heads=2, dtype=jnp.float32,
                            remat=remat, dropout=dropout)
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, 128, (2, 16)), jnp.int32)
        losses = {}
        for scan in (True, False):
            flags.set_flags({"scan_layers": scan})
            try:
                model = gpt.GPT(cfg, seed=0)
                opt = optim.AdamW(learning_rate=1e-3)
                params, opt_state = gpt.init_train_state(model, opt)
                step = gpt.build_train_step(model, opt)
                ls = []
                for i in range(3):
                    params, opt_state, loss = step(
                        params, opt_state, toks, jax.random.PRNGKey(i))
                    ls.append(float(loss))
                losses[scan] = ls
            finally:
                flags.set_flags({"scan_layers": True})
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-6, atol=1e-6)


def test_stacked_train_state_matches_plain():
    """init_train_state(stacked=True) pre-stacks block weights so the
    scan consumes the state with no in-trace stack (the in-program copy
    + its grad-unstack transpose is what pushed the 1.3B step past 16GB
    HBM on hardware). Training must be numerically identical to the
    plain per-layer state, including remat and per-layer dropout rng."""
    from paddle_tpu import optimizer as optim

    for remat, dropout in ((False, 0.0), (True, 0.1)):
        cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=16, d_model=32,
                            n_layers=3, n_heads=2, dtype=jnp.float32,
                            remat=remat, dropout=dropout)
        toks = jnp.asarray(
            np.random.RandomState(1).randint(0, 128, (2, 16)), jnp.int32)
        model = gpt.GPT(cfg, seed=0)
        losses = {}
        for stacked in (False, True):
            opt = optim.AdamW(learning_rate=1e-3, weight_decay=0.01)
            params, opt_state = gpt.init_train_state(model, opt,
                                                     stacked=stacked)
            assert ("_stacked_blocks" in params) == stacked
            step = gpt.build_train_step(model, opt)
            ls = []
            for i in range(3):
                params, opt_state, loss = step(
                    params, opt_state, toks, jax.random.PRNGKey(i))
                ls.append(float(loss))
            losses[stacked] = ls
        np.testing.assert_allclose(losses[True], losses[False],
                                   rtol=1e-6, atol=1e-6)

    # merge_params on a stacked state must leave NO stale per-layer
    # weights: the decode path reads self.blocks, not the scan stack
    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=16, d_model=32,
                        n_layers=3, n_heads=2, dtype=jnp.float32)
    toks = jnp.asarray(
        np.random.RandomState(2).randint(0, 128, (2, 16)), jnp.int32)
    model = gpt.GPT(cfg, seed=0)
    merged = {}
    for stacked in (False, True):
        opt = optim.AdamW(learning_rate=1e-2)
        params, opt_state = gpt.init_train_state(model, opt,
                                                 stacked=stacked)
        step = gpt.build_train_step(model, opt)
        params, opt_state, _ = step(params, opt_state, toks,
                                    jax.random.PRNGKey(0))
        merged[stacked] = model.merge_params(params)
    out_p = gpt.generate(merged[False], toks[:, :4], max_new_tokens=6,
                         max_len=16)
    out_s = gpt.generate(merged[True], toks[:, :4], max_new_tokens=6,
                         max_len=16)
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_s))

    # guardrail: MoE stacks are heterogeneous and refuse the layout
    moe_cfg = gpt.GPTConfig(vocab_size=64, max_seq_len=8, d_model=16,
                            n_layers=2, n_heads=2, dtype=jnp.float32,
                            moe_experts=2)
    with pytest.raises(ValueError, match="dense"):
        gpt.init_train_state(gpt.GPT(moe_cfg, seed=0), optim.AdamW(),
                             stacked=True)
    # apply_decay_param_fun no longer refuses: the mask is resolved
    # against the block template and broadcast along the layer axis
    # (parity-tested in tests/test_sharded_stacked.py)
    opt = optim.AdamW(apply_decay_param_fun=lambda n: True)
    params, _ = gpt.init_train_state(model, opt, stacked=True)
    assert "_stacked_blocks" in opt._decay_masks
