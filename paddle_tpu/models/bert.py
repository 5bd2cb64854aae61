"""BERT encoder family (≙ the reference's ERNIE/BERT stack served from
PaddleNLP on top of fleet; BASELINE.md row "ERNIE-3.0 / BERT-base finetune").

TPU-first shape: one fused-QKV post-LN encoder block (large MXU matmuls,
bf16 by default), sharding-annotated for the same dp/fsdp/tp mesh axes as
models.gpt — Megatron column/row TP falls out of PARTITION_RULES + GSPMD
rather than wrapper layers (ref contrast: mp_layers.py ColumnParallelLinear).
"""

import dataclasses
import math
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.distributed.mesh import LAYOUT, mesh_safe_spec
from paddle_tpu.nn.module import Module, Parameter, LayerList
from paddle_tpu.nn import functional as F


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30528          # padded to a multiple of 64 for MXU
    max_position: int = 512
    type_vocab_size: int = 2
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_mult: int = 4
    dropout: float = 0.1
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def d_ffn(self):
        return self.d_model * self.ffn_mult

    def num_params(self, non_embedding: bool = False) -> int:
        d = self.d_model
        per_layer = 4 * d * d + 2 * d * self.d_ffn + 9 * d + self.d_ffn
        n = self.n_layers * per_layer + 2 * d
        if not non_embedding:
            n += (self.vocab_size + self.max_position
                  + self.type_vocab_size) * d
        return n

    def flops_per_token(self) -> float:
        """fwd+bwd model FLOPs per token (6N + attention term)."""
        n = self.num_params(non_embedding=True)
        attn = 12 * self.n_layers * self.d_model * self.max_position
        return 6 * n + attn


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape)).astype(dtype)


class BertLayer(Module):
    """Post-LN encoder block (original BERT residual order)."""

    def __init__(self, cfg: BertConfig, key):
        super().__init__()
        d = cfg.d_model
        self.n_heads = cfg.n_heads
        self.head_dim = cfg.head_dim
        self.dropout = cfg.dropout
        ks = jax.random.split(key, 4)
        std = 0.02
        dt = cfg.dtype
        self.wqkv = Parameter(_normal(ks[0], (d, 3 * d), std, dt))
        self.bqkv = Parameter(jnp.zeros((3 * d,), dt))
        self.wo = Parameter(_normal(ks[1], (d, d), std, dt))
        self.bo = Parameter(jnp.zeros((d,), dt))
        self.wup = Parameter(_normal(ks[2], (d, cfg.d_ffn), std, dt))
        self.bup = Parameter(jnp.zeros((cfg.d_ffn,), dt))
        self.wdown = Parameter(_normal(ks[3], (cfg.d_ffn, d), std, dt))
        self.bdown = Parameter(jnp.zeros((d,), dt))
        self.ln1_scale = Parameter(jnp.ones((d,), jnp.float32))
        self.ln1_bias = Parameter(jnp.zeros((d,), jnp.float32))
        self.ln2_scale = Parameter(jnp.ones((d,), jnp.float32))
        self.ln2_bias = Parameter(jnp.zeros((d,), jnp.float32))

    def _ln(self, x, scale, bias):
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        return ((x32 - mu) * lax.rsqrt(var + 1e-12) * scale
                + bias).astype(x.dtype)

    def forward(self, x, attn_bias=None, rng_key=None, kv_lens=None):
        b, s, d = x.shape
        qkv = x @ self.wqkv + self.bqkv
        qkv = qkv.reshape(b, s, 3, self.n_heads, self.head_dim)
        qkv = _shard_act(qkv, P(("dp", "fsdp"), None, None, "tp", None))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        # kv_lens routes the Pallas flash kernel (contiguous padding mask);
        # attn_bias covers the XLA fallback path
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_bias, is_causal=False, dropout_p=0.0,
            kv_lens=kv_lens)
        attn = attn.reshape(b, s, d) @ self.wo + self.bo
        attn = _maybe_dropout(attn, self.dropout, rng_key, 1)
        x = self._ln(x + attn, self.ln1_scale, self.ln1_bias)
        h = jax.nn.gelu(x @ self.wup + self.bup)
        h = _shard_act(h, P(("dp", "fsdp"), None, "tp"))
        h = h @ self.wdown + self.bdown
        h = _maybe_dropout(h, self.dropout, rng_key, 2)
        x = self._ln(x + h, self.ln2_scale, self.ln2_bias)
        return _shard_act(x, P(("dp", "fsdp"), None, None))


def _maybe_dropout(x, p, key, salt):
    if p == 0.0 or key is None:
        return x
    k = jax.random.fold_in(key, salt)
    keep = jax.random.bernoulli(k, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


def _shard_act(x, spec: P):
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    try:
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except Exception:
        return x


class Bert(Module):
    """Encoder trunk: embeddings → L layers → (sequence_output, pooled)."""

    def __init__(self, cfg: BertConfig, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        key = jax.random.PRNGKey(seed)
        kw, kp, kt, kpool, kl = jax.random.split(key, 5)
        d, dt = cfg.d_model, cfg.dtype
        self.wte = Parameter(_normal(kw, (cfg.vocab_size, d), 0.02, dt))
        self.wpe = Parameter(_normal(kp, (cfg.max_position, d), 0.02, dt))
        self.wtype = Parameter(_normal(kt, (cfg.type_vocab_size, d),
                                       0.02, dt))
        self.emb_ln_scale = Parameter(jnp.ones((d,), jnp.float32))
        self.emb_ln_bias = Parameter(jnp.zeros((d,), jnp.float32))
        self.layers = LayerList([
            BertLayer(cfg, jax.random.fold_in(kl, i))
            for i in range(cfg.n_layers)])
        self.pooler_w = Parameter(_normal(kpool, (d, d), 0.02, dt))
        self.pooler_b = Parameter(jnp.zeros((d,), dt))

    def forward(self, tokens, token_type_ids=None, attention_mask=None,
                rng_key=None, extra_embed=None):
        """``extra_embed``: optional additive embedding plane folded in
        BEFORE the embedding LayerNorm (the ERNIE task-type embedding
        rides this hook — models/ernie.py)."""
        b, s = tokens.shape
        x = jnp.take(self.wte, tokens, axis=0) + self.wpe[:s]
        if token_type_ids is not None:
            x = x + jnp.take(self.wtype, token_type_ids, axis=0)
        else:
            x = x + self.wtype[0]
        if extra_embed is not None:
            x = x + extra_embed
        x32 = x.astype(jnp.float32)
        mu = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        x = ((x32 - mu) * lax.rsqrt(var + 1e-12) * self.emb_ln_scale
             + self.emb_ln_bias).astype(x.dtype)
        x = _shard_act(x, P(("dp", "fsdp"), None, None))
        attn_bias = None
        kv_lens = None
        if attention_mask is not None:
            # (B, S) 1=keep → additive bias (B, 1, 1, S) broadcast over
            # heads and query positions (finite fill: fully-masked rows
            # must produce zeros, not NaN softmax)
            keep = attention_mask.astype(bool)
            attn_bias = jnp.where(
                keep[:, None, None, :], 0.0, -1e30).astype(jnp.float32)
            # per-example KV length lets the Pallas kernel SKIP blocks
            # beyond the padding (fused_softmax_mask.cu.h analog). Only a
            # contiguous valid prefix may declare a length; rows with
            # interior holes (packed sequences) keep the full length and
            # rely on the bias alone — checked per example, in-trace.
            lens = jnp.sum(keep.astype(jnp.int32), axis=-1)
            is_prefix = jnp.all(
                keep == (jnp.arange(s)[None, :] < lens[:, None]), axis=-1)
            kv_lens = jnp.where(is_prefix, lens, s)
        from paddle_tpu import flags as _flags
        prestacked = getattr(self, "_stacked_layers", None)
        use_scan = prestacked is not None or (
            self.cfg.n_layers > 1 and _flags.get_flag("scan_layers"))
        if use_scan:
            # one compiled encoder-layer body instead of L unrolled
            # copies (L-fold faster XLA compile — same rationale and
            # helper as the GPT stack). A state built by
            # init_train_state(stacked=True) carries the weights
            # pre-stacked, so the scan consumes them with zero in-trace
            # copy (the in-trace stack costs ~2x block-param HBM per
            # step: the stack forward plus its grad-unstack transpose)
            from paddle_tpu.models.gpt import (_shard_stacked,
                                               stack_block_weights)
            stacked = prestacked if prestacked is not None else \
                stack_block_weights(
                    [self.layers[i] for i in range(self.cfg.n_layers)])
            if prestacked is not None:
                from paddle_tpu.distributed.mesh import get_mesh
                mesh = get_mesh()
                if mesh is not None and mesh.size > 1:
                    # same rationale as GPT.hidden_states: constrain only
                    # the PRE-stacked state; in-trace stacks keep
                    # propagation-only sharding (established numerics)
                    stacked = _shard_stacked(stacked, self.layers[0],
                                             mesh, spec_fn=partition_spec)

            def body(h, lyr_i):
                lyr, i = lyr_i
                k = (jax.random.fold_in(rng_key, i)
                     if rng_key is not None else None)
                return lyr(h, attn_bias=attn_bias, rng_key=k,
                           kv_lens=kv_lens), None

            x, _ = jax.lax.scan(
                body, x, (stacked, jnp.arange(self.cfg.n_layers)))
        else:
            for i in range(self.cfg.n_layers):
                k = (jax.random.fold_in(rng_key, i)
                     if rng_key is not None else None)
                x = self.layers[i](x, attn_bias=attn_bias, rng_key=k,
                                   kv_lens=kv_lens)
        pooled = jnp.tanh(x[:, 0] @ self.pooler_w + self.pooler_b)
        return x, pooled

    def merge_params(self, params):
        new = Module.merge_params(self, params)
        _bind_stacked(new)
        return new


def _bind_stacked(trunk: "Bert"):
    """Rebind each per-layer module to a sliced view of the pre-stacked
    state (same contract as GPT.merge_params): consumers outside the scan
    forward (state_dict export, unrolled escape hatch) must never read
    the init-time weights still sitting in ``trunk.layers``. Inside jit
    the unconsumed slices are dead code XLA eliminates."""
    st = getattr(trunk, "_stacked_layers", None)
    if st is not None:
        for i in range(trunk.cfg.n_layers):
            lyr = jax.tree_util.tree_map(lambda x, i=i: x[i], st)
            object.__setattr__(trunk.layers, f"item_{i}", lyr)


class BertForPretraining(Module):
    """MLM + NSP heads (decoder tied to wte, ≙ BertPretrainingHeads)."""

    def __init__(self, cfg: BertConfig, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        self.bert = Bert(cfg, seed)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 999)
        d, dt = cfg.d_model, cfg.dtype
        k1, k2 = jax.random.split(key)
        self.mlm_transform_w = Parameter(_normal(k1, (d, d), 0.02, dt))
        self.mlm_transform_b = Parameter(jnp.zeros((d,), dt))
        self.mlm_ln_scale = Parameter(jnp.ones((d,), jnp.float32))
        self.mlm_ln_bias = Parameter(jnp.zeros((d,), jnp.float32))
        self.mlm_bias = Parameter(jnp.zeros((cfg.vocab_size,), jnp.float32))
        self.nsp_w = Parameter(_normal(k2, (d, 2), 0.02, dt))
        self.nsp_b = Parameter(jnp.zeros((2,), dt))

    def merge_params(self, params):
        new = Module.merge_params(self, params)
        # subclasses may re-home the trunk (Ernie moves it under
        # .ernie.bert); only a directly-attached Bert can carry the
        # pre-stacked state this head's init_train_state produces
        trunk = getattr(new, "bert", None)
        if isinstance(trunk, Bert):
            _bind_stacked(trunk)
        return new

    def mlm_head(self, h):
        """Transform + LN + tied vocab projection over (..., d) states."""
        h = jax.nn.gelu(h @ self.mlm_transform_w + self.mlm_transform_b)
        h32 = h.astype(jnp.float32)
        mu = jnp.mean(h32, -1, keepdims=True)
        var = jnp.var(h32, -1, keepdims=True)
        h = ((h32 - mu) * lax.rsqrt(var + 1e-12) * self.mlm_ln_scale
             + self.mlm_ln_bias).astype(h.dtype)
        return h @ self.bert.wte.T + self.mlm_bias

    def forward(self, tokens, token_type_ids=None, attention_mask=None,
                rng_key=None, mlm_positions=None):
        """mlm_positions (B, M): compute MLM logits only at those gathered
        positions — the standard pretraining optimization (the reference
        gathers masked positions before the vocab projection too; at 15%
        masking this removes ~85% of the vocab-head FLOPs). Returns
        (B, M, V) logits then; (B, S, V) when None."""
        seq, pooled = self.bert(tokens, token_type_ids, attention_mask,
                                rng_key)
        if mlm_positions is not None:
            seq = jnp.take_along_axis(seq, mlm_positions[..., None], axis=1)
        mlm_logits = self.mlm_head(seq)
        nsp_logits = pooled @ self.nsp_w + self.nsp_b
        return (_shard_act(mlm_logits, P(("dp", "fsdp"), None, "tp")),
                nsp_logits)


class BertForSequenceClassification(Module):
    """Finetune head (≙ ERNIE/BERT fine-tuning configs in BASELINE.md)."""

    def __init__(self, cfg: BertConfig, num_classes: int = 2, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        self.bert = Bert(cfg, seed)
        k = jax.random.fold_in(jax.random.PRNGKey(seed), 12345)
        self.cls_w = Parameter(_normal(k, (cfg.d_model, num_classes),
                                       0.02, cfg.dtype))
        self.cls_b = Parameter(jnp.zeros((num_classes,), cfg.dtype))

    def merge_params(self, params):
        new = Module.merge_params(self, params)
        # subclasses may re-home the trunk (Ernie moves it under
        # .ernie.bert); only a directly-attached Bert can carry the
        # pre-stacked state this head's init_train_state produces
        trunk = getattr(new, "bert", None)
        if isinstance(trunk, Bert):
            _bind_stacked(trunk)
        return new

    def forward(self, tokens, token_type_ids=None, attention_mask=None,
                rng_key=None):
        _, pooled = self.bert(tokens, token_type_ids, attention_mask,
                              rng_key)
        return pooled @ self.cls_w + self.cls_b


def mlm_loss(mlm_logits, labels, ignore_index: int = -100):
    """Masked-LM CE: positions with ignore_index contribute nothing.
    Dispatches to the vocab-parallel CE when the mesh tp-shards the vocab
    axis (same path as models.gpt.lm_loss)."""
    from paddle_tpu.models.gpt import _tp_sharded_vocab
    b, s, v = mlm_logits.shape
    if _tp_sharded_vocab(b, s, v):
        from paddle_tpu.distributed.mesh import get_mesh
        from paddle_tpu.distributed.mp_ops import parallel_cross_entropy
        tok = parallel_cross_entropy(mlm_logits, labels, mesh=get_mesh(),
                                     ignore_index=ignore_index)
        n = jnp.maximum(jnp.sum(labels != ignore_index), 1)
        return jnp.sum(tok) / n
    return F.cross_entropy(mlm_logits.astype(jnp.float32), labels,
                           ignore_index=ignore_index)


def pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels):
    loss = mlm_loss(mlm_logits, mlm_labels)
    nsp = F.cross_entropy(nsp_logits.astype(jnp.float32), nsp_labels)
    return loss + nsp


# Megatron TP × ZeRO-3 fsdp rules, spelled in the same SpecLayout
# vocabulary as models.gpt.PARTITION_RULES (distributed.mesh.LAYOUT)
PARTITION_RULES = (
    (r"wte$", LAYOUT.vocab_embedding()),
    (r"(wpe|wtype)$", LAYOUT.position_table()),
    (r"wqkv$", LAYOUT.column()),
    (r"bqkv$", LAYOUT.column_bias()),
    (r"wo$", LAYOUT.row()),
    (r"wup$", LAYOUT.column()),
    (r"bup$", LAYOUT.column_bias()),
    (r"wdown$", LAYOUT.row()),
    (r"mlm_transform_w$", LAYOUT.root_linear()),
    (r"mlm_bias$", LAYOUT.vocab_bias()),
    (r"(pooler_w|nsp_w|cls_w)$", LAYOUT.root_linear()),
    (r".*", LAYOUT.replicated()),
)


def partition_spec(path: str) -> P:
    for pat, spec in PARTITION_RULES:
        if re.search(pat, path):
            return spec
    return P()


def shard_params(params: Dict[str, jax.Array], mesh: Mesh):
    return {k: jax.device_put(
        jnp.copy(v), NamedSharding(mesh, partition_spec(k)))
        for k, v in params.items()}


def build_pretrain_step(model: BertForPretraining, optimizer,
                        mesh: Optional[Mesh] = None, donate: bool = True,
                        max_predictions: Optional[int] = None):
    """``max_predictions``: static per-example cap on MLM positions. When
    set, the step sorts masked positions first and computes the vocab head
    only on those M slots (slots beyond the actual masked count carry the
    ignore label and contribute nothing). Equal loss, ~85% fewer
    vocab-head FLOPs at 15% masking.

    With PT_NUMERICS_EVERY > 0 (ISSUE 18) the step returns a 4th
    output: the packed numerics vector — per-layer grad and
    param-update stats over the ``*_stacked_layers`` axis plus the NaN
    provenance header — at the configured cadence."""
    from paddle_tpu.observability import numerics as _nm
    num_on = _nm.enabled()
    num_box = _nm.LayoutBox()

    def step(params, opt_state, tokens, type_ids, attn_mask, mlm_labels,
             nsp_labels, rng):
        pos = labels = None
        if max_predictions is not None:
            masked_first = jnp.argsort(mlm_labels == -100, axis=1,
                                       stable=True)
            pos = masked_first[:, :max_predictions]
            labels = jnp.take_along_axis(mlm_labels, pos, axis=1)

        def loss_fn(p):
            m = model.merge_params(p)
            mlm_logits, nsp_logits = m(tokens, type_ids, attn_mask,
                                       rng_key=rng, mlm_positions=pos)
            return pretrain_loss(
                mlm_logits, nsp_logits,
                mlm_labels if labels is None else labels, nsp_labels)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = _nm.poison_grads(grads, step_count=opt_state["step"])
        new_params, new_state = optimizer.update(grads, opt_state, params)
        if num_on:
            updates = jax.tree_util.tree_map(
                lambda n, o: n - o, new_params, params)
            packed = _nm.capture_step(
                grads, loss=loss, updates=updates,
                step_count=opt_state["step"], box=num_box)
            return new_params, new_state, loss, packed
        return new_params, new_state, loss

    kw = {"donate_argnums": (0, 1)} if donate else {}
    fn = jax.jit(step, **kw)
    fn.numerics_layout = num_box
    return fn


def _trunk_of(model) -> (Bert, str):
    """(encoder trunk, its param-path prefix) for any BERT-family head."""
    if isinstance(model, Bert):
        return model, ""
    trunk = getattr(model, "bert", None)
    if not isinstance(trunk, Bert):
        raise ValueError(
            f"{type(model).__name__} does not expose a .bert trunk; the "
            "stacked layout supports Bert and the Bert* heads")
    return trunk, "bert."


def init_train_state(model, optimizer, mesh: Optional[Mesh] = None,
                     stacked: bool = False):
    """Params + optimizer state, sharded onto the mesh if given.

    ``stacked=True``: encoder layers enter the state PRE-stacked under a
    ``{prefix}_stacked_layers`` key the forward scan consumes directly —
    the previous in-trace ``stack_block_weights`` copied every layer
    weight inside the step, the exact cost the GPT path eliminated. Same
    SpecLayout-aware placement as GPT: under a multi-device mesh each
    stacked leaf is emitted sharded by its layer-leading PARTITION_RULES
    spec via the stacking jit's out_shardings."""
    from paddle_tpu.models.gpt import (register_stacked_decay_mask,
                                       stack_block_weights,
                                       stacked_block_specs)
    params, _ = model.split_params()
    if stacked:
        trunk, prefix = _trunk_of(model)
        L = trunk.cfg.n_layers
        entry = f"{prefix}_stacked_layers"
        params = {k: v for k, v in params.items()
                  if not k.startswith(f"{prefix}layers.")}
        layers = [trunk.layers[i] for i in range(L)]
        if getattr(optimizer, "apply_decay_param_fun", None) is not None:
            register_stacked_decay_mask(
                optimizer, trunk.layers[0], L,
                lambda i, name: f"{prefix}layers.item_{i}.{name}", entry)
        if mesh is not None and mesh.size > 1:
            params = shard_params(params, mesh)
            _, treedef, specs = stacked_block_specs(trunk.layers[0],
                                                    partition_spec)
            sh_tree = jax.tree_util.tree_unflatten(
                treedef, [NamedSharding(mesh, mesh_safe_spec(s, mesh))
                          for s in specs])
            params[entry] = jax.jit(
                stack_block_weights, out_shardings=sh_tree)(layers)
            opt_state = jax.jit(optimizer.init)(params)
        else:
            params = {k: jnp.copy(v) for k, v in params.items()}
            params[entry] = stack_block_weights(layers)
            opt_state = optimizer.init(params)
        return params, opt_state
    if mesh is not None and mesh.size > 1:
        params = shard_params(params, mesh)
        opt_state = jax.jit(optimizer.init)(params)
    else:
        params = {k: jnp.copy(v) for k, v in params.items()}
        opt_state = optimizer.init(params)
    return params, opt_state


def bert_tiny(**kw):
    d = dict(vocab_size=256, max_position=64, d_model=64, n_layers=2,
             n_heads=2, dropout=0.0, type_vocab_size=2, dtype=jnp.float32)
    d.update(kw)
    return BertConfig(**d)


def bert_base(**kw):
    d = dict(d_model=768, n_layers=12, n_heads=12)
    d.update(kw)
    return BertConfig(**d)


def bert_large(**kw):
    d = dict(d_model=1024, n_layers=24, n_heads=16)
    d.update(kw)
    return BertConfig(**d)
