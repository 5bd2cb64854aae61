"""GPT decoder-only LM — the flagship pretrain model family.

Reference analog: PaddleNLP gpt-3 trained with fleet hybrid parallel
(SURVEY §2.2, §3.4; BASELINE north-star "GPT-3 1.3B pretrain, DP×MP×PP").
The reference expresses parallelism as wrapper modules + NCCL ops
(mp_layers.py ColumnParallelLinear:173 / RowParallelLinear:327,
pipeline_parallel.py:117 1F1B); here the same strategies are sharding
annotations on one jitted program over a named mesh:

- TP  ≙ megatron Column/Row parallel: qkv/up weights sharded P('fsdp','tp'),
  out/down weights P('tp','fsdp'); XLA inserts the reduce-scatter/all-reduce
  the reference codes by hand (c_identity / mp_allreduce, mpu/mp_ops.py).
- FSDP ≙ sharding stage 3: every weight additionally sharded over 'fsdp';
  XLA all-gathers at use and reduce-scatters grads (ZeRO-3 semantics without
  the reference's gather/release hooks, group_sharded_stage3.py:59).
- SP: activation seq axis sharded over 'sp' (capability absent in the
  reference, SURVEY §5.7).
- PP ≙ GPipe/1F1B: see `pipelined_apply` — stage-stacked weights sharded
  P('pp') with a rolling activation buffer; XLA compiles the roll into a
  collective-permute ring over ICI. (ref contrast: FleetExecutor/interceptor
  runtime + send_v2/recv_v2 ops.)
"""

import collections
import dataclasses
import functools
import math
import re
import weakref
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu import nn
from paddle_tpu.distributed.mesh import LAYOUT, mesh_safe_spec
from paddle_tpu.incubate.moe import EXPERT_PARTITION_RULES
from paddle_tpu.nn.module import Module, Parameter, LayerList
from paddle_tpu.nn import functional as F


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304
    max_seq_len: int = 1024
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_mult: int = 4
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    use_bias: bool = True
    tie_embeddings: bool = True
    # remat ≙ reference recompute (fleet/recompute/recompute.py:386)
    remat: bool = False
    # MoE (≙ incubate MoE GPT): every `moe_every`-th block swaps its FFN for
    # an expert-parallel MoELayer; 0 experts = dense
    moe_experts: int = 0
    moe_every: int = 2
    moe_aux_weight: float = 0.01
    moe_gate: str = "gshard"
    # GQA/MQA: fewer KV heads than query heads — the KV cache (and the
    # decode HBM roofline) shrinks by n_heads/n_kv_heads. None = MHA.
    n_kv_heads: Optional[int] = None
    # Rotary position embeddings (Llama-family positions) instead of the
    # learned wpe table; max_seq_len still caps the cache length.
    rope: bool = False
    rope_theta: float = 10000.0
    # Norms: "layernorm" (scale and offset) or "rmsnorm" (scale only),
    # with ``norm_eps`` under the root
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    # Feed-forward: "gelu" (up, GELU, down) or "swiglu" (a SiLU gate
    # times up, then down); ``ffn_width`` sets a width that is no whole
    # multiple of d_model (else d_model * ffn_mult)
    ffn: str = "gelu"
    ffn_width: Optional[int] = None
    # per-head RMSNorm of q and k before the rotary positions
    qk_norm: bool = False
    # What mixes the tokens of a layer (`models/layer_kinds.py`):
    # "softmax" attention over keys and values kept per token,
    # "retention" (power retention: a state kept per sequence), or
    # "latent" (multi-head latent attention: ONE compressed row kept per
    # token, shared by the heads)
    mixer: str = "softmax"
    # Latent attention (DeepSeek-V2/V3's MLA; all five set with
    # mixer="latent"): the ranks of the query's and of the keys' and
    # values' compressions, a head's key split into a part without
    # positions and a rotary part shared by the heads, and a head's
    # value size. ``rope_interleave`` pairs (2i, 2i+1) under the rotary
    # positions instead of (i, i + half).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_interleave: bool = False
    # Dropless sparse experts (`models/expert_layer.py`): after
    # ``leading_dense`` layers whose feed-forward is the dense one (of
    # ``ffn_width``), every layer has ``routed_experts`` experts of
    # ``expert_width``, ``experts_per_token`` of them a token, and
    # ``shared_experts`` that every token goes through; the chosen
    # scores (a sigmoid of each logit) are normalised and scaled by
    # ``routed_scaling``.
    # 0 routed experts = none. (``moe_experts`` above is the GShard
    # capacity form, for training.)
    routed_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    shared_experts: int = 0
    routed_scaling: float = 1.0
    leading_dense: int = 0

    def __post_init__(self):
        for name, value, allowed in (
                ("norm", self.norm, ("layernorm", "rmsnorm")),
                ("ffn", self.ffn, ("gelu", "swiglu")),
                ("mixer", self.mixer, ("softmax", "retention", "latent"))):
            if value not in allowed:
                raise ValueError(f"{name} must be one of {allowed}, "
                                 f"got {value!r}")
        if self.mixer == "latent":
            if not (self.q_lora_rank and self.kv_lora_rank
                    and self.qk_nope_head_dim and self.qk_rope_head_dim
                    and self.v_head_dim):
                raise ValueError("mixer='latent' needs q_lora_rank, "
                                 "kv_lora_rank, qk_nope_head_dim, "
                                 "qk_rope_head_dim and v_head_dim")
            if not (self.rms_norm and self.rope and not self.use_bias
                    and self.ffn == "swiglu"):
                raise ValueError("latent attention is built with RMSNorm, "
                                 "rotary positions, a gated feed-forward "
                                 "and no bias")
        if self.routed_experts:
            if not 0 < self.experts_per_token <= self.routed_experts:
                raise ValueError("experts_per_token must lie in "
                                 "1..routed_experts")
            if self.ffn != "swiglu" or self.moe_experts:
                raise ValueError("dropless experts are SiLU-gated "
                                 "(ffn='swiglu') and exclude moe_experts")
            if not 0 <= self.leading_dense < self.n_layers:
                raise ValueError("leading_dense must leave an expert "
                                 "layer")
        elif self.leading_dense:
            raise ValueError("leading_dense is for stacks with "
                             "routed_experts")

    @property
    def rms_norm(self) -> bool:
        return self.norm == "rmsnorm"

    @property
    def head_dim(self):
        return self.d_model // self.n_heads

    @property
    def kv_heads(self):
        kv = self.n_kv_heads or self.n_heads
        if self.n_heads % kv:
            raise ValueError(
                f"n_heads {self.n_heads} not divisible by n_kv_heads {kv}")
        return kv

    @property
    def d_ffn(self):
        return self.ffn_width or self.d_model * self.ffn_mult

    def flops_per_token(self) -> float:
        """Model FLOPs per token (fwd+bwd), 6*N + attention term."""
        n = self.num_params(non_embedding=True)
        attn = 12 * self.n_layers * self.d_model * self.max_seq_len
        return 6 * n + attn

    @property
    def latent_row(self) -> int:
        """What a latent layer caches a token: the compressed row and
        the rotary part of the key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def _latent_num_params(self, non_embedding: bool) -> int:
        d, L, H = self.d_model, self.n_layers, self.n_heads
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        attn = (d * self.q_lora_rank + self.q_lora_rank * (H * qk + 1)
                + d * self.latent_row + self.kv_lora_rank
                * (H * (self.qk_nope_head_dim + self.v_head_dim) + 1)
                + H * self.v_head_dim * d + 2 * d)
        dense = 3 * d * self.d_ffn
        sparse = (3 * d * self.expert_width
                  * (self.routed_experts + self.shared_experts)
                  + (d + 1) * self.routed_experts)
        lead = self.leading_dense if self.routed_experts else L
        n = L * attn + lead * dense + (L - lead) * sparse + d
        if not non_embedding:
            n += self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return n

    def num_params(self, non_embedding: bool = False) -> int:
        d, L = self.d_model, self.n_layers
        if self.mixer == "latent":
            return self._latent_num_params(non_embedding)
        kv_dim = self.kv_heads * self.head_dim
        per_layer = (d * (d + 2 * kv_dim)   # wqkv (GQA-sized kv)
                     + d * d                # wo
                     + 2 * d * self.d_ffn + 4 * d)
        if self.ffn == "swiglu":
            per_layer += d * self.d_ffn     # wgate
        if self.rms_norm:
            per_layer -= 2 * d              # no offsets
        if self.qk_norm:
            per_layer += 2 * self.head_dim
        if self.mixer == "retention":
            per_layer += d * self.kv_heads  # wg
        if self.use_bias:
            # bqkv(d+2kv) + bo(d) + bup(ffn) + bdown(d)
            per_layer += (d + 2 * kv_dim) + 2 * d + self.d_ffn
        n = L * per_layer + 2 * d  # + final ln
        if not non_embedding:
            n += self.vocab_size * d
            if not self.rope:
                n += self.max_seq_len * d
            if not self.tie_embeddings:
                n += self.vocab_size * d
        return n


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape)).astype(dtype)


def _flag(name):
    from paddle_tpu import flags
    return flags.get_flag(name)


def stack_block_weights(blocks):
    """One scan-stacked pytree over structurally identical blocks.

    Not a plain tree_map: per-instance STATIC attributes (e.g. the
    ``_path`` tag_paths stores — "blocks.0" vs "blocks.1") differ
    between blocks and would fail tree_map's aux-data equality even
    though the blocks are computationally identical. Leaves are stacked
    positionally and rebuilt with block 0's treedef, whose static
    metadata drives the scanned body."""
    leaves = [jax.tree_util.tree_leaves(b) for b in blocks]
    treedef = jax.tree_util.tree_structure(blocks[0])
    if any(len(ls) != len(leaves[0]) for ls in leaves):
        raise ValueError("blocks are structurally heterogeneous; "
                         "cannot scan-stack")
    return jax.tree_util.tree_unflatten(
        treedef, [jnp.stack(xs) for xs in zip(*leaves)])


def _use_decode_kernel(T: int) -> bool:
    """Route single-token decode through the Pallas flash-decode kernel.
    Disabled under a multi-device mesh: GSPMD has no partitioning rule for
    the pallas custom-call, so a tp-sharded KV cache would be all-gathered
    per layer per step — the einsum path lets the partitioner shard."""
    if T % 128 or not _flag("use_pallas_kernels"):
        return False
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    return mesh is None or mesh.size == 1


class GPTBlock(Module):
    """Pre-LN transformer decoder block with fused qkv (one (d,3d) matmul
    keeps the MXU busy vs three thin ones)."""

    def __init__(self, cfg: GPTConfig, key: jax.Array, use_moe=False,
                 use_experts=False):
        super().__init__()
        d, h = cfg.d_model, cfg.n_heads
        self.n_heads = h
        self.kv_heads = cfg.kv_heads
        self.head_dim = cfg.head_dim
        self.rope = cfg.rope
        self.rope_theta = cfg.rope_theta
        if cfg.rope and cfg.head_dim % 2:
            raise ValueError("rope needs an even head_dim")
        kv_dim = self.kv_heads * self.head_dim
        self.dropout = cfg.dropout
        ks = jax.random.split(key, 4)
        std = 0.02
        resid_std = std / math.sqrt(2 * cfg.n_layers)
        dt = cfg.dtype
        self.use_moe = use_moe
        self.norm_eps = cfg.norm_eps
        self.rms = cfg.rms_norm
        self.mixer = cfg.mixer
        if use_moe and cfg.ffn != "gelu":
            raise NotImplementedError("expert blocks have a GELU "
                                      "feed-forward only")
        self.ln1_scale = Parameter(jnp.ones((d,), jnp.float32))
        self.ln2_scale = Parameter(jnp.ones((d,), jnp.float32))
        if self.rms:
            self.ln1_bias = self.ln2_bias = None
        else:
            self.ln1_bias = Parameter(jnp.zeros((d,), jnp.float32))
            self.ln2_bias = Parameter(jnp.zeros((d,), jnp.float32))
        if cfg.qk_norm:
            self.q_norm = Parameter(jnp.ones((cfg.head_dim,), jnp.float32))
            self.k_norm = Parameter(jnp.ones((cfg.head_dim,), jnp.float32))
        else:
            self.q_norm = self.k_norm = None
        # retention's gate: one logit per key/value head and token
        self.wg = (Parameter(_normal(jax.random.fold_in(key, 5),
                                     (d, self.kv_heads), std, dt))
                   if cfg.mixer == "retention" else None)
        dense_ffn = not (use_moe or use_experts)
        self.wgate = (Parameter(_normal(jax.random.fold_in(key, 6),
                                        (d, cfg.d_ffn), std, dt))
                      if cfg.ffn == "swiglu" and dense_ffn else None)
        if cfg.mixer == "latent":
            self._init_latent(cfg, jax.random.fold_in(key, 7), std,
                              resid_std)
        else:
            self.wqkv = Parameter(_normal(ks[0], (d, d + 2 * kv_dim), std,
                                          dt))
            self.wo = Parameter(_normal(ks[1], (d, d), resid_std, dt))
        # dropless sparse experts with a shared expert in the dense
        # feed-forward's place (`models/expert_layer.py`)
        self.experts = None
        if use_experts:
            from paddle_tpu.models.expert_layer import ExpertLayer
            self.experts = ExpertLayer(
                d, cfg.expert_width, cfg.routed_experts,
                cfg.experts_per_token, ks[2],
                n_shared=cfg.shared_experts, scale=cfg.routed_scaling,
                dtype=dt, std=std,
                down_std=resid_std)
            self.moe = self.wup = self.wdown = None
        elif use_moe:
            from paddle_tpu.incubate.moe import MoELayer
            self.moe = MoELayer(d, cfg.d_ffn, cfg.moe_experts,
                                gate=cfg.moe_gate, dtype=dt,
                                seed=int(jax.random.randint(
                                    ks[2], (), 0, 2**31 - 1)))
            self.wup = self.wdown = None
        else:
            self.moe = None
            self.wup = Parameter(_normal(ks[2], (d, cfg.d_ffn), std, dt))
            self.wdown = Parameter(_normal(ks[3], (cfg.d_ffn, d),
                                           resid_std, dt))
        if cfg.use_bias:
            self.bqkv = Parameter(jnp.zeros((d + 2 * kv_dim,), dt))
            self.bo = Parameter(jnp.zeros((d,), dt))
            if dense_ffn:
                self.bup = Parameter(jnp.zeros((cfg.d_ffn,), dt))
                self.bdown = Parameter(jnp.zeros((d,), dt))
            else:
                self.bup = self.bdown = None
        else:
            self.bqkv = self.bo = self.bup = self.bdown = None

    def _init_latent(self, cfg, key, std, resid_std):
        """Latent attention's matrices in place of the fused ``wqkv``:
        the query through a rank ``q_lora_rank`` (norm between), keys
        and values through ONE row of ``kv_lora_rank`` a token (normed)
        beside the rotary part of the key; ``wkv_b`` takes the row to a
        head's key without positions and its value."""
        d, h, dt = cfg.d_model, cfg.n_heads, cfg.dtype
        self.nope, self.rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.v_dim, self.kv_rank = cfg.v_head_dim, cfg.kv_lora_rank
        self.rope_interleave = cfg.rope_interleave
        ks = jax.random.split(key, 5)
        self.wqkv = None
        self.wq_a = Parameter(_normal(ks[0], (d, cfg.q_lora_rank), std, dt))
        self.q_a_norm = Parameter(jnp.ones((cfg.q_lora_rank,), jnp.float32))
        self.wq_b = Parameter(_normal(
            ks[1], (cfg.q_lora_rank, h * (self.nope + self.rope_dim)), std,
            dt))
        self.wkv_a = Parameter(_normal(ks[2], (d, cfg.latent_row), std, dt))
        self.kv_a_norm = Parameter(jnp.ones((self.kv_rank,), jnp.float32))
        self.wkv_b = Parameter(_normal(
            ks[3], (self.kv_rank, h * (self.nope + self.v_dim)), std, dt))
        self.wo = Parameter(_normal(ks[4], (h * self.v_dim, d), resid_std,
                                    dt))

    def _rope_pairs(self, x, positions):
        """Rotary positions on (B, K, ..., D) at ``positions`` (B, K):
        `_apply_rope`'s rotation over the last axis, whatever its size,
        with the pairing the configuration names ((2i, 2i+1) where
        ``rope_interleave``, else (i, i + D/2))."""
        half = x.shape[-1] // 2
        freqs = self.rope_theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[..., None] * freqs
        ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
        cos, sin = jnp.cos(ang), jnp.sin(ang)
        x32 = x.astype(jnp.float32)
        if self.rope_interleave:
            x1, x2 = x32[..., 0::2], x32[..., 1::2]
            out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                            axis=-1).reshape(x.shape)
        else:
            x1, x2 = x32[..., :half], x32[..., half:]
            out = jnp.concatenate(
                [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.astype(x.dtype)

    def _latent_inputs(self, x, positions):
        """Norm 1 and latent attention's projections of x (B, K, d) at
        positions ``positions[b] + k``: a head's query without positions
        (B, K, H, nope) and its rotary part (B, K, H, rope), and what the
        cache keeps of a token, ``row = [c_kv | k_rope]``
        (B, K, rank + rope): the normed compressed row and the key's
        rotary part, rotated, ONE for all heads."""
        b, K = x.shape[:2]
        h = self._ln(x, self.ln1_scale, self.ln1_bias)
        cq = final_ln(h @ self.wq_a, self.q_a_norm, None, self.norm_eps,
                      True)
        q = (cq @ self.wq_b).reshape(b, K, self.n_heads,
                                     self.nope + self.rope_dim)
        kv = h @ self.wkv_a
        c_kv = final_ln(kv[..., :self.kv_rank], self.kv_a_norm, None,
                        self.norm_eps, True)
        pos2 = positions[:, None] + jnp.arange(K)[None, :]
        q_rope = self._rope_pairs(q[..., self.nope:], pos2)
        k_rope = self._rope_pairs(kv[..., self.kv_rank:], pos2)
        return (q[..., :self.nope], q_rope,
                jnp.concatenate([c_kv, k_rope], axis=-1))

    @property
    def latent_scale(self) -> float:
        return 1.0 / math.sqrt(self.nope + self.rope_dim)

    def _wkv_b_heads(self):
        """``wkv_b`` as (rank, H, nope + v)."""
        return self.wkv_b.reshape(self.kv_rank, self.n_heads,
                                  self.nope + self.v_dim)

    def _split_qkv(self, qkv):
        """(B, L, d+2·kv_dim) fused projection → q (B, L, H, D),
        k/v (B, L, Hkv, D) — GQA-sized kv."""
        b, L = qkv.shape[:2]
        d = self.n_heads * self.head_dim
        kvd = self.kv_heads * self.head_dim
        q = qkv[..., :d].reshape(b, L, self.n_heads, self.head_dim)
        k = qkv[..., d:d + kvd].reshape(b, L, self.kv_heads,
                                        self.head_dim)
        v = qkv[..., d + kvd:].reshape(b, L, self.kv_heads, self.head_dim)
        return q, k, v

    def _apply_rope(self, x, positions):
        """Rotary embedding on (B, L, Hx, D) at absolute ``positions``
        (B, L) or (L,) (Llama-family positions; rotate-half convention)."""
        if not self.rope:
            return x
        half = self.head_dim // 2
        freqs = self.rope_theta ** (
            -jnp.arange(0, half, dtype=jnp.float32) / half)
        pos = jnp.asarray(positions, jnp.float32)
        ang = pos[..., None] * freqs               # (..., L, half)
        while ang.ndim < 3:
            ang = ang[None]
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
        x32 = x.astype(jnp.float32)
        x1, x2 = x32[..., :half], x32[..., half:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.astype(x.dtype)

    def _attention(self, q, k, v, s):
        """Ring attention over the 'sp' axis when the global mesh shards the
        sequence (SURVEY §5.7 gap — new capability); otherwise the flash /
        XLA path. Disabled inside the vmapped pipeline stages (shard_map
        does not nest under that vmap), where GSPMD handles 'sp'."""
        from paddle_tpu.distributed.mesh import get_mesh
        mesh = get_mesh()
        shape = dict(mesh.shape) if mesh is not None else {}
        sp = shape.get("sp", 1)
        # shard_map needs every spec'd dim divisible by its mesh axes
        # (unlike with_sharding_constraint, which tolerates odd shapes)
        divisible = (s % sp == 0
                     and q.shape[0] % (shape.get("dp", 1)
                                       * shape.get("fsdp", 1)) == 0
                     and self.n_heads % shape.get("tp", 1) == 0)
        if (sp > 1 and not _in_pipeline() and divisible
                and self.kv_heads == self.n_heads):
            from paddle_tpu.distributed.ring_attention import (
                sequence_parallel_attention)
            return sequence_parallel_attention(q, k, v, mesh, causal=True,
                                               mode="ring")
        return F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              dropout_p=0.0)

    def _ln(self, x, scale, bias):
        return final_ln(x, scale, bias, self.norm_eps, self.rms)

    def _ffn(self, h, shard=False):
        """The dense feed-forward on normed ``h``: up, GELU, down — or,
        gated, ``silu(h @ wgate) * (h @ wup)`` then down."""
        up = h @ self.wup
        if self.bup is not None:
            up = up + self.bup
        h = (jax.nn.silu(h @ self.wgate) * up if self.wgate is not None
             else jax.nn.gelu(up))
        if shard:
            h = _shard_act(h, LAYOUT.activation("sp", "tp"))
        h = h @ self.wdown
        if self.bdown is not None:
            h = h + self.bdown
        return h

    def _block_tail(self, x, attn):
        """Post-attention half of the block (out-proj + MLP), shared by
        every cached-decode variant — ONE definition."""
        return self._block_tail_sets((x,), (attn,))[0][0]

    def _block_tail_sets(self, xs, attns, expert_stacks=None):
        """`_block_tail` of one or more sets of rows in one call: each
        set's output projection, residual and norm as it would be alone,
        and the feed-forward's weights read ONCE for all sets (the dense
        matrices on the sets' rows together; routed experts through
        `ExpertLayer.forward_sets`: each set routed and combined alone,
        one kernel call over every set's pairs; capacity experts set by
        set). Returns the sets' outputs; how many of the layer's routed
        experts some token of any set chose (0 for a layer without
        them); and, for a layer with them, what its router saw and chose
        for the FIRST set: ``(the normed tokens, the set's shape;
        experts (tokens, k))``, else None. ``expert_stacks``: as
        `ExpertLayer.forward` takes them. Of several sets the normed
        tokens are made once (`lax.optimization_barrier`), so that the
        router and what is reported of it read the very same array: XLA
        may otherwise make the two copies at different precisions."""
        res, ns = [], []
        for x, attn in zip(xs, attns):
            o = attn @ self.wo
            if self.bo is not None:
                o = o + self.bo
            res.append(x + o)
            ns.append(self._ln(res[-1], self.ln2_scale, self.ln2_bias))
        if len(ns) > 1:
            ns = lax.optimization_barrier(ns)
        touched, routed = jnp.int32(0), None
        if self.experts is not None:
            hs, touched, experts = self.experts.forward_sets(ns,
                                                             expert_stacks)
            routed = (ns[0], experts[0])
        elif self.moe is not None:
            # capacity experts drop by the tokens a call holds: each set
            # as alone
            hs = [self.moe(n, None)[0] for n in ns]
        elif len(ns) == 1:
            hs = [self._ffn(ns[0])]
        else:
            d = ns[0].shape[-1]
            h = self._ffn(jnp.concatenate([n.reshape(-1, d) for n in ns]))
            hs, at = [], 0
            for n in ns:
                hs.append(h[at:at + n.size // d].reshape(n.shape))
                at += n.size // d
        return [x + h for x, h in zip(res, hs)], touched, routed

    def _mix_inputs(self, x, positions):
        """Norm 1 + fused QKV (+ per-head q/k norm, + rope at
        ``positions``) — the shared front half of every layer kind's
        prefill and step (ONE definition). x: (B, K, d) → q (B,K,H,D),
        k/v (B,K,Hkv,D), and g (B,K,Hkv) float32: the log of a retention
        layer's gate, ``None`` for softmax attention."""
        K = x.shape[1]
        h = self._ln(x, self.ln1_scale, self.ln1_bias)
        qkv = h @ self.wqkv
        if self.bqkv is not None:
            qkv = qkv + self.bqkv
        q, k, v = self._split_qkv(qkv)
        if self.q_norm is not None:
            q = final_ln(q, self.q_norm, None, self.norm_eps, True)
            k = final_ln(k, self.k_norm, None, self.norm_eps, True)
        if self.rope:
            pos2 = positions[:, None] + jnp.arange(K)[None, :]
            q = self._apply_rope(q, pos2)
            k = self._apply_rope(k, pos2)
        g = (None if self.wg is None else
             jax.nn.log_sigmoid((h @ self.wg).astype(jnp.float32)))
        return q, k, v, g

    def _qkv(self, x, positions):
        return self._mix_inputs(x, positions)[:3]

    def _write_kv_rows(self, kv, k, v, positions):
        """Write each row's K new KV entries ((B, K, Hkv, D), any dtype)
        into the head-major caches at per-row ``positions`` — the ONE
        cache-write definition."""
        k_cache, v_cache = kv

        def write(cache, new, pos):  # (Hkv, T, D) ← (Hkv, K, D) at pos
            return lax.dynamic_update_slice(cache, new, (0, pos, 0))

        k_cache = jax.vmap(write)(
            k_cache, jnp.transpose(k, (0, 2, 1, 3)).astype(k_cache.dtype),
            positions)
        v_cache = jax.vmap(write)(
            v_cache, jnp.transpose(v, (0, 2, 1, 3)).astype(v_cache.dtype),
            positions)
        return k_cache, v_cache

    def _qkv_write(self, x, kv, positions):
        """`_qkv` + per-row cache write at ``positions``.
        x: (B, K, d) → (q (B,K,H,D), new k/v caches (B,Hkv,T,D))."""
        q, k, v = self._qkv(x, positions)
        k_cache, v_cache = self._write_kv_rows(kv, k, v, positions)
        return q, k_cache, v_cache

    def decode_rows(self, x, kv, positions, allow_kernel: bool = True):
        """K-token ragged decode that does NOT write the cache: the
        bandwidth-optimal serving primitive (VERDICT r5 decode work).

        The previous engine formulation carried the caches through the
        layer scan as xs AND ys, so XLA rebuilt the whole (L, S, H, T, D)
        buffer every token (~2x the cache size in pure copy traffic per
        step). Here the cache is read-only; the current K tokens'
        attention contribution is folded in analytically (their K/V rows
        ride alongside the prefix softmax as extra columns), and the rows
        are returned for the CALLER to write back — one tiny
        dynamic_update_slice per sequence per step instead of a
        full-cache rebuild.

        x: (B, K, d) embeddings at positions [positions[b],
        positions[b]+K); kv: head-major (B, Hkv, T, D) holding each row's
        prefix [0, positions[b]) (entries at/after positions[b] are
        ignored). Row (b, j) attends to the prefix plus new rows i <= j —
        the same [0, positions[b]+j] window as the cache-writing path.

        Returns (y, k_rows, v_rows) with rows (B, K, Hkv, D) in cache
        dtype.
        """
        if self.mixer != "softmax":
            raise NotImplementedError(
                f"{self.mixer} layers keep no key/value cache: serve "
                f"them through inference.make_engine (the paged engine "
                f"holds their per-sequence state)")
        b, K, d = x.shape
        k_cache, v_cache = kv
        T = k_cache.shape[2]
        q, k, v = self._qkv(x, positions)
        # round-trip the new rows through the CACHE dtype before they
        # enter attention: row i<j must look identical to verify row j
        # (K>1) as it would to a later K=1 step reading it from the
        # cache, or speculative acceptance would not be lossless when
        # cache_dtype differs from the compute dtype
        k = k.astype(k_cache.dtype)
        v = v.astype(v_cache.dtype)
        scale = 1.0 / math.sqrt(self.head_dim)
        if (allow_kernel and K == 1
                and T >= int(_flag("decode_kernel_min_t"))
                and _use_decode_kernel(T)):
            # long caches: the flash-decode kernel reads ONLY each row's
            # valid prefix blocks (clamped index maps); the fresh row is
            # folded into its online softmax analytically via the
            # returned (m, l) stats. The dense einsum below reads the
            # whole T whatever the lengths — at serving cache lengths
            # that is the dominant wasted bandwidth.
            from paddle_tpu.ops.pallas.decode_attention import (
                decode_attention, fold_fresh_row)
            o, m, l = decode_attention(
                q[:, 0].astype(k_cache.dtype), k_cache, v_cache,
                positions, scale=scale, return_stats=True)
            attn = fold_fresh_row(o, m, l, q[:, 0], k[:, 0], v[:, 0],
                                  scale, self.n_heads // self.kv_heads)
            attn = attn.reshape(b, K, d).astype(x.dtype)
            return self._block_tail(x, attn), k, v
        # GQA via grouped einsum against the UN-expanded cache (query
        # head h reads kv head h // group — same convention as the
        # flash-decode kernel); never jnp.repeat the cache in HBM
        group = self.n_heads // self.kv_heads
        qg = q.reshape(b, K, self.kv_heads, group, self.head_dim)
        att = jnp.einsum("bkhgd,bhtd->bhgkt", qg, k_cache) * scale
        k_pos = jnp.arange(T)[None, None, None, None, :]
        att = jnp.where(k_pos < positions[:, None, None, None, None],
                        att.astype(jnp.float32), -jnp.inf)
        # the K new rows attend to each other causally (row j sees rows
        # i <= j); their logits join the prefix as K extra columns
        att_new = jnp.einsum("bkhgd,bihd->bhgki", qg, k) * scale
        causal = jnp.arange(K)[None, None, None, :, None] \
            >= jnp.arange(K)[None, None, None, None, :]
        att_new = jnp.where(causal, att_new.astype(jnp.float32), -jnp.inf)
        full = jax.nn.softmax(
            jnp.concatenate([att, att_new], axis=-1), axis=-1)
        # probabilities stay in the COMPUTE dtype (only K/V round-trip
        # through the cache dtype): quantized-cache configs must not
        # also truncate the attention weights
        p_cache = full[..., :T].astype(x.dtype)
        p_new = full[..., T:].astype(x.dtype)
        attn = (jnp.einsum("bhgkt,bhtd->bkhgd", p_cache, v_cache)
                + jnp.einsum("bhgki,bihd->bkhgd", p_new, v))
        attn = attn.reshape(b, K, d).astype(x.dtype)
        return self._block_tail(x, attn), k, v

    def verify_step(self, x, kv, positions):
        """K-token decode with RAGGED per-row cache positions, writing the
        rows into the cache (≙ masked_multihead_attention in
        fused_multi_transformer_op.cu at K=1, which likewise takes a
        per-sequence ``sequence_lengths`` tensor; K>1 is the
        speculative-decoding verify primitive — no reference analog, the
        reference decodes strictly one token per kernel launch).

        One attention definition: delegates to `decode_rows` and writes
        the returned rows at ``positions`` (callers that own the cache
        buffer — the decode engine — call `decode_rows` directly and
        batch the writes).
        """
        y, k_rows, v_rows = self.decode_rows(x, kv, positions)
        return y, self._write_kv_rows(kv, k_rows, v_rows, positions)

    def decode_step(self, x, kv, positions):
        """One-token ragged decode: the Pallas flash-decode kernel when
        it can engage, else `verify_step` with K=1 (same einsum math —
        one definition, not a drifted copy)."""
        if not _use_decode_kernel(kv[0].shape[2]):
            return self.verify_step(x, kv, positions)
        b, L, d = x.shape
        q, k_cache, v_cache = self._qkv_write(x, kv, positions)
        from paddle_tpu.ops.pallas.decode_attention import decode_attention
        attn = decode_attention(q[:, 0].astype(k_cache.dtype), k_cache,
                                v_cache, positions + 1,
                                scale=1.0 / math.sqrt(self.head_dim))
        attn = attn.astype(x.dtype).reshape(b, 1, d)
        return self._block_tail(x, attn), (k_cache, v_cache)

    def forward_cached(self, x, kv, pos):
        """Decode/prefill step with a KV cache and a SCALAR start
        position (≙ fused_multi_transformer_op.cu CacheKV write + masked
        attention over the prefix). x: (B, L, d) at positions
        [pos, pos+L); delegates to the ragged-position variants."""
        b = x.shape[0]
        positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
        if x.shape[1] == 1:
            return self.decode_step(x, kv, positions)
        return self.verify_step(x, kv, positions)

    def _latent_forward(self, x):
        """Whole sequences (B, S, d) through latent attention in the
        expanded form (every key and value made from its row), causal."""
        from paddle_tpu.ops.pallas.latent_attention import (
            expanded_attention)
        b, s, _ = x.shape
        q_nope, q_rope, row = self._latent_inputs(
            x, jnp.zeros((b,), jnp.int32))
        with jax.named_scope("mla_prefill"):
            attn = jax.vmap(lambda qn, qr, r: expanded_attention(
                qn, qr, r[:, :self.kv_rank], r[:, self.kv_rank:],
                self._wkv_b_heads(), self.latent_scale, 0))(
                    q_nope, q_rope, row)
        return self._block_tail(x, attn.astype(x.dtype).reshape(b, s, -1))

    def forward(self, x, rng_key=None, aux_acc=None):
        if self.mixer == "latent":
            return self._latent_forward(x)
        b, s, d = x.shape
        q, k, v, g = self._mix_inputs(x, jnp.zeros((b,), jnp.int32))
        q = _shard_act(q, LAYOUT.activation("sp", "tp", None))
        k = _shard_act(k, LAYOUT.activation("sp", "tp", None))
        v = _shard_act(v, LAYOUT.activation("sp", "tp", None))
        if g is None:
            attn = self._attention(q, k, v, s)
        else:
            from paddle_tpu.ops.pallas.retention import retention_sequence
            attn = jax.vmap(retention_sequence)(q, k, v, g).astype(x.dtype)
        attn = attn.reshape(b, s, d)
        o = attn @ self.wo
        if self.bo is not None:
            o = o + self.bo
        x = x + _maybe_dropout(o, self.dropout, rng_key, 1)
        h = self._ln(x, self.ln2_scale, self.ln2_bias)
        if self.experts is not None:
            h = self.experts(h)[0]
        elif self.moe is not None:
            h, aux = self.moe(h, rng_key)
            if aux_acc is not None:
                aux_acc.append(aux)
        else:
            h = self._ffn(h, shard=True)
        x = x + _maybe_dropout(h, self.dropout, rng_key, 2)
        return _shard_act(x, LAYOUT.activation("sp", None))


_BATCH_AXES = LAYOUT.batch_axes

_PIPELINE_DEPTH = 0


def _in_pipeline() -> bool:
    return _PIPELINE_DEPTH > 0


def _maybe_dropout(x, p, key, salt):
    if p == 0.0 or key is None:
        return x
    k = jax.random.fold_in(key, salt)
    keep = jax.random.bernoulli(k, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


def _shard_act(x, spec: P):
    """Constrain activation sharding when a global mesh is installed and we
    are under its trace; no-op otherwise (single-chip / no mesh)."""
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1:
        return x
    try:
        return lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except Exception:
        return x


def final_ln(x, scale, bias, eps: float = 1e-5, rms: bool = False):
    """LayerNorm over the last axis, or with ``rms`` RMSNorm (no mean
    taken off, no offset), fp32 statistics — the single definition
    shared by the blocks, GPT.head, fused_lm_loss and the engines."""
    x32 = x.astype(jnp.float32)
    if rms:
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * scale).astype(x.dtype)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.var(x32, -1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale
            + bias).astype(x.dtype)


def _gathered_table(w):
    """ZeRO-3 gather-for-use on an fsdp-sharded embedding table: a lookup
    from a d-sharded table produces d-sharded rows, and the partitioner has
    no efficient transition from that to batch-sharded activations — it
    falls back to "involuntary full rematerialization" (MULTICHIP_r02
    phase-D warning). All-gathering the d dim first (what GroupSharded
    stage-3 forward pre-hooks do, group_sharded_stage3.py:59) keeps the
    gather local and the transition free."""
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or dict(mesh.shape).get("fsdp", 1) == 1:
        return w
    try:
        return lax.with_sharding_constraint(
            w, NamedSharding(mesh, P("tp", None)))
    except Exception:
        return w


class GPT(Module):
    """≙ PaddleNLP GPTForPretraining (decoder-only, learned positions)."""

    def __init__(self, cfg: GPTConfig, seed: int = 0):
        super().__init__()
        self.cfg = cfg
        key = jax.random.PRNGKey(seed)
        kw, kp, kh, kb = jax.random.split(key, 4)
        dt = cfg.dtype
        self.wte = Parameter(_normal(kw, (cfg.vocab_size, cfg.d_model),
                                     0.02, dt))
        # rope models carry positions in the attention rotation, not a
        # learned table
        self.wpe = None if cfg.rope else Parameter(
            _normal(kp, (cfg.max_seq_len, cfg.d_model), 0.01, dt))
        if cfg.moe_experts > 0 and cfg.moe_every < 1:
            raise ValueError(
                f"moe_every must be >= 1, got {cfg.moe_every}")
        if cfg.moe_experts > 0 and cfg.remat:
            raise ValueError("moe_experts with remat is unsupported (the "
                             "aux-loss accumulator cannot cross a "
                             "jax.checkpoint boundary)")
        self.blocks = LayerList([
            GPTBlock(cfg, jax.random.fold_in(kb, i),
                     use_moe=(cfg.moe_experts > 0
                              and (i + 1) % cfg.moe_every == 0),
                     use_experts=(cfg.routed_experts > 0
                                  and i >= cfg.leading_dense))
            for i in range(cfg.n_layers)])
        self.lnf_scale = Parameter(jnp.ones((cfg.d_model,), jnp.float32))
        self.lnf_bias = (None if cfg.rms_norm else Parameter(
            jnp.zeros((cfg.d_model,), jnp.float32)))
        if not cfg.tie_embeddings:
            self.lm_head = Parameter(_normal(kh, (cfg.d_model,
                                                  cfg.vocab_size), 0.02, dt))
        else:
            self.lm_head = None

    def merge_params(self, params):
        """Module.merge_params plus stacked-state awareness: when the
        state carries ``_stacked_blocks`` (init_train_state(stacked=True)),
        ALSO rebind each per-layer block to a sliced view of the stack —
        otherwise every consumer outside the scan forward (decode
        forward_cached, generate, state_dict export) would silently read
        the init-time weights still sitting in self.blocks. Inside jit
        the unconsumed slices are dead code XLA eliminates; outside jit
        they materialize only if actually used."""
        new = Module.merge_params(self, params)
        st = getattr(new, "_stacked_blocks", None)
        if st is not None:
            # (the stack holds the layers after the leading dense ones,
            # which stay blocks of their own)
            lead = new.cfg.leading_dense
            for i in range(lead, new.cfg.n_layers):
                blk = jax.tree_util.tree_map(lambda x, i=i: x[i - lead],
                                             st)
                object.__setattr__(new.blocks, f"item_{i}", blk)
        return new

    def embed(self, tokens):
        s = tokens.shape[-1]
        if _tp_sharded_vocab(tokens.shape[0], s, self.cfg.vocab_size,
                             self.cfg.d_model):
            from paddle_tpu.distributed.mesh import get_mesh
            from paddle_tpu.distributed.mp_ops import (
                vocab_parallel_embedding)
            # ≙ VocabParallelEmbedding (mp_layers.py:37): masked local
            # lookup + psum — the (V, d) table is never all-gathered
            x = vocab_parallel_embedding(self.wte, tokens, mesh=get_mesh())
        else:
            x = jnp.take(_gathered_table(self.wte), tokens, axis=0)
        if self.wpe is not None:  # rope models position in attention
            x = x + self.wpe[:s]
        return _shard_act(x, LAYOUT.activation("sp", None))

    def head(self, x):
        x = final_ln(x, self.lnf_scale, self.lnf_bias, self.cfg.norm_eps,
                     self.cfg.rms_norm)
        w = self.wte.T if self.lm_head is None else self.lm_head
        logits = x @ w
        return _shard_act(logits, LAYOUT.activation("sp", "tp"))

    def hidden_states(self, tokens, rng_key=None, aux_acc=None):
        """Final hidden states (B, S, d) — forward minus the LM head (the
        fused-CE loss path consumes these directly so (B, S, V) logits
        never materialize).

        Homogeneous (dense) stacks run the layer loop as lax.scan over
        in-jit-stacked block weights: the compiled program contains ONE
        layer body instead of L unrolled copies, which cuts the 1.3B
        train-step compile from tens of minutes to minutes (the decode
        path has always done this; XLA's cost for the in-trace stack is
        a single fused gather the partitioner shards like the weights).
        MoE stacks (structurally heterogeneous blocks) and the
        ``scan_layers=False`` escape hatch keep the unrolled loop."""
        x = self.embed(tokens)
        L = self.cfg.n_layers
        dense = all(self.blocks[i].moe is None
                    and self.blocks[i].experts is None for i in range(L))
        prestacked = (getattr(self, "_stacked_blocks", None)
                      if dense else None)
        use_scan = prestacked is not None or (dense and L > 1
                                              and _flag("scan_layers"))
        if use_scan:
            # in-trace stacking copies every block weight (and its grad
            # transpose un-stacks) — ~2x block-param HBM the unrolled
            # loop never needed; a state built by
            # init_train_state(stacked=True) carries the weights
            # pre-stacked so the scan consumes them with ZERO extra
            # in-program buffers (this is what made the 1.3B step OOM
            # on 16GB while the round-start unrolled form fit)
            stacked = prestacked if prestacked is not None else \
                stack_block_weights([self.blocks[i] for i in range(L)])
            if prestacked is not None:
                from paddle_tpu.distributed.mesh import get_mesh
                mesh = get_mesh()
                if mesh is not None and mesh.size > 1:
                    # re-assert the layer-leading PARTITION_RULES specs
                    # inside the trace: the scanned body then runs
                    # fsdp/tp-sharded matmuls on each layer slice instead
                    # of the partitioner falling back to replicating the
                    # whole (L, ...) stack. (The in-trace-stacked branch
                    # keeps propagation-only sharding — constraining it
                    # would perturb the established per-layer numerics.)
                    stacked = _shard_stacked(stacked, self.blocks[0],
                                             mesh)

            def body(h, blk_i):
                blk, i = blk_i
                k = (jax.random.fold_in(rng_key, i)
                     if rng_key is not None else None)
                return blk(h, k), None

            if self.cfg.remat:
                body = jax.checkpoint(body)
            x, _ = lax.scan(body, x, (stacked, jnp.arange(L)))
            return x
        # remat never coexists with MoE (enforced in __init__), so the
        # checkpointed closure does not capture aux_acc
        blk_fn = (jax.checkpoint(lambda b, h, k: b(h, k),
                                 static_argnums=())
                  if self.cfg.remat
                  else (lambda b, h, k: b(h, k, aux_acc=aux_acc)))
        for i in range(L):
            k = (jax.random.fold_in(rng_key, i)
                 if rng_key is not None else None)
            x = blk_fn(self.blocks[i], x, k)
        return x

    def forward(self, tokens, rng_key=None, return_aux=False):
        """return_aux=True additionally returns the summed MoE load-balance
        aux loss (zeros for dense configs); threaded explicitly — no
        global state, safe across multiple forwards per trace."""
        aux_acc = []
        x = self.hidden_states(tokens, rng_key, aux_acc)
        logits = self.head(x)
        if return_aux:
            aux = jnp.zeros((), jnp.float32)
            for a in aux_acc:
                aux = aux + a
            return logits, aux
        return logits

    # -- KV-cache decoding (≙ inference/api/analysis_predictor.h:95 decode
    # serving + fused_multi_transformer_op.cu CacheKV) ---------------------

    def init_cache(self, batch: int, max_len: Optional[int] = None,
                   dtype=None):
        """Preallocated per-layer (k, v) caches, head-major (B, H, T, D)
        each (the flash-decode kernel's layout)."""
        cfg = self.cfg
        T = max_len or cfg.max_seq_len
        dt = dtype or cfg.dtype
        shape = (batch, cfg.kv_heads, T, cfg.head_dim)
        return [(jnp.zeros(shape, dt), jnp.zeros(shape, dt))
                for _ in range(cfg.n_layers)]

    def embed_at(self, tokens, pos):
        """Embedding for a chunk starting at (possibly traced) `pos`."""
        L = tokens.shape[-1]
        x = jnp.take(_gathered_table(self.wte), tokens, axis=0)
        if self.wpe is None:
            return x
        return x + lax.dynamic_slice_in_dim(self.wpe, pos, L)

    def forward_cached(self, tokens, cache, pos):
        """(B, L) tokens at positions [pos, pos+L) → (logits, new_cache)."""
        x = self.embed_at(tokens, pos)
        new_cache = []
        for i in range(self.cfg.n_layers):
            x, kv = self.blocks[i].forward_cached(x, cache[i], pos)
            new_cache.append(kv)
        return self.head(x), new_cache


def _sample_token(logits, rng, temperature: float, top_p: float,
                  top_k: int):
    """Greedy (temperature==0) / temperature / top-k / nucleus sampling.
    logits: (B, V) fp32."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with mass >= top_p (shifted cumsum keeps
        # the first token crossing the threshold)
        keep = (cum - probs) < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_l, jnp.inf), axis=-1)
        logits = jnp.where(logits < cutoff[:, None], -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def generate(model: "GPT", tokens, max_new_tokens: int,
             temperature: float = 0.0, top_p: float = 1.0, top_k: int = 0,
             eos_id: Optional[int] = None, rng=None,
             max_len: Optional[int] = None):
    """Autoregressive generation with a functional KV cache (≙ the decode
    loop the reference serves through AnalysisPredictor +
    fused_multi_transformer; VERDICT r1 item 2).

    tokens: (B, S0) prompt. Returns (B, S0 + max_new_tokens) int32 — after
    eos (if given) positions are padded with eos. Greedy by default;
    temperature/top-k/top-p sampling otherwise. The decode loop is a
    lax.scan inside ONE jit, so serving pays a single dispatch.
    """
    cfg = model.cfg
    b, s0 = tokens.shape
    total = s0 + max_new_tokens
    T = max_len or cfg.max_seq_len
    assert total <= T, f"{total} tokens exceed cache length {T}"
    if rng is None:
        rng = jax.random.PRNGKey(0)

    params, _ = model.split_params()
    key = (b, s0, T, max_new_tokens, temperature, top_p, top_k, eos_id)
    cache_d = _GEN_CACHE.setdefault(model, collections.OrderedDict())
    run = cache_d.get(key)
    if run is None:
        run = jax.jit(functools.partial(
            _generate_impl, model, b, s0, T, max_new_tokens, temperature,
            top_p, top_k, eos_id))
        cache_d[key] = run
        # LRU bound: a long-lived server sweeping shapes must not
        # accumulate compiled executables forever (VERDICT r2 weak 11)
        while len(cache_d) > _GEN_CACHE_MAX:
            cache_d.popitem(last=False)
    else:
        cache_d.move_to_end(key)
    return run(params, jnp.asarray(tokens, jnp.int32), rng)


def _stacked_forward_cached(m: GPT, stacked, tokens, kc, vc, pos):
    """Cached forward with the layer loop as lax.scan over stacked weights:
    the compiled decode program contains ONE layer body instead of L
    unrolled copies — at 1.3B this cuts serving compile time ~L×.
    kc/vc: (L, B, Hkv, T, D)."""
    x = m.embed_at(tokens, pos)

    def layer(x, blk_kv):
        blk, k_l, v_l = blk_kv
        x, (k_l, v_l) = blk.forward_cached(x, (k_l, v_l), pos)
        return x, (k_l, v_l)

    x, (kc, vc) = lax.scan(layer, x, (stacked, kc, vc))
    return m.head(x), kc, vc


def _stacked_decode_rows(m: GPT, stacked, cur, kc, vc, pos):
    """One-token cached decode with the caches as READ-ONLY scan xs:
    each layer emits only its new KV row (`GPTBlock.decode_rows`), and
    because every batch row decodes at the same scalar ``pos``, ONE
    dynamic_update_slice per cache writes all (L, B) rows. The previous
    formulation carried the caches through the scan as ys, making XLA
    rebuild the whole (L, B, Hkv, T, D) buffer every token (~2x the
    cache size in copy traffic — the dominant decode overhead measured
    on hardware, r5)."""
    b = cur.shape[0]
    x = m.embed_at(cur[:, None], pos)
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))

    def layer(x, blk_kv):
        blk, k_l, v_l = blk_kv
        y, k_rows, v_rows = blk.decode_rows(x, (k_l, v_l), positions)
        return y, (k_rows, v_rows)

    x, (k_rows, v_rows) = lax.scan(layer, x, (stacked, kc, vc))
    kr = jnp.transpose(k_rows, (0, 1, 3, 2, 4))   # (L, B, Hkv, 1, D)
    vr = jnp.transpose(v_rows, (0, 1, 3, 2, 4))
    kc = lax.dynamic_update_slice(kc, kr, (0, 0, 0, pos, 0))
    vc = lax.dynamic_update_slice(vc, vr, (0, 0, 0, pos, 0))
    return m.head(x), kc, vc


def _generate_impl(model, b, s0, T, max_new_tokens, temperature, top_p,
                   top_k, eos_id, params, tokens, rng):
    m = model.merge_params(params)
    homogeneous = all(m.blocks[i].moe is None
                      for i in range(m.cfg.n_layers))
    if homogeneous:
        return _generate_scan(m, b, s0, T, max_new_tokens, temperature,
                              top_p, top_k, eos_id, tokens, rng)
    cache = m.init_cache(b, T)
    logits, cache = m.forward_cached(tokens, cache, 0)
    last = logits[:, -1].astype(jnp.float32)
    rng, k0 = jax.random.split(rng)
    nxt = _sample_token(last, k0, temperature, top_p, top_k)
    done = jnp.zeros((b,), bool) if eos_id is None else (nxt == eos_id)
    if max_new_tokens == 1:
        return jnp.concatenate([tokens, nxt[:, None]], axis=1)

    def step(carry, _):
        cache, cur, pos, rng, done = carry
        logits, cache = m.forward_cached(cur[:, None], cache, pos)
        rng, k = jax.random.split(rng)
        nx = _sample_token(logits[:, -1].astype(jnp.float32), k,
                           temperature, top_p, top_k)
        if eos_id is not None:
            nx = jnp.where(done, eos_id, nx)
            done = done | (nx == eos_id)
        return (cache, nx, pos + 1, rng, done), nx

    (_, _, _, _, _), rest = lax.scan(
        step, (cache, nxt, jnp.int32(s0), rng, done),
        None, length=max_new_tokens - 1)
    out = jnp.concatenate([nxt[:, None], rest.T], axis=1)
    return jnp.concatenate([tokens, out], axis=1)


def _decode_mesh(cfg, b):
    """The active mesh when it can shard decode: tp divides heads, dp
    divides batch (≙ HybridParallelInference serving TP,
    fleet/utils/hybrid_parallel_inference.py:23)."""
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or mesh.size == 1 or _in_pipeline():
        return None
    shape = dict(mesh.shape)
    if (cfg.n_heads % shape.get("tp", 1)
            or cfg.kv_heads % shape.get("tp", 1)   # GQA cache sharding
            or b % shape.get("dp", 1)):
        return None
    return mesh


def stacked_block_specs(template_blk, spec_fn=None):
    """Per-leaf PartitionSpecs for the scan-stacked form of one template
    block: each param's PARTITION_RULES spec behind a leading (replicated)
    layer axis (``LAYOUT.stacked``). Leaf→name mapping goes by object
    identity against the template block (Module pytree paths are
    index-keyed). ``spec_fn`` maps a param name to its per-block spec
    (default: this module's `partition_spec`; models.bert passes its
    own). Returns (template_leaves, treedef, specs) — derivable BEFORE
    any stacking happens, so init can place the stacked state with
    out_shardings instead of re-laying it out afterwards."""
    spec_fn = spec_fn or partition_spec
    id2name = {id(v): n for n, v in template_blk.named_parameters()}
    tleaves, treedef = jax.tree_util.tree_flatten(template_blk)
    specs = [LAYOUT.stacked(spec_fn(id2name.get(id(t), "")),
                            ndim=t.ndim + 1)
             for t in tleaves]
    return tleaves, treedef, specs


def stacked_partition_specs(stacked, template_blk, spec_fn=None):
    """Per-leaf PartitionSpecs for an already scan-stacked block pytree —
    the ONE spec derivation shared by the sharded generate path, the
    tensor-parallel DecodeEngine, and the sharded-stacked train state
    (which derives them pre-stack via `stacked_block_specs`)."""
    _, _, specs = stacked_block_specs(template_blk, spec_fn)
    sleaves, streedef = jax.tree_util.tree_flatten(stacked)
    return sleaves, streedef, specs


def _shard_stacked(stacked, template_blk, mesh, spec_fn=None):
    """Constrain stacked per-layer weights by PARTITION_RULES with a
    leading (replicated) layer axis, so the decode jit runs TP-sharded
    matmuls instead of replicating every block."""
    sleaves, streedef, specs = stacked_partition_specs(stacked,
                                                       template_blk,
                                                       spec_fn)
    out = []
    for leaf, spec in zip(sleaves, specs):
        try:
            leaf = lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, mesh_safe_spec(spec, mesh)))
        except Exception:
            pass
        out.append(leaf)
    return jax.tree_util.tree_unflatten(streedef, out)


def _generate_scan(m: GPT, b, s0, T, max_new_tokens, temperature, top_p,
                   top_k, eos_id, tokens, rng):
    """Homogeneous (dense) stack: layer loop via lax.scan (small HLO)."""
    cfg = m.cfg
    L = cfg.n_layers
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *[m.blocks[i] for i in range(L)])
    shape = (L, b, cfg.kv_heads, T, cfg.head_dim)
    kc = jnp.zeros(shape, cfg.dtype)
    vc = jnp.zeros(shape, cfg.dtype)
    mesh = _decode_mesh(cfg, b)
    if mesh is not None:
        # KV cache sharded over tp heads + dp batch: the whole decode loop
        # then runs TP-parallel with psum'd attention/MLP outputs
        kv_spec = NamedSharding(mesh, P(None, "dp", "tp", None, None))
        kc = lax.with_sharding_constraint(kc, kv_spec)
        vc = lax.with_sharding_constraint(vc, kv_spec)
        stacked = _shard_stacked(stacked, m.blocks[0], mesh)
    logits, kc, vc = _stacked_forward_cached(m, stacked, tokens, kc, vc, 0)
    rng, k0 = jax.random.split(rng)
    nxt = _sample_token(logits[:, -1].astype(jnp.float32), k0, temperature,
                        top_p, top_k)
    done = jnp.zeros((b,), bool) if eos_id is None else (nxt == eos_id)
    if max_new_tokens == 1:
        return jnp.concatenate([tokens, nxt[:, None]], axis=1)

    def step(carry, _):
        kc, vc, cur, pos, rng, done = carry
        logits, kc, vc = _stacked_decode_rows(
            m, stacked, cur, kc, vc, pos)
        rng, k = jax.random.split(rng)
        nx = _sample_token(logits[:, -1].astype(jnp.float32), k,
                           temperature, top_p, top_k)
        if eos_id is not None:
            nx = jnp.where(done, eos_id, nx)
            done = done | (nx == eos_id)
        return (kc, vc, nx, pos + 1, rng, done), nx

    _, rest = lax.scan(step, (kc, vc, nxt, jnp.int32(s0), rng, done),
                       None, length=max_new_tokens - 1)
    out = jnp.concatenate([nxt[:, None], rest.T], axis=1)
    return jnp.concatenate([tokens, out], axis=1)


_GEN_CACHE = weakref.WeakKeyDictionary()
_GEN_CACHE_MAX = 8  # compiled-executable LRU bound per model

GPT.generate = generate


# ---------------------------------------------------------------------------
# Loss & sharding rules
# ---------------------------------------------------------------------------

def _tp_sharded_vocab(b, s, vocab, d_model=None) -> bool:
    """True when the global mesh tp-shards the vocab axis and every mapped
    dim divides its mesh axes (shard_map's requirement; GSPMD tolerates odd
    shapes, shard_map does not)."""
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is None or _in_pipeline():
        return False
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    fsdp = shape.get("fsdp", 1)
    return (tp > 1 and vocab % tp == 0
            and s % shape.get("sp", 1) == 0
            and b % (shape.get("dp", 1) * fsdp) == 0
            and (d_model is None or d_model % fsdp == 0))


def lm_loss(logits, labels):
    """Causal LM next-token loss; logits (B,S,V) fp32-softmaxed.

    When the mesh tp-shards the vocab axis, dispatches to the
    vocab-parallel CE (mp_ops.parallel_cross_entropy ≙ the reference's
    c_softmax_with_cross_entropy_op.cu) — no device ever materializes a
    full-vocab logit row. Dense path otherwise."""
    b, s, vocab = logits.shape
    if _tp_sharded_vocab(b, s, vocab):
        from paddle_tpu.distributed.mesh import get_mesh
        from paddle_tpu.distributed.mp_ops import parallel_cross_entropy
        # keep shapes sp/tp-divisible: score all S positions, mask the last
        # (its next-token label does not exist) via ignore_index
        shifted = jnp.concatenate(
            [labels[:, 1:], jnp.full((b, 1), -1, labels.dtype)], axis=1)
        tok = parallel_cross_entropy(logits, shifted, mesh=get_mesh(),
                                     ignore_index=-1)
        return jnp.sum(tok) / (b * (s - 1))
    logits = logits[:, :-1].astype(jnp.float32)
    labels = labels[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None],
                                 axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def _use_fused_ce(cfg) -> bool:
    """Route the train loss through the Pallas fused blockwise CE.
    Requires: kernel flag on, dense stack, no multi-device mesh (the
    sharded cases go through parallel_cross_entropy / GSPMD), and a vocab
    with a 128-multiple block divisor."""
    if not _flag("use_pallas_kernels") or cfg.moe_experts > 0:
        return False
    from paddle_tpu.distributed.mesh import get_mesh
    mesh = get_mesh()
    if mesh is not None and mesh.size > 1:
        return False
    from paddle_tpu.ops.pallas.fused_ce import _pick_block_v
    try:
        _pick_block_v(cfg.vocab_size, 512)
    except ValueError:
        return False
    return True


def fused_lm_loss(m: GPT, tokens, rng_key=None, force: bool = False):
    """Causal LM loss with head-LN + LM projection + softmax-CE fused so
    the (B, S, V) logits and their grads never exist in HBM
    (≙ c_softmax_with_cross_entropy_op.cu:38-192 — here the fusion also
    swallows the projection matmul, the reference only fuses the CE).
    Falls back to forward()+lm_loss when the kernel can't engage."""
    if not force and not _use_fused_ce(m.cfg):
        return lm_loss(m(tokens, rng_key=rng_key), tokens)
    from paddle_tpu.ops.pallas.fused_ce import fused_softmax_cross_entropy
    x = m.hidden_states(tokens, rng_key)
    b, s, d = x.shape
    xn = final_ln(x, m.lnf_scale, m.lnf_bias, m.cfg.norm_eps,
                  m.cfg.rms_norm)
    w = m.wte if m.lm_head is None else m.lm_head.T   # (V, d)
    rows = xn[:, :-1].reshape(b * (s - 1), d)
    labels = tokens[:, 1:].reshape(-1)
    per_tok = fused_softmax_cross_entropy(rows, w, labels)
    return jnp.sum(per_tok) / (b * (s - 1))


# (regex on param path → PartitionSpec). Megatron-style TP composed with
# ZeRO-3-style fsdp (ref: mp_layers.py + group_sharded_stage3.py), spelled
# in the canonical SpecLayout vocabulary (distributed.mesh.LAYOUT) so GPT,
# BERT, the planner, and auto_parallel all speak one sharding language.
PARTITION_RULES = (
    (r"wte$", LAYOUT.vocab_embedding()),
    (r"wpe$", LAYOUT.position_table()),
    (r"lm_head$", LAYOUT.vocab_head()),
    (r"wqkv$", LAYOUT.column()),
    (r"bqkv$", LAYOUT.column_bias()),
    (r"wo$", LAYOUT.row()),
    (r"wup$", LAYOUT.column()),
    (r"bup$", LAYOUT.column_bias()),
    (r"wdown$", LAYOUT.row()),
    (r"(bo|bdown)$", LAYOUT.row_bias()),
    (r"(ln1|ln2|lnf)_(scale|bias)$", LAYOUT.norm()),
) + EXPERT_PARTITION_RULES


def partition_spec(path: str) -> P:
    for pat, spec in PARTITION_RULES:
        if re.search(pat, path):
            return spec
    return P()


def param_shardings(params: Dict[str, jax.Array], mesh: Mesh):
    return {k: NamedSharding(mesh, partition_spec(k)) for k in params}


def shard_params(params: Dict[str, jax.Array], mesh: Mesh):
    """Place a param dict onto the mesh per PARTITION_RULES (≙ the moment
    fleet.distributed_model() scatters weights).

    Always copies: device_put may alias when the sharding already matches,
    and the donating train steps would then delete the caller's arrays."""
    shardings = param_shardings(params, mesh)
    return {k: jax.device_put(jnp.copy(v), shardings[k])
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# Train step builders
# ---------------------------------------------------------------------------

def build_train_step(model: GPT, optimizer, mesh: Optional[Mesh] = None,
                     donate: bool = True):
    """One jitted SPMD train step: fwd → loss → bwd → optimizer update.

    Parallelism (dp/fsdp/tp/sp) comes entirely from operand shardings +
    the activation constraints inside the model — XLA inserts all
    collectives (SURVEY §5.8 mapping). ≙ the reference's
    HybridParallelOptimizer.step + EagerReducer allreduce path.

    With PT_NUMERICS_EVERY > 0 (ISSUE 18) the step returns a 4th
    output: the packed numerics vector — per-layer grad AND
    param-update stats over the stacked layer axis plus the NaN
    provenance header — at the configured cadence. Capture reads the
    grads/updates the step already computed, so it cannot perturb the
    update math.
    """
    from paddle_tpu.observability import numerics as _nm
    num_on = _nm.enabled()
    num_box = _nm.LayoutBox()

    def step(params, opt_state, tokens, rng):
        def loss_fn(p):
            m = model.merge_params(p)
            if model.cfg.moe_experts > 0:
                logits, aux = m(tokens, rng_key=rng, return_aux=True)
                return lm_loss(logits, tokens) \
                    + model.cfg.moe_aux_weight * aux
            # dense: fused blockwise CE when it can engage — the (B,S,V)
            # logits never hit HBM (falls back internally otherwise)
            return fused_lm_loss(m, tokens, rng_key=rng)

        # scopes name the compiled operations' metadata (a profile can
        # split the step by them); they cost nothing at run time
        with jax.named_scope("train/loss_and_grad"):
            loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = _nm.poison_grads(grads, step_count=opt_state["step"])
        with jax.named_scope("train/optimizer"):
            new_params, new_state = optimizer.update(grads, opt_state,
                                                     params)
        if num_on:
            updates = jax.tree_util.tree_map(
                lambda n, o: n - o, new_params, params)
            packed = _nm.capture_step(
                grads, loss=loss, updates=updates,
                step_count=opt_state["step"], box=num_box)
            return new_params, new_state, loss, packed
        return new_params, new_state, loss

    kw = {}
    if donate:
        kw["donate_argnums"] = (0, 1)
    fn = jax.jit(step, **kw)
    fn.numerics_layout = num_box
    return fn


def register_stacked_decay_mask(optimizer, template_blk, n_layers: int,
                                name_of, entry: str):
    """Resolve a name-keyed weight-decay mask against the block template
    ONCE and broadcast it along the layer axis: leaf j of the stacked
    block pytree gets an (L, 1, ...) float mask whose layer-l entry is
    ``decay_fn(name_of(l, <param name>))`` — exactly the names the
    per-layer state presents — registered on the optimizer under the
    stacked ``entry`` (`AdamW.set_decay_mask`). Layer-varying decisions
    are preserved (the mask has one row per layer); the common uniform
    case folds into a broadcast at compile time. Shared by the GPT and
    BERT stacked layouts."""
    decay_fn = optimizer.apply_decay_param_fun
    set_mask = getattr(optimizer, "set_decay_mask", None)
    if set_mask is None:
        raise ValueError(
            f"optimizer {type(optimizer).__name__} sets "
            "apply_decay_param_fun but has no set_decay_mask(); the "
            "stacked layout needs the masked update path "
            "(paddle_tpu.optimizer.AdamW)")
    id2name = {id(v): n for n, v in template_blk.named_parameters()}
    tleaves, treedef = jax.tree_util.tree_flatten(template_blk)
    masks = []
    for t in tleaves:
        name = id2name.get(id(t), "")
        col = [float(bool(decay_fn(name_of(i, name))))
               for i in range(n_layers)]
        masks.append(jnp.asarray(col, jnp.float32).reshape(
            (n_layers,) + (1,) * t.ndim))
    set_mask(entry, jax.tree_util.tree_unflatten(treedef, masks))


def _init_opt_state_sharded(optimizer, params, mesh):
    """``optimizer.init`` with every slot placed like the param it
    belongs to. The slots are zeros with no data dependence on the
    params, so a bare ``jit(optimizer.init)`` leaves ALL of them whole
    on the first device (4.9 GiB of bf16 moments at 1.3B): the first
    step then runs on a re-placed copy and the second step, whose
    inputs are the first's sharded outputs, compiles the program
    again. Entries that are not per-param (the step counter) are
    replicated."""
    shapes = jax.eval_shape(optimizer.init, params)
    rep = NamedSharding(mesh, P())
    out = {k: jax.tree_util.tree_map(lambda _: rep, v)
           for k, v in shapes.items() if k != "slots"}
    out["slots"] = jax.tree_util.tree_map(
        lambda p, slot: jax.tree_util.tree_map(
            lambda s: p.sharding if s.shape == p.shape else rep, slot),
        params, shapes["slots"])
    return jax.jit(optimizer.init, out_shardings=out)(params)


def init_train_state(model: GPT, optimizer, mesh: Optional[Mesh] = None,
                     stacked: bool = False):
    """Params + optimizer state, sharded onto the mesh if given.

    ``stacked=True`` (dense models): block weights enter the state
    PRE-stacked along a leading layer axis, under one ``_stacked_blocks``
    key that merge_params binds back onto the model. The scan-over-layers
    forward then reads them directly — without this, the in-trace
    ``stack_block_weights`` materializes a full copy of every block
    weight inside the step (plus the stacked cotangent on the way back),
    which pushed the 1.3B train step past 16GB HBM.

    With a multi-device ``mesh`` the stacked leaves are placed by their
    `stacked_block_specs` (PARTITION_RULES behind a replicated layer
    axis, `LAYOUT.stacked`): the stacking jit emits them directly into
    that layout via out_shardings, so the scan-over-layers fast path and
    hybrid dp/fsdp/tp parallelism compose instead of excluding each
    other. An ``apply_decay_param_fun`` decay mask is resolved against
    the block template once and broadcast along the layer axis
    (`AdamW.set_decay_mask`) — the name-keyed fn itself can't see into
    the folded '_stacked_blocks' entry."""
    if stacked:
        L = model.cfg.n_layers
        if any(model.blocks[i].moe is not None for i in range(L)):
            raise ValueError("MoE stacks are heterogeneous; stacked "
                             "layout needs a dense model")
        params, _ = model.split_params()
        params = {k: v for k, v in params.items()
                  if not k.startswith("blocks.")}
        blocks = [model.blocks[i] for i in range(L)]
        if getattr(optimizer, "apply_decay_param_fun", None) is not None:
            register_stacked_decay_mask(
                optimizer, model.blocks[0], L,
                lambda i, name: f"blocks.item_{i}.{name}",
                "_stacked_blocks")
        if mesh is not None and mesh.size > 1:
            params = shard_params(params, mesh)
            tleaves, treedef, specs = stacked_block_specs(model.blocks[0])
            sh_tree = jax.tree_util.tree_unflatten(
                treedef, [NamedSharding(mesh, mesh_safe_spec(s, mesh))
                          for s in specs])
            # one jit stacks AND places: every stacked leaf lands sharded
            # by its layer-leading spec — never materialized replicated —
            # and its buffers are fresh, so step donation can't free the
            # module's own arrays
            params["_stacked_blocks"] = jax.jit(
                stack_block_weights, out_shardings=sh_tree)(blocks)
            opt_state = _init_opt_state_sharded(optimizer, params, mesh)
        else:
            # jnp.stack allocates fresh buffers, so donation in the train
            # step never frees the module's own arrays
            params = {k: jnp.copy(v) for k, v in params.items()}
            params["_stacked_blocks"] = stack_block_weights(blocks)
            opt_state = optimizer.init(params)
        return params, opt_state
    params, _ = model.split_params()
    if mesh is not None and mesh.size > 1:
        params = shard_params(params, mesh)
        opt_state = _init_opt_state_sharded(optimizer, params, mesh)
    else:
        # copy: the jitted step donates its inputs, and split_params aliases
        # the module's own arrays — donation must not delete those.
        params = {k: jnp.copy(v) for k, v in params.items()}
        opt_state = optimizer.init(params)
    return params, opt_state


# ---------------------------------------------------------------------------
# SPMD pipeline parallelism (GPipe schedule in one XLA program)
# ---------------------------------------------------------------------------

def stack_blocks(model: GPT, n_stages: int):
    """Stack the per-layer block pytrees into one pytree with leading axes
    (n_stages, layers_per_stage, ...). The stage axis is sharded over 'pp'.
    ≙ PipelineLayer._segment_network (parallel_layers/pp_layers.py:550).

    MoE models pipeline too, provided the stack is homogeneous (either
    every block dense or every block MoE — moe_every=1); mixed stacks
    cannot share one stacked pytree. Uneven L % n_stages is handled by
    padding the short stages with masked (skipped) layer slots — use
    stack_blocks_uneven to get the mask.
    """
    stacked, mask = stack_blocks_uneven(model, n_stages)
    if mask is not None:
        raise ValueError(
            f"{model.cfg.n_layers} layers not divisible by {n_stages} "
            f"stages; use stack_blocks_uneven / pass uneven=True to "
            f"init_pipelined_state")
    return stacked


def stack_blocks_uneven(model: GPT, n_stages: int):
    """Like stack_blocks but allows L % n_stages != 0 (≙ the reference's
    seg_method-custom uneven segmentation, pp_layers.py:550): short stages
    are padded by REUSING their first layer's weights under a mask that
    skips the slot at run time (weights must exist for a uniform pytree;
    the mask guarantees they are never applied). Returns (stacked, mask)
    where mask is (n_stages, lps) bool — None when evenly divisible."""
    L = model.cfg.n_layers
    lps = -(-L // n_stages)  # ceil
    counts = [min(lps, L - s * lps) for s in range(n_stages)]
    if any(c <= 0 for c in counts):
        raise ValueError(f"{L} layers over {n_stages} stages leaves an "
                         f"empty stage; reduce n_stages")
    stacked = _stack_block_rows(model, counts, lps, (n_stages,))
    return stacked, layer_slot_mask(L, n_stages)


def _stack_block_rows(model, counts, slots, lead_shape):
    """Stack per-layer block pytrees into groups of ``slots`` layer slots
    (one group per entry in ``counts``), padding short groups by REUSING
    their first layer's weights under a run-time mask, then reshape the
    leading group axis to ``lead_shape``. Shared by the plain and
    interleaved stackings."""
    kinds = {b.moe is not None for b in model.blocks}
    if len(kinds) > 1:
        raise ValueError(
            "pipeline stacking needs homogeneous blocks (all dense or all "
            "MoE, e.g. moe_every=1); mixed dense/MoE stacks cannot stack")
    rows = []
    idx = 0
    for take in counts:
        layer_ids = list(range(idx, idx + take))
        idx += take
        layer_ids += [layer_ids[0]] * (slots - take)  # placeholders, masked
        rows.append([model.blocks[i] for i in layer_ids])
    flat = [b for row in rows for b in row]
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *flat)
    return jax.tree_util.tree_map(
        lambda x: x.reshape(tuple(lead_shape) + (slots,) + x.shape[1:]),
        stacked)


def layer_slot_mask(n_layers: int, n_stages: int):
    """(n_stages, ceil(L/S)) bool mask of real layer slots; None if even."""
    if n_layers % n_stages == 0:
        return None
    lps = -(-n_layers // n_stages)
    counts = [min(lps, n_layers - s * lps) for s in range(n_stages)]
    return jnp.asarray([[i < c for i in range(lps)] for c in counts])


def stack_blocks_interleaved(model: GPT, n_stages: int, n_virtual: int):
    """Interleaved (virtual-stage) stacking: leading axes
    (n_virtual, n_stages, layers_per_global_stage, ...), where pp rank r
    holds the n_virtual chunks {v·S + r} of the S·V-deep global pipeline
    (≙ PipelineParallelWithInterleave's model-chunk assignment,
    fleet/meta_parallel/pipeline_parallel.py:457). Returns
    (stacked, mask) with mask (V, S, lpg) marking real layer slots
    (None when L divides evenly)."""
    L = model.cfg.n_layers
    S, V = n_stages, n_virtual
    G = S * V
    if L < G:
        raise ValueError(f"{L} layers over {G} global stages leaves an "
                         f"empty stage; reduce n_stages or n_virtual")
    counts = _balanced_counts(L, G)
    stacked = _stack_block_rows(model, counts, counts[0], (V, S))
    return stacked, interleaved_slot_mask(L, S, V)


def _balanced_counts(n_layers: int, n_groups: int):
    """Balanced layer counts per group: the first L%G groups get one layer
    more (finer placement than ceil-greedy — the interleave's point)."""
    q, rem = divmod(n_layers, n_groups)
    return [q + 1] * rem + [q] * (n_groups - rem)


def interleaved_slot_mask(n_layers: int, n_stages: int, n_virtual: int):
    """(V, S, lpg) bool mask of real layer slots under the balanced
    interleaved split; None when evenly divisible."""
    G = n_stages * n_virtual
    if n_layers % G == 0:
        return None
    counts = _balanced_counts(n_layers, G)
    lpg = counts[0]
    flat = jnp.asarray([[i < c for i in range(lpg)] for c in counts])
    return flat.reshape(n_virtual, n_stages, lpg)


def pipelined_apply_interleaved(stacked_blocks, x_mb, n_stages: int,
                                n_virtual: int, remat_stages: bool = False,
                                layer_mask=None, collect_aux: bool = False):
    """Virtual-stage (interleaved) rolling-buffer schedule: the buffer has
    one row per GLOBAL stage, shaped (V, S, ...) with the S axis sharded
    over 'pp' — pp rank r owns its V chunk rows. One tick advances every
    live row one global stage; the flat roll (v, S-1) → (v+1, 0) is the
    chunk boundary hop, which stays ON-RANK only for the ring neighbor —
    XLA lowers the whole shift to one collective-permute.

    Honest scheduling note (vs PipelineParallelWithInterleave,
    pipeline_parallel.py:457): inside ONE XLA program the backward is the
    reversed forward scan, so fwd/bwd interleaving — where Megatron's
    bubble ÷V comes from — is the compiler's call, not ours; this variant
    buys finer-grained layer placement (uneven models balance over S·V
    slots instead of S) and halves the per-hop activation dwell time. The
    schedule-owned interleave with real 1F1B overlap is the cross-host
    runtime (distributed/fleet_executor.py, n_virtual>1).
    """
    global _PIPELINE_DEPTH
    n_micro = x_mb.shape[0]
    S, V = n_stages, n_virtual
    G = S * V
    if layer_mask is None:
        lpg = jax.tree_util.tree_leaves(stacked_blocks)[0].shape[2]
        layer_mask = jnp.ones((V, S, lpg), bool)

    def stage_fn(blocks_one_stage, h, mask_one_stage):
        def body(hh, blk_m):
            blk, m = blk_m
            if collect_aux:
                out, aux = _moe_block_with_aux(blk, hh)
                aux = jnp.where(m, aux, 0.0)
            else:
                out = blk(hh)
                aux = jnp.zeros((), jnp.float32)
            hh = jnp.where(m, out, hh)
            return hh, aux
        h, auxs = lax.scan(body, h, (blocks_one_stage, mask_one_stage))
        return h, jnp.sum(auxs)

    if remat_stages:
        stage_fn = jax.checkpoint(stage_fn)

    # per-(v, r) block trees extracted once, outside the tick scan (same
    # adjoint-accumulation reasoning as pipelined_apply)
    row_blocks = [[jax.tree_util.tree_map(lambda x, v=v, r=r: x[v, r],
                                          stacked_blocks)
                   for r in range(S)] for v in range(V)]

    state = jnp.zeros((V, S) + x_mb.shape[1:], x_mb.dtype)
    outputs = jnp.zeros_like(x_mb)
    aux_total = jnp.zeros((), jnp.float32)

    def tick(carry, t):
        state, outputs, aux_total = carry
        inp = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        state = state.at[0, 0].set(inp)
        state = _shard_act(state, P(None, "pp", _BATCH_AXES, "sp", None))
        rows = []
        for v in range(V):
            rank_rows = []
            for r in range(S):
                g = v * S + r
                live = ((t - g) >= 0) & ((t - g) < n_micro)
                h, aux_r = lax.cond(
                    live,
                    lambda h, b=row_blocks[v][r], mk=layer_mask[v, r]:
                        stage_fn(b, h, mk),
                    lambda h: (h, jnp.zeros((), jnp.float32)),
                    state[v, r])
                rank_rows.append(h)
                # live-guarded by the cond's false branch: bubble rows
                # contribute zero aux
                aux_total = aux_total + aux_r
            rows.append(jnp.stack(rank_rows))
        processed = jnp.stack(rows)
        out_t = processed[V - 1, S - 1]
        outputs = lax.cond(
            t >= G - 1,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out_t, jnp.clip(t - (G - 1), 0, n_micro - 1), 0),
            lambda o: o, outputs)
        flat = processed.reshape((G,) + processed.shape[2:])
        state = jnp.roll(flat, 1, axis=0).reshape(state.shape)
        return (state, outputs, aux_total), None

    _PIPELINE_DEPTH += 1
    try:
        (state, outputs, aux_total), _ = lax.scan(
            tick, (state, outputs, aux_total),
            jnp.arange(n_micro + G - 1))
    finally:
        _PIPELINE_DEPTH -= 1
    if collect_aux:
        return outputs, aux_total
    return outputs


def unstack_blocks(stacked, n_layers: int):
    """Inverse of stack_blocks → list of per-layer block pytrees."""
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((n_layers,) + x.shape[2:]), stacked)
    return [jax.tree_util.tree_map(lambda x: x[i], flat)
            for i in range(n_layers)]


def pipelined_apply(stacked_blocks, x_mb, n_stages: int,
                    remat_stages: bool = False, layer_mask=None,
                    collect_aux: bool = False,
                    skip_dead_rows: Optional[bool] = None):
    """GPipe schedule as a rolling buffer over a 'pp'-sharded stage axis.

    x_mb: (n_micro, mb, seq, d) microbatched activations (post-embedding).
    Returns (n_micro, mb, seq, d) outputs of the last stage — or
    (outputs, aux) when collect_aux (MoE load-balance loss summed over all
    real layer applications, bubble rows masked out).

    Stage i's current input lives in row i of `state` (sharded P('pp')); one
    schedule tick = vmapped stage compute (each pp rank runs its own stage —
    rows are independent) + roll(+1) of the buffer, which XLA lowers to a
    collective-permute ring over ICI. Total ticks = n_micro + n_stages - 1;
    the bubble is the same as the reference's 1F1B warmup/cooldown
    (pipeline_parallel.py:117). Backward is jax.grad through the scan — the
    reversed schedule the reference hand-codes.

    layer_mask (n_stages, lps) marks real vs padded layer slots for uneven
    L % n_stages (stack_blocks_uneven); padded slots pass h through.

    remat_stages=True checkpoints each stage's compute, so the backward
    holds only per-tick stage BOUNDARY activations instead of every
    intermediate — the memory profile that motivates the reference's 1F1B
    over GPipe, achieved here with rematerialization instead of schedule
    reordering (in one XLA program the compiler owns the schedule).

    skip_dead_rows: warmup/cooldown rows hold zeros; with a REAL pp mesh
    their compute is free wall-clock (the owning rank idles while live
    ranks set the tick's critical path), so the vmapped stage keeps the
    single-program SPMD shape. WITHOUT a pp mesh (stages time-multiplexed
    on one device — the single-chip bench case) dead rows cost real time;
    this mode unrolls the stage loop with a ``lax.cond`` per row so dead
    ticks skip the FLOPs (VERDICT r2 item 9). Default: auto (skip iff no
    pp>1 mesh axis).
    """
    global _PIPELINE_DEPTH
    n_micro = x_mb.shape[0]
    S = n_stages
    if skip_dead_rows is None:
        from paddle_tpu.distributed.mesh import get_mesh
        m = get_mesh()
        skip_dead_rows = m is None or dict(m.shape).get("pp", 1) == 1
    if layer_mask is None:
        layer_mask = jnp.ones(
            (S, jax.tree_util.tree_leaves(stacked_blocks)[0].shape[1]),
            bool)

    def stage_fn(blocks_one_stage, h, mask_one_stage):
        def body(hh, blk_m):
            blk, m = blk_m
            if collect_aux:
                out, aux = _moe_block_with_aux(blk, hh)
                aux = jnp.where(m, aux, 0.0)
            else:
                out = blk(hh)
                aux = jnp.zeros((), jnp.float32)
            hh = jnp.where(m, out, hh)
            return hh, aux
        h, auxs = lax.scan(body, h, (blocks_one_stage, mask_one_stage))
        return h, jnp.sum(auxs)

    if remat_stages:
        stage_fn = jax.checkpoint(stage_fn)

    vstage = jax.vmap(stage_fn)
    # per-row block trees are extracted ONCE, outside the tick scan: each
    # row's weights then enter the scan as its own constant, so the
    # backward accumulates dW_r directly across ticks. Indexing inside the
    # tick instead would make every tick's adjoint materialize a full
    # (S, ...)-stacked zero buffer per row and scatter dW_r into it — the
    # dominant cost of the measured single-chip pp2 backward overhead.
    if skip_dead_rows:
        row_blocks = [jax.tree_util.tree_map(lambda x, r=r: x[r],
                                             stacked_blocks)
                      for r in range(S)]

    state = jnp.zeros((S,) + x_mb.shape[1:], x_mb.dtype)
    outputs = jnp.zeros_like(x_mb)
    aux_total = jnp.zeros((), jnp.float32)

    def tick(carry, t):
        state, outputs, aux_total = carry
        inp = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
        state = lax.dynamic_update_index_in_dim(state, inp, 0, 0)
        state = _shard_act(state, P("pp", _BATCH_AXES, "sp", None))
        if skip_dead_rows:
            rows, aux_rows = [], []
            for r in range(S):
                live_r = ((t - r) >= 0) & ((t - r) < n_micro)
                h_r, aux_r = lax.cond(
                    live_r,
                    lambda h, b=row_blocks[r], mk=layer_mask[r]:
                        stage_fn(b, h, mk),
                    lambda h: (h, jnp.zeros((), jnp.float32)),
                    state[r])
                rows.append(h_r)
                aux_rows.append(aux_r)
            processed = jnp.stack(rows)
            aux_s = jnp.stack(aux_rows)
        else:
            processed, aux_s = vstage(stacked_blocks, state, layer_mask)
        # row i is live iff its current microbatch index t-i is real
        # (warmup/cooldown rows chew zeros; their aux must not count)
        live = ((t - jnp.arange(S)) >= 0) & ((t - jnp.arange(S)) < n_micro)
        aux_total = aux_total + jnp.sum(jnp.where(live, aux_s, 0.0))
        out_t = processed[-1]
        outputs = lax.cond(
            t >= S - 1,
            lambda o: lax.dynamic_update_index_in_dim(
                o, out_t, jnp.clip(t - (S - 1), 0, n_micro - 1), 0),
            lambda o: o, outputs)
        state = jnp.roll(processed, 1, axis=0)
        return (state, outputs, aux_total), None

    _PIPELINE_DEPTH += 1
    try:
        (state, outputs, aux_total), _ = lax.scan(
            tick, (state, outputs, aux_total),
            jnp.arange(n_micro + S - 1))
    finally:
        _PIPELINE_DEPTH -= 1
    if collect_aux:
        return outputs, aux_total
    return outputs


def _moe_block_with_aux(blk: GPTBlock, x):
    """One MoE block forward returning (out, aux) — used by the pipeline
    where the list-accumulator pattern cannot cross the scan."""
    acc = []
    out = blk(x, aux_acc=acc)
    aux = acc[0] if acc else jnp.zeros((), jnp.float32)
    return out, aux


def pipeline_partition_spec(path: str, n_virtual: int = 1) -> P:
    """Partition spec for a stacked-block param: leading axes (S, lps) —
    or (V, S, lpg) for the interleaved stacking, where only S shards."""
    return LAYOUT.pipeline_stacked(partition_spec(path.split(".")[-1]),
                                   n_virtual)


def build_pipelined_train_step(model: GPT, optimizer, mesh: Mesh,
                               n_stages: int, n_micro: int,
                               remat_stages: bool = False,
                               n_virtual: int = 1):
    """Full hybrid dp×fsdp×tp×sp×pp train step (≙ §3.4 call stack:
    fleet.distributed_model + train_batch + HybridParallelOptimizer.step,
    all fused into one XLA program). ``n_virtual > 1`` uses the
    interleaved virtual-stage buffer (stacked blocks from
    ``stack_blocks_interleaved``)."""
    cfg = model.cfg
    use_moe = cfg.moe_experts > 0
    if n_virtual > 1:
        mask = interleaved_slot_mask(cfg.n_layers, n_stages, n_virtual)
    else:
        mask = layer_slot_mask(cfg.n_layers, n_stages)

    def step(emb_params, stacked_blocks, opt_state, tokens, rng):
        # tokens: (n_micro, mb, seq)
        nm, mb, s = tokens.shape
        def loss_fn(emb_p, blocks_p):
            m = model.merge_params(emb_p)
            x = m.embed(tokens.reshape(nm * mb, s))
            x = x.reshape(nm, mb, s, -1)
            if n_virtual > 1:
                out = pipelined_apply_interleaved(
                    blocks_p, x, n_stages, n_virtual,
                    remat_stages=remat_stages, layer_mask=mask,
                    collect_aux=use_moe)
            else:
                out = pipelined_apply(blocks_p, x, n_stages,
                                      remat_stages=remat_stages,
                                      layer_mask=mask, collect_aux=use_moe)
            x, aux = out if use_moe else (out, 0.0)
            logits = m.head(x.reshape(nm * mb, s, -1))
            loss = lm_loss(logits, tokens.reshape(nm * mb, s))
            if use_moe:
                # normalize: aux accumulated over n_micro microbatches
                loss = loss + cfg.moe_aux_weight * aux / nm
            return loss

        loss, (g_emb, g_blocks) = jax.value_and_grad(
            loss_fn, argnums=(0, 1))(emb_params, stacked_blocks)
        (new_emb, new_blocks), new_state = optimizer.update(
            (g_emb, g_blocks), opt_state, (emb_params, stacked_blocks))
        return new_emb, new_blocks, new_state, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


def init_pipelined_state(model: GPT, optimizer, mesh: Mesh, n_stages: int,
                         n_virtual: int = 1):
    """Split params into (embedding/head dict, pp-stacked blocks) and place
    them on the mesh."""
    params, _ = model.split_params()
    emb_params = {k: v for k, v in params.items()
                  if not k.startswith("blocks.")}
    # jnp.copy: donation in the train step must not delete module arrays
    # (device_put aliases when the sharding already matches)
    emb_params = {k: jax.device_put(
        jnp.copy(v), NamedSharding(mesh, partition_spec(k))) for k, v in
        emb_params.items()}
    if n_virtual > 1:
        stacked, _ = stack_blocks_interleaved(model, n_stages, n_virtual)
    else:
        stacked, _ = stack_blocks_uneven(model, n_stages)
    # `stacked` is itself a GPTBlock pytree (leaves have extra leading
    # axes); place each named param per the pipeline rules.
    for name in sorted(stacked._params):
        arr = getattr(stacked, name)
        object.__setattr__(stacked, name, jax.device_put(
            arr, NamedSharding(mesh,
                               pipeline_partition_spec(name, n_virtual))))
    opt_state = jax.jit(optimizer.init)((emb_params, stacked))
    return emb_params, stacked, opt_state


# ---------------------------------------------------------------------------
# Presets (PaddleNLP gpt-3 family sizes)
# ---------------------------------------------------------------------------

def gpt_tiny(**kw):
    d = dict(vocab_size=256, max_seq_len=64, d_model=64, n_layers=2,
             n_heads=2, dtype=jnp.float32)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_125m(**kw):
    d = dict(d_model=768, n_layers=12, n_heads=12)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_350m(**kw):
    d = dict(d_model=1024, n_layers=24, n_heads=16)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_1p3b(**kw):
    d = dict(d_model=2048, n_layers=24, n_heads=16, max_seq_len=2048)
    d.update(kw)
    return GPTConfig(**d)


def llama_style_1b(**kw):
    """Llama-family shape: rope positions, GQA kv heads, no biases,
    untied head — the modern serving config (the GQA cache is 4x smaller,
    which raises the decode HBM roofline by the same factor)."""
    d = dict(d_model=2048, n_layers=22, n_heads=16, n_kv_heads=4,
             rope=True, use_bias=False, tie_embeddings=False,
             max_seq_len=2048, vocab_size=32000)
    d.update(kw)
    return GPTConfig(**d)
