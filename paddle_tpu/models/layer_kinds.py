"""Layer kinds: what a serving engine and its cache manager need to know
about a layer, in one description (ROADMAP D2's form).

A kind says what state its layers keep — keys and values per TOKEN, in
pages of the shared pool, or a state of fixed size per SEQUENCE, in a
slot of a state pool — and brings the two functions that read and write
that state: ``prefill`` (a run of prompt tokens of one sequence) and
``step`` (one token of every live slot). The parameters are the block's
own (`models/gpt.py` ``GPTBlock``: the kind's name is ``GPTConfig.mixer``),
and the halves every kind shares stay there: ``_mix_inputs`` (norm, q/k/v,
q/k norm, rotary positions, the gate) before and ``_block_tail`` (output
projection, feed-forward) after.

`inference/paged_engine.py` reads a kind for: whether to hold a page pool
at all (``pages``), which per-slot pools to allocate, zero on admission
and free on release (``slot_state``), whether a prompt of any length can
be prefilled in chunks (``chunked_prefill``), and the two functions.

``SOFTMAX`` (the dense decoder's attention) is the first instance,
``RETENTION`` (power retention, `ops/pallas/retention.py`) the second,
``LATENT`` (multi-head latent attention,
`ops/pallas/latent_attention.py`) the third: its pages hold ONE row a
token (no head axis) in ONE pool of ``(pages, row, page)``, it prefills a prompt of any length in
chunks that attend to the pages written before them, in the expanded
form, and it steps in the absorbed form, which expands nothing.

Both functions take the arrays a layer may update as one dict,
``pools`` (the layer-folded page pools, ``kp``/``vp`` or the kind's own
as ``page_pools`` names them; and the kind's slot pools, WHOLE: a layer
picks its own part by index), and return it.
"""

import dataclasses
import math
from typing import Callable

import jax.numpy as jnp
from jax import lax

__all__ = ["LayerKind", "SOFTMAX", "RETENTION", "LATENT", "kind_of"]


@dataclasses.dataclass(frozen=True)
class LayerKind:
    name: str
    # keys and values of every token, in pages of the shared pool
    pages: bool
    # (cfg, slots) -> {pool name: ShapeDtypeStruct}: state per sequence
    slot_state: Callable
    # a prompt of any length, a chunk at a time, the state carried
    chunked_prefill: bool
    # chunked: (blk, layer, h, pos0, n_valid, slot, first, pools, view)
    # -> (mixed tokens, pools), one chunk of one slot's prompt (``view``:
    # the slot's pages, None for a kind without); else (blk, h) ->
    # (mixed tokens, rows to page), a whole prompt at once
    prefill: Callable
    # (blk, layer, h, lengths, active, pools, view) -> (mixed, pools)
    step: Callable
    # (cfg, rows, page) -> {pool name: ShapeDtypeStruct}: the page pools
    # of ``rows`` pages each (every layer's pages and the scratch page)
    page_pools: Callable = lambda cfg, rows, page: {}


# ------------------------------------------------------------------ softmax
def _softmax_prefill(blk, h):
    """A whole prompt (1, bucket, d) attends to itself, causally: no
    cache is read. Returns the mixed tokens and the rows of keys and
    values (bucket, Hkv, D) for the engine to write into pages."""
    from paddle_tpu.nn import functional as F
    q, k, v = blk._qkv(h, jnp.zeros((1,), jnp.int32))
    attn = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                          dropout_p=0.0)
    return attn.reshape(h.shape).astype(h.dtype), (k[0], v[0])


def _softmax_step(blk, layer, h, lengths, active, pools, view):
    """One token a slot: the fresh row of keys and values is merged into
    its page in place, then the attend runs over the pools at
    ``lengths + 1`` (`paged_append_attend`: two launches, each pool
    handed in once). ``view``: the page ``table``, each slot's write
    page ``base``, the pool's ``n_pages`` a layer and the ``scratch``
    page that slots not active write to."""
    from paddle_tpu.ops.pallas.paged_attention import paged_append_attend
    kp, vp = pools["kp"], pools["vp"]
    q, k, v = blk._qkv(h, lengths)
    fold = layer * view["n_pages"]
    wpids = jnp.where(active, fold + view["base"], view["scratch"])
    o, kp, vp = paged_append_attend(
        q[:, 0].astype(kp.dtype), kp, vp, k[:, 0].astype(kp.dtype),
        v[:, 0].astype(vp.dtype), fold + view["table"], wpids, lengths,
        scale=1.0 / math.sqrt(blk.head_dim))
    return o.astype(h.dtype).reshape(h.shape), dict(pools, kp=kp, vp=vp)


def _softmax_pools(cfg, rows, page):
    import jax
    sds = jax.ShapeDtypeStruct((rows, cfg.kv_heads, page, cfg.head_dim),
                               cfg.dtype)
    return {"kp": sds, "vp": sds}


SOFTMAX = LayerKind(
    name="softmax", pages=True, slot_state=lambda cfg, slots: {},
    chunked_prefill=False, prefill=_softmax_prefill, step=_softmax_step,
    page_pools=_softmax_pools)


# ---------------------------------------------------------------- retention
def _retention_state(cfg, slots):
    from paddle_tpu.ops.pallas.retention import state_shapes
    return state_shapes(cfg.n_layers, slots, cfg.kv_heads, cfg.head_dim)


def _retention_prefill(blk, layer, h, pos0, n_valid, slot, first, pools,
                       view):
    """One chunk (1, C, d) of slot ``slot``'s prompt, at positions from
    ``pos0``, of which the first ``n_valid`` tokens are real: the chunked
    form, from the slot's state (from zero where ``first``: admission
    zeroes a slot by not reading it) to the slot's state."""
    from paddle_tpu.ops.pallas.retention import retention_chunk
    q, k, v, g = blk._mix_inputs(h, jnp.reshape(pos0, (1,)))
    S, z = pools["S"], pools["z"]
    at = (layer, slot, 0, 0, 0)
    s0 = lax.dynamic_slice(S, at, (1, 1) + S.shape[2:])[0, 0]
    z0 = lax.dynamic_slice(z, at, (1, 1) + z.shape[2:])[0, 0]
    s0 = jnp.where(first, 0.0, s0)
    z0 = jnp.where(first, 0.0, z0)
    o, s1, z1 = retention_chunk(q[0], k[0], v[0], g[0], s0, z0, n_valid)
    S = lax.dynamic_update_slice(S, s1[None, None], at)
    z = lax.dynamic_update_slice(z, z1[None, None], at)
    return o.astype(h.dtype).reshape(h.shape), dict(pools, S=S, z=z)


def _retention_step(blk, layer, h, lengths, active, pools, view):
    """One token a slot through `retention_step`: the state of each
    active slot is decayed, updated and read in place; a slot not active
    (idle, or still in prefill) is not touched."""
    from paddle_tpu.ops.pallas.retention import retention_step
    q, k, v, g = blk._mix_inputs(h, lengths)
    o, S, z = retention_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             pools["S"], pools["z"], layer, active)
    return o.astype(h.dtype).reshape(h.shape), dict(pools, S=S, z=z)


RETENTION = LayerKind(
    name="retention", pages=False, slot_state=_retention_state,
    chunked_prefill=True, prefill=_retention_prefill,
    step=_retention_step)


# ------------------------------------------------------------------- latent
def _latent_pools(cfg, rows, page):
    import jax
    # a page keeps its tokens along the lanes
    # (`ops/pallas/latent_attention.py` says why)
    return {"cp": jax.ShapeDtypeStruct((rows, cfg.latent_row, page),
                                       cfg.dtype)}


def _latent_prefill(blk, layer, h, pos0, n_valid, slot, first, pools,
                    view):
    """One chunk (1, C, d) of one slot's prompt at positions from
    ``pos0`` (a multiple of the page): its latent rows go into the
    slot's pages (``view["table_row"]``), then it attends, in the
    EXPANDED form, to the pages before it and to itself."""
    import jax
    from paddle_tpu.ops.pallas.latent_attention import latent_chunk_attend
    q_nope, q_rope, row = blk._latent_inputs(h, jnp.reshape(pos0, (1,)))
    with jax.named_scope("mla_prefill"):
        o, cp = latent_chunk_attend(
            q_nope[0], q_rope[0], row[0], pools["cp"], blk._wkv_b_heads(),
            view["table_row"], layer * view["n_pages"], view["scratch"],
            pos0, n_valid, blk.kv_rank, blk.latent_scale)
    return o.astype(h.dtype).reshape(1, h.shape[1], -1), dict(pools, cp=cp)


def _latent_step(blk, layer, h, lengths, active, pools, view):
    """One token a slot in the ABSORBED form: the key half of ``wkv_b``
    goes into the query, the fresh row into its page, the attend over
    the slot's live rows as they are (`latent_append_attend`), and the
    value half of ``wkv_b`` onto the weighted sums."""
    import jax
    from paddle_tpu.ops.pallas.latent_attention import latent_append_attend
    q_nope, q_rope, row = blk._latent_inputs(h, lengths)
    with jax.named_scope("mla_step"):
        w = blk._wkv_b_heads()
        q_lat = jnp.einsum("shd,chd->shc", q_nope[:, 0], w[..., :blk.nope],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(h.dtype), q_rope[:, 0]], axis=-1)
        fold = layer * view["n_pages"]
        wpids = jnp.where(active, fold + view["base"], view["scratch"])
        o_lat, cp = latent_append_attend(
            q, pools["cp"], row[:, 0], fold + view["table"], wpids,
            lengths, blk.kv_rank, blk.latent_scale)
        o = jnp.einsum("shc,chd->shd", o_lat, w[..., blk.nope:],
                       preferred_element_type=jnp.float32)
    return (o.astype(h.dtype).reshape(h.shape[0], 1, -1),
            dict(pools, cp=cp))


LATENT = LayerKind(
    name="latent", pages=True, slot_state=lambda cfg, slots: {},
    chunked_prefill=True, prefill=_latent_prefill, step=_latent_step,
    page_pools=_latent_pools)


def kind_of(cfg) -> LayerKind:
    """The kind of every layer of ``cfg``'s stack (one kind a model: the
    period of the layer pattern is 1)."""
    return {"softmax": SOFTMAX, "retention": RETENTION,
            "latent": LATENT}[cfg.mixer]
