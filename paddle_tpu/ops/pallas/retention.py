"""Power retention (Buckman, Gelada, Zhang, "Scaling Context Requires
Rethinking Attention", arXiv:2507.04239) at power 2: the state form's
decode step as a Pallas kernel, and the chunked form that carries the
state through a prompt.

The attention form, for query head ``i`` of key/value group ``j`` and
``s <= t``::

    a_ts = (q_ti . k_sj)^2 * exp(g_(s+1)j + ... + g_tj)
    o_ti = sum_s a_ts v_sj / sum_s a_ts

The state form gives the same numbers from a state of fixed size::

    S_t = exp(g_t) S_(t-1) + phi(k_t) v_t^T      z_t = exp(g_t) z_(t-1) + phi(k_t)
    o_t = S_t^T phi(q_t) / (z_t . phi(q_t))      phi(x) . phi(y) = (x . y)^2

**Layout of phi and of the state.** ``phi(x)`` holds every unordered pair
``x_a x_b`` once. Here the pairs are ordered by their cyclic distance
``d = (b - a) mod D``: entry ``d * D + a`` is ``c_d x_a x_((a + d) mod D)``
for ``d = 0 .. D/2``, with ``c_0 = 1``, ``c_d = sqrt(2)`` for ``0 < d <
D/2``, and ``c_(D/2) = 1`` because at that distance every pair turns up
twice (from ``a`` and from ``a + D/2``). That is ``(D/2 + 1) * D`` entries
(8,320 at ``D`` 128 against the 8,256 distinct pairs: 64 doubled ones),
each block of ``D`` a lane-aligned product of ``x`` with a rotation of
itself. The state is kept transposed, ``S^T``: ``(value dim, phi dim)``,
so that every ``phi`` vector lies along the lanes and the value vector
along the sublanes, and the normaliser ``z`` as one row of the same
width.

The pools hold every layer and every slot: ``S`` is ``(layers, slots,
kv heads, D, phi_dim)`` and ``z`` ``(layers, slots, kv heads, 1,
phi_dim)``, float32. `retention_step` takes both WHOLE, once, aliased in
to out, and its index maps pick the layer and the live slots: nothing
slices or copies a pool (PERF.md section 5: an operand handed in twice
made XLA copy the KV pools, 84% of a step).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["phi_dim", "phi", "state_shapes", "retention_step",
           "retention_step_reference", "retention_chunk",
           "retention_sequence"]

OUT_LANES = 128                 # the step kernel's output tile is (D, 128)
_VMEM_LIMIT = 32 * 1024 * 1024


def phi_dim(head_dim: int) -> int:
    if head_dim % 2:
        raise ValueError("power retention needs an even head_dim")
    return (head_dim // 2 + 1) * head_dim


def phi(x):
    """(..., D) -> (..., phi_dim(D)) in ``x``'s type: the layout above."""
    d = x.shape[-1]
    nb = d // 2 + 1
    idx = (jnp.arange(nb)[:, None] + jnp.arange(d)[None, :]) % d
    c = jnp.full((nb, 1), math.sqrt(2.0), x.dtype)
    c = c.at[0].set(1.0).at[nb - 1].set(1.0)
    out = c * x[..., None, :] * x[..., idx]
    return out.reshape(*x.shape[:-1], nb * d)


def state_shapes(n_layers, slots, kv_heads, head_dim):
    """The two per-sequence pools of a stack of retention layers."""
    dp = phi_dim(head_dim)
    return {
        "S": jax.ShapeDtypeStruct(
            (n_layers, slots, kv_heads, head_dim, dp), jnp.float32),
        "z": jax.ShapeDtypeStruct(
            (n_layers, slots, kv_heads, 1, dp), jnp.float32),
    }


# ------------------------------------------------------------ the decode step
def _step_kernel(ids_ref, nlive_ref, _layer_ref, dec_ref, v_ref, pk_ref,
                 pq_ref, s_ref, z_ref, o_ref, so_ref, zo_ref, acc_ref,
                 den_ref, *, d, tb, group, tiles):
    i, j, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_live = nlive_ref[0]

    @pl.when(jnp.logical_and(n_live == 0,
                             jnp.logical_and(i == 0, (j + t) == 0)))
    def _pass_through():
        # no live slot: every step of the grid maps to one block, which
        # is written back once, so it has to hold what was there
        so_ref[...] = s_ref[...]
        zo_ref[...] = z_ref[...]

    @pl.when(i < n_live)
    def _live():
        @pl.when(t == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            den_ref[...] = jnp.zeros_like(den_ref)

        a = dec_ref[0, 0]                       # (1, 1) decay exp(g)
        vcol = v_ref[0, 0]                      # (d, 1)
        for b in range(tb):
            sl = slice(b * d, (b + 1) * d)
            pk = pk_ref[0, 0, :, sl]            # (1, d)
            so_ref[0, 0, 0, :, sl] = a * s_ref[0, 0, 0, :, sl] + vcol * pk
            zo_ref[0, 0, 0, :, sl] = a * z_ref[0, 0, 0, :, sl] + pk
        for h in range(group):
            num = jnp.zeros((d, d), jnp.float32)
            den = jnp.zeros((1, d), jnp.float32)
            for b in range(tb):
                sl = slice(b * d, (b + 1) * d)
                pq = pq_ref[0, 0, h:h + 1, sl]  # (1, d)
                num = num + so_ref[0, 0, 0, :, sl] * pq
                den = den + zo_ref[0, 0, 0, :, sl] * pq
            acc_ref[h] += num
            den_ref[h] += den

        @pl.when(t == tiles - 1)
        def _finish():
            lane = jax.lax.broadcasted_iota(jnp.int32, (d, OUT_LANES), 1)
            out = jnp.zeros((d, OUT_LANES), jnp.float32)
            for h in range(group):
                num = jnp.sum(acc_ref[h], axis=1, keepdims=True)  # (d, 1)
                den = jnp.sum(den_ref[h], axis=1, keepdims=True)  # (1, 1)
                out = jnp.where(lane == h, num / den, out)
            o_ref[0, 0] = out


def _tile_blocks(nb: int) -> int:
    """Blocks of ``D`` lanes per grid step: the largest divisor of the
    block count up to 13 (65 blocks at D 128: five tiles of 13, 852 KB
    of state each way)."""
    return max(t for t in range(1, 14) if nb % t == 0)


def _live_order(active):
    """Live slots first, then the last live slot repeated: the grid's
    trailing steps then map to the block the last live step held, and
    move nothing."""
    n_live = jnp.sum(active).astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(active), stable=True).astype(
        jnp.int32)
    last = order[jnp.maximum(n_live - 1, 0)]
    ids = jnp.where(jnp.arange(active.shape[0]) < n_live, order, last)
    return ids, n_live.reshape(1)


def retention_step(q, k, v, g, S, z, layer, active, interpret=None):
    """One token of every live slot through one layer, in place.

    q (B, H, D), k and v (B, Hkv, D), g (B, Hkv) the log of the gate
    (<= 0); ``S`` / ``z`` the WHOLE pools (`state_shapes`), of which
    layer ``layer`` (a traced scalar) of each slot with ``active`` set is
    decayed, updated with ``phi(k) v^T`` and read with the group's query
    heads. Slots not active are skipped: their state is not read, not
    written and costs no time; their rows of the output are undefined.
    Returns (o (B, H, D) float32, S, z)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, hq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    dp = phi_dim(d)
    nb = dp // d
    tb = _tile_blocks(nb)
    tiles = nb // tb
    f32 = jnp.float32
    pq = phi(q.astype(f32)).reshape(b, hkv, group, dp)
    pk = phi(k.astype(f32)).reshape(b, hkv, 1, dp)
    dec = jnp.exp(g.astype(f32)).reshape(b, hkv, 1, 1)
    vcol = v.astype(f32).reshape(b, hkv, d, 1)
    ids, n_live = _live_order(active)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def row(i, j, t, ids, n, layer):            # per (slot, kv head)
        return (ids[i], jnp.where(i < n[0], j, hkv - 1), 0, 0)

    def tile(i, j, t, ids, n, layer):           # ... and tile of phi
        live = i < n[0]
        return (ids[i], jnp.where(live, j, hkv - 1), 0,
                jnp.where(live, t, tiles - 1))

    def pool(i, j, t, ids, n, layer):
        return (layer[0],) + tile(i, j, t, ids, n, layer)

    s_spec = pl.BlockSpec((1, 1, 1, d, tb * d), pool)
    z_spec = pl.BlockSpec((1, 1, 1, 1, tb * d), pool)
    o, S, z = pl.pallas_call(
        functools.partial(_step_kernel, d=d, tb=tb, group=group,
                          tiles=tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, hkv, tiles),
            in_specs=[pl.BlockSpec((1, 1, 1, 1), row),
                      pl.BlockSpec((1, 1, d, 1), row),
                      pl.BlockSpec((1, 1, 1, tb * d), tile),
                      pl.BlockSpec((1, 1, group, tb * d), tile),
                      s_spec, z_spec],
            out_specs=[pl.BlockSpec((1, 1, d, OUT_LANES), row),
                       s_spec, z_spec],
            scratch_shapes=[pltpu.VMEM((group, d, d), f32),
                            pltpu.VMEM((group, 1, d), f32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, d, OUT_LANES), f32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        # operands count the three scalar-prefetch refs: the pools are 7
        # and 8, each handed in once and aliased to its output
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="retention_step",
        interpret=interpret,
    )(ids, n_live, layer, dec, vcol, pk, pq, S, z)
    o = jnp.swapaxes(o[..., :group], 2, 3).reshape(b, hq, d)
    return o, S, z


def retention_step_reference(q, k, v, g, S, z, layer, active):
    """`retention_step` in plain ``jax.numpy`` (the kernels' tests compare
    with it; XLA's form of it copies the pool, so nothing serves it)."""
    b, hq, d = q.shape
    hkv = k.shape[1]
    f32 = jnp.float32
    pq = phi(q.astype(f32)).reshape(b, hkv, hq // hkv, -1)
    pk = phi(k.astype(f32))
    a = jnp.exp(g.astype(f32))[..., None, None]
    s_new = a * S[layer] + v.astype(f32)[..., :, None] * pk[..., None, :]
    z_new = a * z[layer] + pk[..., None, :]
    num = jnp.einsum("bjgp,bjvp->bjgv", pq, s_new)
    den = jnp.einsum("bjgp,bjp->bjg", pq, z_new[..., 0, :])
    keep = active[:, None, None, None]
    S = S.at[layer].set(jnp.where(keep, s_new, S[layer]))
    z = z.at[layer].set(jnp.where(keep, z_new, z[layer]))
    return (num / den[..., None]).reshape(b, hq, d), S, z


# ------------------------------------------------------------ the chunked form
def retention_chunk(q, k, v, g, S, z, n_valid=None):
    """One chunk of one sequence: the tokens attend to each other in the
    attention form and to what came before through the carried state,
    which is then decayed over the chunk and updated.

    q (C, H, D), k and v (C, Hkv, D), g (C, Hkv) float32 logs of the
    gates, S (Hkv, D, phi_dim) and z (Hkv, 1, phi_dim) float32. Tokens
    from ``n_valid`` on are padding: they add nothing to the state and
    their outputs are undefined. The matrix products take their operands
    in ``q``'s type and accumulate in float32. Returns (o (C, H, D)
    float32, S, z)."""
    with jax.named_scope("retention_chunk"):
        c, hq, d = q.shape
        hkv = k.shape[1]
        group = hq // hkv
        f32, dt = jnp.float32, q.dtype
        valid = (jnp.arange(c) < (c if n_valid is None else n_valid))
        g = jnp.where(valid[:, None], g.astype(f32), 0.0)
        bcum = jnp.cumsum(g, axis=0)                       # (C, Hkv)
        qg = q.reshape(c, hkv, group, d)
        dot = functools.partial(jnp.einsum, preferred_element_type=f32)
        # inside the chunk: (q . k)^2 decayed from s to t, s <= t
        qk = dot("tjgd,sjd->jgts", qg, k)
        lag = bcum.T[:, :, None] - bcum.T[:, None, :]      # (Hkv, t, s)
        causal = jnp.tril(jnp.ones((c, c), bool)) & valid[None, :]
        w = jnp.where(causal, jnp.exp(jnp.where(causal, lag, 0.0)), 0.0)
        a = qk * qk * w[:, None]
        num = dot("jgts,sjd->tjgd", a.astype(dt), v)
        den = jnp.sum(a, axis=-1).transpose(2, 0, 1)       # (t, Hkv, g)
        # what came before the chunk, through the state
        pq = phi(qg.astype(f32)).astype(dt)                # (C,Hkv,g,P)
        carry = jnp.exp(bcum)[:, :, None]                  # (C, Hkv, 1)
        num = num + carry[..., None] * dot("tjgp,jdp->tjgd", pq,
                                           S.astype(dt))
        den = den + carry * dot("tjgp,jp->tjg", pq, z[:, 0].astype(dt))
        o = (num / den[..., None]).reshape(c, hq, d)
        # the state after the chunk
        total = bcum[-1]                                   # (Hkv,)
        keep = jnp.where(valid[:, None], jnp.exp(total[None] - bcum), 0.0)
        pk = phi(k.astype(f32))                            # (C, Hkv, P)
        vw = (v.astype(f32) * keep[..., None]).astype(dt)
        S = jnp.exp(total)[:, None, None] * S + dot(
            "sjd,sjp->jdp", vw, pk.astype(dt))
        z = jnp.exp(total)[:, None, None] * z + jnp.sum(
            pk * keep[..., None], axis=0)[:, None]
        return o, S, z


def retention_sequence(q, k, v, g, chunk: int = 512):
    """A whole sequence from an empty state, chunk by chunk (the model's
    full forward: training and the tests). q (T, H, D) ...; returns
    o (T, H, D) float32."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    chunk = min(chunk, t)
    n = -(-t // chunk)
    pad = n * chunk - t
    cut = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)) \
        .reshape(n, chunk, *x.shape[1:])
    shapes = state_shapes(1, 1, hkv, d)
    init = (jnp.zeros(shapes["S"].shape[2:], jnp.float32),
            jnp.zeros(shapes["z"].shape[2:], jnp.float32))

    def one(carry, xs):
        qc, kc, vc, gc, i = xs
        o, S, z = retention_chunk(qc, kc, vc, gc, *carry,
                                  n_valid=t - i * chunk)
        return (S, z), o

    _, o = jax.lax.scan(one, init, (cut(q), cut(k), cut(v), cut(g),
                                    jnp.arange(n)))
    return o.reshape(n * chunk, hq, d)[:t]
