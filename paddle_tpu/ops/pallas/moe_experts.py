"""Grouped SiLU-gated feed-forward of sparse experts as a Pallas TPU
kernel: the rows of token-expert pairs, laid out expert by expert in
tiles of ``tile`` rows that each belong to ONE expert, go through that
expert's ``gate``, ``up`` and ``down`` matrices.

One program a tile. The tile's expert rides as a scalar-prefetch operand
and picks the three weight blocks (an expert's whole matrices: one
contiguous copy each), so consecutive tiles of one expert copy nothing
and an expert no token chose is never read: a call moves the weights of
the experts that were TOUCHED, once. Tiles past the last used one keep
the last expert's blocks (no copy) and write zeros. The weights may be
the stacks of several layers, ``(layers, E, ...)``, with the layer as a
third scalar: a scan over layers then hands the kernel the stacks as
they are, where a layer cut out of them would be a copy of every expert
(a kernel's operand is materialised).

`plan_tiles` makes the layout from the pairs' experts with no sort (a
one-hot running count gives each pair its place in its expert's run);
`models/expert_layer.py` gathers the rows, calls `moe_experts` and
combines.

Forward-only.
"""

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["moe_experts", "plan_tiles", "n_tiles"]

# an expert's three matrices double-buffered pass the default scoped
# limit of 16 MiB at the widths served (3 x 3.1 MB, twice); a v5e core
# has 128 MiB
_VMEM_LIMIT_BYTES = 64 << 20


def n_tiles(n_pairs: int, n_experts: int, tile: int) -> int:
    """The most tiles ``n_pairs`` pairs over ``n_experts`` experts can
    take: every expert's run rounded up to whole tiles."""
    return -(-n_pairs // tile) + min(n_experts, n_pairs)


def plan_tiles(expert_of_pair, n_experts: int, tile: int):
    """Where each pair goes. ``expert_of_pair`` (N,) int32 in
    ``0 .. n_experts - 1``. Returns ``dest`` (N,): the pair's row in the
    padded layout; ``tile_expert`` (n_tiles,): the expert of each
    tile; ``used``: how many tiles hold rows; ``counts`` (n_experts,):
    pairs an expert."""
    n = expert_of_pair.shape[0]
    tiles = n_tiles(n, n_experts, tile)
    onehot = jax.nn.one_hot(expert_of_pair, n_experts, dtype=jnp.int32)
    counts = jnp.sum(onehot, axis=0)
    place = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=1)
    run_tiles = (counts + tile - 1) // tile
    ends = jnp.cumsum(run_tiles)
    starts = (ends - run_tiles) * tile
    dest = jnp.take(starts, expert_of_pair) + place
    last = jnp.max(jnp.where(counts > 0, jnp.arange(n_experts), 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles), side="right"), last)
    return dest, tile_expert.astype(jnp.int32), ends[-1], counts


def _kernel(expert_ref, used_ref, layer_ref, x_ref, wg_ref, wu_ref, wd_ref,
            o_ref):
    del expert_ref, layer_ref

    @pl.when(pl.program_id(0) < used_ref[0])
    def _rows():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        o_ref[...] = jnp.dot(
            h, wd_ref[...], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)

    @pl.when(pl.program_id(0) >= used_ref[0])
    def _no_rows():
        o_ref[...] = jnp.zeros_like(o_ref)


def moe_experts(x, w_gate, w_up, w_down, tile_expert, used, tile: int,
                layer=None, interpret=None):
    """``x`` (n_tiles * tile, d) rows in the tiled layout; ``w_gate``,
    ``w_up`` (E, d, f) and ``w_down`` (E, f, d), or with ``layer`` (an
    int32 scalar) the stacks (layers, E, ...) of which that layer is
    used; ``tile_expert`` (n_tiles,) and ``used`` as `plan_tiles` gives
    them. Returns the rows' ``(silu(x W_g) * (x W_u)) W_d`` in x's
    dtype, zeros in tiles past ``used``."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    rows, d = x.shape
    f = w_gate.shape[-1]
    by_tile = lambda t, experts, used, layer: (t, 0)
    by_expert = lambda t, experts, used, layer: (layer[0], experts[t], 0, 0)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(rows // tile,),
            in_specs=[pl.BlockSpec((tile, d), by_tile),
                      pl.BlockSpec((None, None, d, f), by_expert),
                      pl.BlockSpec((None, None, d, f), by_expert),
                      pl.BlockSpec((None, None, f, d), by_expert)],
            out_specs=pl.BlockSpec((tile, d), by_tile)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="moe_experts",
        interpret=interpret,
    )(tile_expert, jnp.reshape(used, (1,)).astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), x, w_gate, w_up, w_down)
