"""A DROPLESS sparse-expert feed-forward with a shared expert: the expert
layer of DeepSeek-V3-shaped decoders, on the serving path
(`incubate/moe.py` is the GShard capacity form, which drops tokens and
stays for training).

For a token ``x`` (the normed residual): scores ``s = sigmoid(x W_r)``
over ALL ``n_experts`` experts in float32; the
``per_token`` experts with the largest ``s + b`` are chosen (``b``: the
selection bias, which selects and does not weigh); their weights are
``scale * s_e / sum of the chosen s``; the layer returns
``sum_e w_e FFN_e(x) + FFN_shared(x)``, every FFN SiLU-gated. No token is
ever dropped: the token-expert pairs are laid out expert by expert
(`ops/pallas/moe_experts.py`), multiplied group by group, and brought
back.

The layer holds every expert (a range of them, for a chip's share of a
layer, comes with the expert-parallel step that needs it).
"""

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.nn.module import Module, Parameter
from paddle_tpu.ops.pallas import moe_experts as kernel

__all__ = ["ExpertLayer", "route"]


def route(x, w_router, bias, per_token: int, scale: float):
    """(T, d) tokens -> (experts (T, k) int32, weights (T, k) float32).
    The product, the scores and the choice are float32 (a tie goes to
    the expert of the lower index)."""
    logits = jnp.matmul(x.astype(jnp.float32), w_router.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(s + bias.astype(jnp.float32), per_token)
    chosen = jnp.take_along_axis(s, experts, axis=-1)
    weights = scale * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), weights


class ExpertLayer(Module):
    def __init__(self, d_model: int, width: int, n_experts: int,
                 per_token: int, key, *, n_shared: int = 1,
                 scale: float = 1.0, dtype=jnp.bfloat16,
                 std: float = 0.02, down_std: float = 0.02):
        super().__init__()
        self.n_experts, self.per_token = n_experts, per_token
        self.scale = float(scale)
        ks = jax.random.split(key, 7)
        draw = lambda k, shape, s, dt=dtype: (
            s * jax.random.normal(k, shape)).astype(dt)
        e, d, f, fs = n_experts, d_model, width, width * n_shared
        # the router and its selection bias stay float32
        self.w_router = Parameter(draw(ks[0], (d, n_experts), std,
                                       jnp.float32))
        self.router_bias = Parameter(jnp.zeros((n_experts,), jnp.float32))
        self.w_gate = Parameter(draw(ks[1], (e, d, f), std))
        self.w_up = Parameter(draw(ks[2], (e, d, f), std))
        self.w_down = Parameter(draw(ks[3], (e, f, d), down_std))
        self.ws_gate = Parameter(draw(ks[4], (d, fs), std))
        self.ws_up = Parameter(draw(ks[5], (d, fs), std))
        self.ws_down = Parameter(draw(ks[6], (fs, d), down_std))

    def forward(self, x, stacks=None):
        """``x`` (..., d) -> (the layer's sum, same shape; how many of
        the experts some token chose, int32; the experts each token
        chose, (tokens, k) int32).
        ``stacks``: ``(w_gate, w_up, w_down, layer)``, the experts of
        several layers stacked and which of them this is, in place of
        the layer's own three (a scan over layers hands the kernel the
        stacks as they are: `ops/pallas/moe_experts.py`)."""
        (out,), touched, (experts,) = self.forward_sets((x,), stacks)
        return out, touched, experts

    def forward_sets(self, xs, stacks=None):
        """`forward` of several sets of tokens in one call: each set is
        routed, combined and given the shared expert as it would be
        alone, and the routed experts run as ONE kernel call over the
        token-expert pairs of every set, so that each expert some token
        chose is read once for all of them. Returns the sets' sums, how
        many experts some token of any set chose, and each set's
        experts."""
        w_gate, w_up, w_down, layer = (
            (self.w_gate, self.w_up, self.w_down, None) if stacks is None
            else stacks)
        flat = [x.reshape(-1, x.shape[-1]) for x in xs]
        k = self.per_token
        with jax.named_scope("moe_route"):
            routes = [route(x, self.w_router, self.router_bias, k,
                            self.scale) for x in flat]
            x = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
            experts = (routes[0][0] if len(flat) == 1 else
                       jnp.concatenate([e for e, _ in routes]))
            t = x.shape[0]
            # few rows a tile while every expert sees a handful of
            # tokens (the product is bound by the weights' bytes), more
            # once runs grow
            tile = 16 if t * k <= 1024 else 32
            dest, tile_expert, used, counts = kernel.plan_tiles(
                experts.reshape(-1), self.n_experts, tile)
            rows = tile_expert.shape[0] * tile
            # a row that holds no pair reads past the tokens: zeros
            src = jnp.full((rows,), t, jnp.int32).at[dest].set(
                jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
            x_rows = jnp.take(x, src, axis=0, mode="fill", fill_value=0)
        with jax.named_scope("moe_experts"):
            y_rows = kernel.moe_experts(x_rows, w_gate, w_up, w_down,
                                        tile_expert, used, tile, layer)
        outs, at = [], 0
        for x, (_, weights) in zip(flat, routes):
            n = x.shape[0]
            with jax.named_scope("moe_combine"):
                y = jnp.take(y_rows, dest if len(flat) == 1
                             else dest[at * k:(at + n) * k], axis=0)
                out = jnp.sum((y.astype(jnp.float32)
                               * weights.reshape(-1)[:, None]).reshape(
                    n, k, -1), axis=1)
            with jax.named_scope("moe_shared"):
                h = jax.nn.silu(x @ self.ws_gate) * (x @ self.ws_up)
                outs.append(out + (h @ self.ws_down).astype(jnp.float32))
            at += n
        touched = jnp.sum(counts > 0).astype(jnp.int32)
        return ([out.astype(x.dtype).reshape(x.shape)
                 for out, x in zip(outs, xs)], touched,
                [e for e, _ in routes])
