"""What the expert models share: the dense gated MLP, the routed experts
a chip holds with their router's state, and what a training step reports
of that state.  ``glm4_moe_lite`` and ``lfm2_moe`` import these; neither
keeps a copy.

The router balances by a selection bias, not by a loss term: bias and
the step's load sit in the :data:`ROUTER_COLLECTION` collection and are
updated in the training step, as batch-norm statistics are.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..parallel.ep import compact_rows, held_experts_apply, sigmoid_route
from .transformer_lm import swiglu_mlp

__all__ = ["ExpertMLP", "SwiGLU", "ROUTER_COLLECTION", "router_step_metrics"]

#: the flax collection of the routers' selection bias and step load
ROUTER_COLLECTION = "router"


class SwiGLU(nn.Module):
    """The dense gated MLP (and a shared expert) in a scope of its
    own: ``gate``, ``up``, ``down`` as in ``DecoderBlock(mlp="swiglu")``."""

    mlp_dim: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        return swiglu_mlp(x, self.mlp_dim, self.dtype)


class ExpertMLP(nn.Module):
    """The routed experts held here; the router's bias and load."""

    moe_dim: int
    n_routed_experts: int
    experts_held: Tuple[int, int]
    top_k: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    bias_update_rate: float = 0.001
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        e, (first, held) = self.n_routed_experts, self.experts_held
        if not (0 <= first and held >= 1 and first + held <= e):
            raise ValueError(
                f"experts_held {self.experts_held} is not a range of the "
                f"{e} routed experts")
        d, m = x.shape[-1], self.moe_dim
        init = nn.initializers.lecun_normal(in_axis=-2, out_axis=-1,
                                            batch_axis=(0,))
        router = self.param("router", nn.initializers.lecun_normal(), (d, e),
                            jnp.float32)
        w_gate = self.param("w_gate", init, (held, d, m), jnp.float32)
        w_up = self.param("w_up", init, (held, d, m), jnp.float32)
        w_down = self.param("w_down", init, (held, m, d), jnp.float32)
        bias = self.variable(ROUTER_COLLECTION, "bias",
                             lambda: jnp.zeros((e,), jnp.float32))
        load = self.variable(ROUTER_COLLECTION, "load",
                             lambda: jnp.zeros((e,), jnp.float32))
        toks = x.reshape(-1, d)
        with jax.named_scope("fdtpu/moe_route"):
            chosen, weights, count = sigmoid_route(
                toks, router, bias.value, top_k=self.top_k,
                scale=self.routed_scaling_factor,
                normalize=self.norm_topk_prob)
        if train and not self.is_initializing():
            count = jax.lax.stop_gradient(count)
            load.value = count
            bias.value = bias.value + self.bias_update_rate * jnp.sign(
                jnp.mean(count) - count)
        with jax.named_scope("fdtpu/moe_experts"):
            y = held_experts_apply(toks.astype(self.dtype), chosen, weights,
                                   w_gate, w_up, w_down, e, first=first)
        return y.reshape(x.shape)


def router_step_metrics(model_state, experts_held: Optional[Tuple[int, int]],
                        n_routed_experts: int) -> dict:
    """Of the state a training step leaves: each router's load
    ``moe_load`` [routers, experts]; over all routers the token-slots of
    the experts held here and of the absent ones (``moe_slots``) and
    those that found no row (``moe_dropped``: nought, since a step that
    overflows every bounded rung of ``held_experts_apply``'s buffer
    takes the one with a row for every slot); how many routers' layers
    took a rung below the whole buffer and how many the whole one
    (``moe_compact``: the layer's own ladder over the same load; a layer
    whose only rung is all its slots has no branch and counts as whole);
    and the held rows that were live beside the rows of the buffers the
    layers took (``moe_rows``).  Nothing for a model without a
    router."""
    routers = model_state.get(ROUTER_COLLECTION)
    if not routers:
        return {}
    load = jnp.stack([leaf for path, leaf in
                      jax.tree_util.tree_flatten_with_path(routers)[0]
                      if path[-1].key == "load"])
    first, held = experts_held or (0, n_routed_experts)
    here = jnp.sum(load[:, first:first + held], axis=-1)
    slots = jnp.sum(load, axis=-1)
    *rungs, taken = compact_rows(slots.astype(jnp.int32), held,
                                 n_routed_experts)
    for rows in reversed(rungs):  # the first rung that holds the held slots
        taken = jnp.where(here <= rows, rows, taken)
    compact = jnp.sum(taken < slots, dtype=jnp.float32)
    return {"moe_load": load,
            "moe_slots": jnp.stack([jnp.sum(here), jnp.sum(slots - here)]),
            "moe_dropped": jnp.zeros((), jnp.float32),
            "moe_compact": jnp.stack([compact, len(load) - compact]),
            "moe_rows": jnp.stack([jnp.sum(here),
                                   jnp.sum(taken, dtype=jnp.float32)])}
