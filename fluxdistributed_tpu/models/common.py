"""Shared model-building helpers."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.pallas_attention import KEPT_LSE, KEPT_OUT

__all__ = ["maybe_remat", "rms_norm", "json_kwargs"]


class UnitOffsetRMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * (1 + w)``, ``w`` from nought
    (``norm_add_unit_offset``): float32 inside, the result in ``dtype``."""

    dtype: Any
    epsilon: float

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        x = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.epsilon) * (1.0 + w)).astype(
            self.dtype)


def rms_norm(dtype, eps: float, name: str, unit_offset: bool = False):
    """The RMSNorm the decoder models share: a learned scale, ``eps`` as
    the public configs state it; with ``unit_offset`` the scale is ``1 +
    w``."""
    if unit_offset:
        return UnitOffsetRMSNorm(dtype, eps, name=name)
    return nn.RMSNorm(dtype=dtype, epsilon=eps, name=name)


def json_kwargs(kw: dict, *tuple_keys: str) -> dict:
    """A factory's keywords as plain JSON holds them, for a frozen
    config: ``dtype`` may be a string, and each of ``tuple_keys`` a list
    where the config wants a tuple."""
    kw = dict(kw)
    if isinstance(kw.get("dtype"), str):
        kw["dtype"] = jnp.dtype(kw["dtype"])
    for key in tuple_keys:
        if kw.get(key) is not None:
            kw[key] = tuple(kw[key])
    return kw


def maybe_remat(block_cls, enabled: bool, train_argnum: int | None = None):
    """Wrap a block Module class in ``nn.remat`` when ``enabled``.

    ``train_argnum`` marks the block's ``train`` flag static so flax's
    remat does not trace it into a ``bool[]`` tracer (which would break
    ``deterministic=not train``).  Argnums count ``self``: for
    ``__call__(self, x, train)`` pass 2 — and the call site must pass
    ``train`` POSITIONALLY (flax remat traces kwargs regardless of
    static_argnums).  Blocks whose ``__call__`` takes no train flag
    (ResNet blocks — BatchNorm mode is baked in via the ``norm``
    partial) pass ``None``.

    Remat callers must also pin each block's ``name=`` to the unwrapped
    auto-name: the wrapper class is named ``Checkpoint<Block>`` and
    would otherwise rename flax scopes, orphaning checkpoints and
    imported torch weights (asserted by ``tests/test_remat.py``).

    One thing is kept across the block and not made again: what a flash
    forward call returned (``out`` and the rows' ``lse``, named in
    ``ops.pallas_attention``), ``B*T*H*D`` in the compute type a call.
    The backward pass runs projections, norms and rotary again for the
    kernels' ``q``, ``k``, ``v``; the forward kernel it does not.  A
    block that holds no flash call has nothing by those names, and its
    program is plain ``nn.remat``'s.
    """
    if not enabled:
        return block_cls
    return nn.remat(
        block_cls,
        static_argnums=() if train_argnum is None else (train_argnum,),
        policy=jax.checkpoint_policies.save_only_these_names(
            KEPT_OUT, KEPT_LSE))
