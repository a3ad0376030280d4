"""EvaByte (``model_type: evabyte``): a dense byte-level decoder LM
whose attention is EVA (:mod:`..ops.eva_attention`), for the training
path.  Each layer is ``h = x + Attn(rms(x))``, ``y = h + FF(rms(h))``
with both sums in float32 (``fp32_skip_add``) beside products in
``dtype``; ``rms`` has a unit offset (the weight is ``1 + w``); ``FF`` is
a dense SwiGLU; one more ``rms``, then an untied head of
``num_pred_heads x vocab``: output ``i`` at position ``t`` scores byte
``t + 1 + i``.

Built beside :class:`~.lfm2_moe.Lfm2Moe`, sharing the rotary helper, the
RMSNorm maker, ``SwiGLU`` and ``lm_loss_fn``.  What is new:

* :class:`EvaAttention`: ``num_heads`` heads of ``dim / num_heads``, of
  which ``heads_held = (first, count)`` live here (a tensor-parallel
  chip's share: its columns of ``W_q``, ``W_k``, ``W_v``, its rows of
  ``W_o``, so the result is this chip's part of the attention's sum);
  rotary positions on every feature of ``q`` and ``k``; per head two
  learned vectors ``mu``, ``phi`` that pool each chunk's keys and
  values; :func:`~..ops.eva_attention.eva_attention`.
* the float32 residual stream, the eight-headed output: output 0 is the
  model's logits, outputs 1 and up reach ``lm_loss_fn`` as its
  ``mtp_loss*`` terms (``mtp_weight = num_pred_heads - 1`` makes the
  loss the plain sum of the heads' cross-entropies).

Serving is not built: ``decode=True`` and ``LMEngine`` raise
:data:`NO_DECODE`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..obs.metrics import get_registry
from ..ops.eva_attention import eva_attention
from .common import json_kwargs, maybe_remat, rms_norm
from .experts import SwiGLU
from .transformer_lm import next_token_loss, rope

__all__ = ["EvaByteConfig", "EvaByte", "EvaAttention", "evabyte", "NO_DECODE"]

NO_DECODE = (
    "evabyte has no decode path: serving it needs a cache that holds a "
    "window's keys and values beside the chunk summaries of every earlier "
    "window, which neither the decode caches nor LMEngine have")


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    """The model's sizes.  Field names follow ``Lfm2Config`` where they
    mean the same and the public ``config.json`` otherwise;
    ``heads_held`` is ``(first, count)`` of the ``num_heads`` whose
    weights live here (None: all)."""

    vocab: int = 320
    dim: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    heads_held: Optional[Tuple[int, int]] = None
    intermediate_size: int = 11008
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    rope_theta: float = 100000.0
    norm_eps: float = 1e-5
    # lm_loss_fn weighs the MEAN of the further heads' terms by this:
    # None is num_pred_heads - 1, their plain sum
    mtp_weight: Optional[float] = None
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas
    attn_block_q: int = 128
    attn_block_k: int = 128
    remat: bool = False

    def __post_init__(self):
        first, count = self.heads_held or (0, self.num_heads)
        if not (0 <= first and count >= 1
                and first + count <= self.num_heads):
            raise ValueError(f"heads_held {self.heads_held} is not a range "
                             f"of the {self.num_heads} heads")
        if self.dim % self.num_heads:
            raise ValueError(f"dim ({self.dim}) must be a multiple of "
                             f"num_heads ({self.num_heads})")


def _summary_init(key, shape, dtype):
    """``mu``, ``phi``: normal of deviation ``head ** -0.5``, clipped to
    one deviation."""
    std = shape[-1] ** -0.5
    return std * jnp.clip(jax.random.normal(key, shape, dtype), -1.0, 1.0)


class EvaAttention(nn.Module):
    """EVA attention over the heads held here; training forward only."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        d, t = x.shape[-1], x.shape[1]
        _, held = c.heads_held or (0, c.num_heads)
        hd = c.dim // c.num_heads
        heads = lambda name: nn.DenseGeneral(  # noqa: E731
            (held, hd), axis=-1, dtype=c.dtype, use_bias=False, name=name)
        pos = jnp.arange(t)
        q = rope(heads("q")(x), pos, base=c.rope_theta)
        k = rope(heads("k")(x), pos, base=c.rope_theta)
        v = heads("v")(x)
        mu = self.param("mu", _summary_init, (held, hd), jnp.float32)
        phi = self.param("phi", _summary_init, (held, hd), jnp.float32)
        out = eva_attention(
            q, k, v, mu, phi, window=c.window_size, chunk=c.chunk_size,
            impl=c.attention_impl, block_q=c.attn_block_q,
            block_k=c.attn_block_k)
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=c.dtype,
                               use_bias=False, name="out")(out)


class EvaByteBlock(nn.Module):
    """Pre-norm block on a float32 residual stream (``fp32_skip_add``)."""

    cfg: EvaByteConfig

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.cfg
        norm = partial(rms_norm, c.dtype, c.norm_eps, unit_offset=True)
        x = x + EvaAttention(c, name="attn")(norm("attn_norm")(x)).astype(
            jnp.float32)
        return x + SwiGLU(c.intermediate_size, c.dtype, name="mlp")(
            norm("ffn_norm")(x)).astype(jnp.float32)


class _Head(nn.Module):
    """The untied head: operands in ``dtype``, logits in float32
    (``fp32_logits``)."""

    width: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", nn.initializers.lecun_normal(),
                       (x.shape[-1], self.width), jnp.float32)
        return jnp.einsum("btd,dv->btv", x.astype(self.dtype),
                          w.astype(self.dtype),
                          preferred_element_type=jnp.float32)


class EvaByte(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32 (output 0:
    position t predicts byte t+1)."""

    cfg: EvaByteConfig
    decode: bool = False

    #: what ``decode=True``, ``generate`` and ``LMEngine`` raise
    no_decode = NO_DECODE

    #: ``lm_loss_fn`` weighs the mean of the sown terms by this
    @property
    def mtp_weight(self) -> float:
        c = self.cfg
        return (c.num_pred_heads - 1 if c.mtp_weight is None
                else c.mtp_weight)

    def __post_init__(self):
        if self.decode:
            raise NotImplementedError(NO_DECODE)
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        get_registry().gauge(
            "fdtpu_layer_kinds", "layers of the model traced last, by the "
            "kind of their operator", ("kind",)).labels("eva").set(c.num_layers)
        x = nn.Embed(c.vocab, c.dim, dtype=jnp.float32, name="embed")(tokens)
        block = maybe_remat(EvaByteBlock, c.remat, train_argnum=2)
        for i in range(c.num_layers):
            x = block(c, name=f"layer{i}")(x, train)
        x = rms_norm(c.dtype, c.norm_eps, "final_norm", unit_offset=True)(x)
        with jax.named_scope("fdtpu/head"):
            logits = _Head(c.num_pred_heads * c.vocab, c.dtype,
                           name="lm_head")(x)
        logits = logits.reshape(*logits.shape[:2], c.num_pred_heads, c.vocab)
        if train and not self.is_initializing():  # no stale term in the init
            for i in range(1, c.num_pred_heads):
                # output i at position t scores byte t + 1 + i
                self.sow("losses", f"mtp_loss{i - 1}", next_token_loss(
                    logits[:, :logits.shape[1] - i, i], tokens[:, i:]))
        return logits[:, :, 0]


def evabyte(**kw) -> EvaByte:
    """The model from plain JSON: ``dtype`` may be a string and
    ``heads_held`` a list."""
    return EvaByte(EvaByteConfig(**json_kwargs(kw, "heads_held")))
