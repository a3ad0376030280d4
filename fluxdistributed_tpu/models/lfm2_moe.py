"""LFM2-8B-A1B (``model_type: lfm2_moe``): a decoder LM whose layers
differ in kind, for the training path.  Each layer is ``h = x +
Op(rms(x))``, ``y = h + FF(rms(h))``; ``Op`` is a gated short
convolution or grouped-query attention by ``layer_types[i]``, ``FF`` a
dense SwiGLU in the first ``num_dense_layers`` layers and sigmoid-routed
gated experts after them; one more RMSNorm, then a head that is the
embedding's transpose.

Built beside :class:`~.glm4_moe_lite.Glm4MoeLite`, sharing its experts
(:mod:`.experts`), the rotary helper and ``lm_loss_fn``.  What is new:

* :class:`ShortConv`: ``[B | C | z] = x W_in``; ``u = B * z``; a causal
  depthwise filter of ``conv_L_cache`` taps over ``u`` (one filter a
  feature, nought before a row's first position, no bias, no
  activation); ``(C * conv) W_out``.  The core between the two products
  (:func:`short_conv_core`) is elementwise over shifted copies of a
  padded ``u``, which XLA fuses into one pass.
* :class:`GroupedQueryAttention`: ``num_kv_heads`` key-value heads under
  ``num_heads`` query heads, an RMSNorm over each head's features of
  ``q`` and of ``k`` (one weight for all query heads, one for all key
  heads) before the rotary positions.  ``attention_impl="pallas"`` hands
  ``q``, ``k``, ``v`` to ``ops.pallas_attention.flash_attention`` as
  they are (its index maps point a group of query heads at their shared
  key-value head: nothing is repeated); ``"xla"`` is the plain path for
  the CPU.
* a block whose operator is chosen by a per-layer list, a tied head.

Serving is not built: its cache would hold, beside an attention layer's
keys and values, the last ``conv_L_cache - 1`` inputs ``u`` of every
convolution layer, which the decode caches of ``transformer_lm`` and
``serve/engine.py`` do not have.  ``decode=True`` and ``LMEngine`` raise
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
from ..ops.attention import dot_product_attention
from .common import json_kwargs, maybe_remat, rms_norm
from .experts import ExpertMLP, SwiGLU, router_step_metrics
from .transformer_lm import YaRN, rope

__all__ = ["Lfm2Config", "Lfm2Moe", "ShortConv", "GroupedQueryAttention",
           "short_conv_core", "causal_depthwise", "lfm2_moe", "NO_DECODE",
           "LAYER_KINDS", "PUBLISHED_LAYER_TYPES"]

NO_DECODE = (
    "lfm2_moe has no decode path: serving it needs a cache that holds a "
    "convolution's last two inputs beside an attention layer's keys and "
    "values, which neither the decode caches nor LMEngine have")

#: what an entry of ``layer_types`` may say, as the public config does
LAYER_KINDS = ("conv", "full_attention")

#: the published model's 24 layers: a period is attn conv conv conv
PUBLISHED_LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    """The model's sizes.  Field names follow ``Glm4Config`` where they
    mean the same and the public ``config.json`` otherwise;
    ``experts_held`` is ``(first, count)`` of the ``n_routed_experts``
    whose weights live here (None: all)."""

    vocab: int
    dim: int = 2048
    num_layers: int = 24
    num_heads: int = 32
    num_kv_heads: int = 8
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    conv_L_cache: int = 3
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    n_routed_experts: int = 32
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    num_dense_layers: int = 2
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    # the config is silent on it: DeepSeek-V3's value, as the GLM model's
    bias_update_rate: float = 0.001
    tie_embedding: bool = True
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas
    attn_block_q: int = 128
    attn_block_k: int = 128
    remat: bool = False

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_layers is {self.num_layers}")
        unknown = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if unknown:
            raise ValueError(
                f"unknown layer kind {unknown} ({'|'.join(LAYER_KINDS)})")


def causal_depthwise(u, w):
    """``conv_t = sum_j w[:, j] * u_{t - (L - 1) + j}``: ``u`` [rows, T,
    D], ``w`` [D, L], ``u`` nought before position 0.  L shifted slices
    of one padded array, so XLA makes one fused pass of it."""
    taps, t = w.shape[-1], u.shape[1]
    u = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(u[:, j:j + t] * w[:, j] for j in range(taps))


def short_conv_core(b, c, z, w):
    """``C * conv(B * z)``: ``b``, ``c``, ``z`` [rows, T, D], ``w`` [D, L]
    with :func:`causal_depthwise`.  Float32 inside (the chip's vector
    unit has no narrower arithmetic), the result in ``b``'s type."""
    f32 = jnp.float32
    conv = causal_depthwise(b.astype(f32) * z.astype(f32), w.astype(f32))
    return (c.astype(f32) * conv).astype(b.dtype)


class ShortConv(nn.Module):
    """The gated short convolution: two products around
    :func:`short_conv_core`."""

    taps: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        bcz = nn.Dense(3 * d, dtype=self.dtype, use_bias=False,
                       name="in_proj")(x)
        # one filter a feature; lecun-normal over its taps
        w = self.param("filter", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-1, out_axis=-2),
            (d, self.taps), jnp.float32)
        y = short_conv_core(bcz[..., :d], bcz[..., d:2 * d], bcz[..., 2 * d:], w)
        return nn.Dense(d, dtype=self.dtype, use_bias=False,
                        name="out_proj")(y)


class GroupedQueryAttention(nn.Module):
    """Causal grouped-query attention; training forward only.  As LFM2
    has it by default: heads of ``dim / num_heads``, a per-head RMSNorm
    of ``q`` and ``k`` before rotary positions on every feature.  What
    Laguna's layers set: a ``head_dim`` of its own, no norm
    (``qk_norm``), a causal band of the ``window`` newest keys, rotary
    positions on the first ``rotary_dim`` features with ``yarn``'s
    frequencies (``transformer_lm.rope``), and ``head_gate``: each
    head's output times ``sigmoid(x W_g)`` of that head, before the
    output projection (arXiv:2505.06708's head-wise gate)."""

    num_heads: int
    num_kv_heads: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas (the flash kernels)
    block_q: int = 128
    block_k: int = 128
    head_dim: Optional[int] = None  # None: dim // num_heads
    qk_norm: bool = True
    window: Optional[int] = None
    rotary_dim: Optional[int] = None  # None: every feature
    yarn: Optional[YaRN] = None
    head_gate: bool = False

    @nn.compact
    def __call__(self, x):
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r} (xla|pallas)")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.num_kv_heads})")
        d, t = x.shape[-1], x.shape[1]
        hd = self.head_dim
        if hd is None:
            if d % self.num_heads:
                raise ValueError(f"dim ({d}) must be a multiple of num_heads "
                                 f"({self.num_heads})")
            hd = d // self.num_heads
        heads = lambda n, name: nn.DenseGeneral(  # noqa: E731
            (n, hd), axis=-1, dtype=self.dtype, use_bias=False, name=name)
        norm = (partial(rms_norm, self.dtype, self.norm_eps) if self.qk_norm
                else lambda name: lambda y: y)
        pos = jnp.arange(t)
        turn = partial(rope, base=self.rope_theta, rotary_dim=self.rotary_dim,
                       yarn=self.yarn)
        q = turn(norm("q_norm")(heads(self.num_heads, "q")(x)), pos)
        k = turn(norm("k_norm")(heads(self.num_kv_heads, "k")(x)), pos)
        v = heads(self.num_kv_heads, "v")(x)
        if self.attention_impl == "pallas":
            from ..ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v, True, self.block_q, self.block_k,
                                  self.window)
        else:
            out = dot_product_attention(q, k, v, causal=True, window=self.window)
        if self.head_gate:
            with jax.named_scope("fdtpu/head_gate"):
                gate = jax.nn.sigmoid(nn.Dense(
                    self.num_heads, dtype=self.dtype, use_bias=False,
                    name="gate")(x).astype(jnp.float32))
                out = (out.astype(jnp.float32) * gate[..., None]).astype(out.dtype)
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=self.dtype,
                               use_bias=False, name="out")(out)


class Lfm2Block(nn.Module):
    """Pre-norm block: the operator of its ``kind``, then the dense
    SwiGLU or the experts."""

    cfg: Lfm2Config
    kind: str
    dense: bool

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.cfg
        norm = partial(rms_norm, c.dtype, c.norm_eps)
        y = norm("operator_norm")(x)
        if self.kind == "conv":
            with jax.named_scope("fdtpu/shortconv"):
                x = x + ShortConv(c.conv_L_cache, c.dtype, name="conv")(y)
        else:
            with jax.named_scope("fdtpu/gqa"):
                x = x + GroupedQueryAttention(
                    c.num_heads, c.num_kv_heads, rope_theta=c.rope_theta,
                    norm_eps=c.norm_eps, dtype=c.dtype,
                    attention_impl=c.attention_impl, block_q=c.attn_block_q,
                    block_k=c.attn_block_k, name="attn")(y)
        y = norm("ffn_norm")(x)
        if self.dense:
            return x + SwiGLU(c.intermediate_size, c.dtype, name="mlp")(y)
        return x + ExpertMLP(
            c.moe_intermediate_size, c.n_routed_experts,
            tuple(c.experts_held or (0, c.n_routed_experts)),
            c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob,
            c.bias_update_rate, c.dtype, name="moe")(y, train)


class Lfm2Moe(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32 (position t
    predicts token t+1)."""

    cfg: Lfm2Config
    decode: bool = False

    #: what ``decode=True``, ``generate`` and ``LMEngine`` raise
    no_decode = NO_DECODE

    def step_metrics(self, model_state) -> dict:
        """:func:`~.experts.router_step_metrics` of this model's share."""
        return router_step_metrics(model_state, self.cfg.experts_held,
                                   self.cfg.n_routed_experts)

    def __post_init__(self):
        if self.decode:
            raise NotImplementedError(NO_DECODE)
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        # how many layers of each kind the program traced last holds
        gauge = get_registry().gauge(
            "fdtpu_layer_kinds", "layers of the model traced last, by the "
            "kind of their operator", ("kind",))
        for kind in LAYER_KINDS:
            gauge.labels(kind).set(c.layer_types.count(kind))
        embed = nn.Embed(c.vocab, c.dim, dtype=c.dtype, name="embed")
        block = maybe_remat(Lfm2Block, c.remat, train_argnum=2)
        x = embed(tokens)
        for i, kind in enumerate(c.layer_types):
            x = block(c, kind, i < c.num_dense_layers, name=f"layer{i}")(x, train)
        x = rms_norm(c.dtype, c.norm_eps, "final_norm")(x)
        with jax.named_scope("fdtpu/head"):
            if c.tie_embedding:
                logits = embed.attend(x)
            else:
                logits = nn.Dense(c.vocab, dtype=c.dtype, use_bias=False,
                                  name="head")(x)
        return jnp.asarray(logits, jnp.float32)


def lfm2_moe(**kw) -> Lfm2Moe:
    """The model from plain JSON: ``dtype`` may be a string,
    ``experts_held`` and ``layer_types`` lists."""
    return Lfm2Moe(Lfm2Config(**json_kwargs(kw, "experts_held", "layer_types")))
