"""Laguna (``model_type: laguna``; poolside's Laguna-S-2.1): a decoder LM
whose layers differ in attention and head count, with sparse experts
beside a shared one, for the training path.  Each layer is ``h = x +
Attn(rms(x))``, ``y = h + FF(rms(h))``; one more RMSNorm, then an untied
head.  By the layer's published index ``l`` (``layer_offset + i``), so
that a cut stage gets its published kinds:

* ``layer_types[l]``: ``full_attention`` attends every earlier key, with
  YaRN's rotary frequencies (``rope_theta``, ``yarn_*``) on the first
  ``partial_rotary_factor`` of each head's features;
  ``sliding_attention`` attends a causal band of the ``sliding_window``
  newest keys (its own among them), with plain rotary positions on every
  feature (``sliding_rope_theta``);
* ``num_heads_per_layer[l]`` query heads over ``num_kv_heads``, each of
  ``head_dim`` features (not ``dim / heads``), no norm of ``q`` or
  ``k``; every head's output is gated, ``o_h <- sigmoid(x W_g)_h o_h``
  before ``W_o`` (the per-head gate of arXiv:2505.06708);
* ``l`` in ``mlp_only_layers``: a dense SwiGLU of ``intermediate_size``;
  else the routed experts of :class:`~.experts.ExpertMLP` (sigmoid
  scores over all ``n_routed_experts``, ``num_experts_per_tok`` chosen,
  their weights normalised and scaled) beside a shared SwiGLU expert.

Built of what the other decoders share: ``lfm2_moe.GroupedQueryAttention``
with its head width, window, rotary and gate set; ``ExpertMLP`` and
``SwiGLU``; the rotary helper (``transformer_lm.rope``, partial and
YaRN); ``lm_loss_fn``.  ``attention_impl="pallas"`` runs the flash
kernels (a band in the sliding layers), ``"xla"`` the plain path for the
CPU.

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
from .common import json_kwargs, maybe_remat, rms_norm
from .experts import ExpertMLP, SwiGLU, router_step_metrics
from .lfm2_moe import GroupedQueryAttention
from .transformer_lm import YaRN

__all__ = ["LagunaConfig", "Laguna", "laguna_s21", "attended_pairs",
           "NO_DECODE", "LAYER_KINDS"]

NO_DECODE = (
    "laguna has no decode path: serving it needs a ring of a window's keys "
    "and values beside full caches, head counts that differ by layer, "
    "a head width apart from dim / heads and the per-head gate, which "
    "neither the decode caches nor LMEngine have")

#: what an entry of ``layer_types`` may say, as the public config does
LAYER_KINDS = ("full_attention", "sliding_attention")

#: the published model's 48 layers: a period is full, sliding x 3, with
#: 48 and 72 query heads
PUBLISHED_LAYER_TYPES = tuple(
    "sliding_attention" if i % 4 else "full_attention" for i in range(48))
PUBLISHED_HEADS = tuple(72 if i % 4 else 48 for i in range(48))


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The model's sizes, named as ``Lfm2Config`` names them where they
    mean the same.  ``num_layers`` are held, from published layer
    ``layer_offset``; ``layer_types``, ``num_heads_per_layer`` and
    ``mlp_only_layers`` speak of published layers.  ``experts_held`` is
    ``(first, count)`` of the ``n_routed_experts`` whose weights live
    here (None: all)."""

    vocab: int = 100352
    dim: int = 3072
    num_layers: int = 48
    layer_offset: int = 0
    layer_types: Tuple[str, ...] = PUBLISHED_LAYER_TYPES
    num_heads_per_layer: Tuple[int, ...] = PUBLISHED_HEADS
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 1024
    shared_expert_intermediate_size: int = 1024
    n_routed_experts: int = 256
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 10
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    mlp_only_layers: Tuple[int, ...] = (0,)
    sliding_window: int = 512
    rope_theta: float = 500000.0
    partial_rotary_factor: float = 0.5
    yarn_factor: float = 128.0
    yarn_original_max_positions: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.4852030263919618
    sliding_rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    # the config is silent on it: DeepSeek-V3's value, as the GLM model's
    bias_update_rate: float = 0.001
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas
    attn_block_q: int = 128
    attn_block_k: int = 128
    remat: bool = False

    def __post_init__(self):
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r} "
                             "(xla|pallas)")
        stop = self.layer_offset + self.num_layers
        if min(len(self.layer_types), len(self.num_heads_per_layer)) < stop:
            raise ValueError(
                f"layers {self.layer_offset}-{stop - 1} are not all named by "
                f"layer_types ({len(self.layer_types)}) and num_heads_per_layer "
                f"({len(self.num_heads_per_layer)})")
        unknown = sorted(set(self.layer_types) - set(LAYER_KINDS))
        if unknown:
            raise ValueError(
                f"unknown layer kind {unknown} ({'|'.join(LAYER_KINDS)})")
        for h in sorted(set(self.num_heads_per_layer)):
            if h % self.num_kv_heads:
                raise ValueError(f"num_heads ({h}) must be a multiple of "
                                 f"num_kv_heads ({self.num_kv_heads})")
        if self.rotary_dim % 2:
            raise ValueError(f"the rotary part of a head ({self.rotary_dim} "
                             "features) must be even")

    @property
    def held(self) -> range:
        """The published indices of the layers held here."""
        return range(self.layer_offset, self.layer_offset + self.num_layers)

    @property
    def rotary_dim(self) -> int:
        """Features of a head that a full layer turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def yarn(self) -> YaRN:
        return YaRN(self.yarn_factor, self.yarn_original_max_positions,
                    self.yarn_beta_fast, self.yarn_beta_slow,
                    self.yarn_attention_factor)


def attended_pairs(t: int, window: Optional[int] = None) -> int:
    """Query-key pairs one row and head attends over ``t`` positions:
    the causal square, or a band of the ``window`` newest keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def _publish(c: LagunaConfig, rows: int, t: int):
    """Trace-time gauges of the layers held: how many of each kind, and
    the query-key pairs each kind attends, weighted by its heads."""
    reg = get_registry()
    kinds = reg.gauge("fdtpu_layer_kinds", "layers of the model traced last, "
                      "by the kind of their operator", ("kind",))
    pairs = reg.gauge("fdtpu_attn_pairs", "attended query-key pairs of the "
                      "model traced last, over its rows and query heads, by "
                      "the kind of layer", ("kind",))
    for kind in LAYER_KINDS:
        layers = [l for l in c.held if c.layer_types[l] == kind]
        window = c.sliding_window if kind == "sliding_attention" else None
        short = kind.split("_")[0]
        kinds.labels(short).set(len(layers))
        pairs.labels(short).set(rows * attended_pairs(t, window) * sum(
            c.num_heads_per_layer[l] for l in layers))


class LagunaBlock(nn.Module):
    """Pre-norm block at published ``index``: attention of the layer's
    kind and head count, then the dense SwiGLU or the experts beside the
    shared expert."""

    cfg: LagunaConfig
    index: int

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.cfg
        full = c.layer_types[self.index] == "full_attention"
        norm = partial(rms_norm, c.dtype, c.norm_eps)
        with jax.named_scope("fdtpu/full_attn" if full else "fdtpu/swa"):
            x = x + GroupedQueryAttention(
                c.num_heads_per_layer[self.index], c.num_kv_heads,
                rope_theta=c.rope_theta if full else c.sliding_rope_theta,
                dtype=c.dtype, attention_impl=c.attention_impl,
                block_q=c.attn_block_q, block_k=c.attn_block_k,
                head_dim=c.head_dim, qk_norm=False,
                window=None if full else c.sliding_window,
                rotary_dim=c.rotary_dim if full else None,
                yarn=c.yarn if full else None, head_gate=True,
                name="attn")(norm("attn_norm")(x))
        y = norm("mlp_norm")(x)
        if self.index in c.mlp_only_layers:
            return x + SwiGLU(c.intermediate_size, c.dtype, name="mlp")(y)
        routed = ExpertMLP(
            c.moe_intermediate_size, c.n_routed_experts,
            tuple(c.experts_held or (0, c.n_routed_experts)),
            c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob,
            c.bias_update_rate, c.dtype, name="moe")(y, train)
        with jax.named_scope("fdtpu/moe_shared"):
            shared = SwiGLU(c.shared_expert_intermediate_size, c.dtype,
                            name="shared")(y)
        return x + routed + shared


class Laguna(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32 (position t
    predicts token t+1)."""

    cfg: LagunaConfig
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
        _publish(c, *tokens.shape)
        block = maybe_remat(LagunaBlock, c.remat, train_argnum=2)
        x = nn.Embed(c.vocab, c.dim, dtype=c.dtype, name="embed")(tokens)
        for i, l in enumerate(c.held):
            x = block(c, l, name=f"layer{i}")(x, train)
        x = rms_norm(c.dtype, c.norm_eps, "final_norm")(x)
        with jax.named_scope("fdtpu/head"):
            logits = nn.Dense(c.vocab, dtype=c.dtype, use_bias=False,
                              name="head")(x)
        return jnp.asarray(logits, jnp.float32)


def laguna_s21(**kw) -> Laguna:
    """The model from plain JSON: ``dtype`` may be a string, the
    per-layer lists and ``experts_held`` lists."""
    return Laguna(LagunaConfig(**json_kwargs(
        kw, "experts_held", "layer_types", "num_heads_per_layer",
        "mlp_only_layers")))
