"""GLM-4.7-Flash (``model_type: glm4_moe_lite``): a decoder LM with latent
attention, sigmoid-routed gated experts beside a shared expert, leading
dense layers and a multi-token-prediction module, for the training path.

Built beside :class:`~.transformer_lm.TransformerLM` and reusing its
RMSNorm, rotary helper and SwiGLU block; ``lm_loss_fn(model)`` serves
both, and the experts live in :mod:`.experts`.  What is new:

* :class:`LatentAttention` (MLA): queries and keys/values go through
  low-rank projections with an RMSNorm between, the rotary part of a key
  is one vector a position that every head shares, and the head size
  (``qk_nope_head_dim + qk_rope_head_dim``, ``v_head_dim``) is apart
  from ``dim / heads``.  ``attention_impl="pallas"`` hands the expanded
  ``q``, ``k``, ``v`` to ``ops.pallas_attention.flash_attention``, so no
  ``[T, T]`` score matrix is stored; ``"xla"`` is the plain path for
  the CPU.
* :class:`~.experts.ExpertMLP` (shared with ``lfm2_moe``):
  ``parallel.ep.sigmoid_route`` over all
  ``n_routed_experts`` in float32, ``held_experts_apply`` for the
  ``experts_held = (first, count)`` that live here (no capacity, no
  drops), plus the shared expert.  The router balances by a selection
  bias, not by a loss term: bias and the step's load sit in the
  ``"router"`` collection and are updated in the training step, as
  batch-norm statistics are.
* ``num_nextn_predict_layers``: each module projects ``[rms(h_i) ;
  rms(Emb(t_{i+1}))]``, runs one more expert layer and the shared head,
  and sows its cross-entropy against ``t_{i+2}`` into ``"losses"``;
  ``lm_loss_fn`` adds it times ``mtp_weight``.

Serving is not built: a cache for this attention holds a latent row a
position (``kv_lora_rank + qk_rope_head_dim`` features), which the
decode caches of ``transformer_lm`` and ``serve/engine.py`` do not
have.  ``decode=True`` and ``LMEngine`` raise :data:`NO_DECODE`.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..ops.attention import dot_product_attention
from .common import json_kwargs, maybe_remat, rms_norm
from .experts import ExpertMLP, SwiGLU, router_step_metrics
from .transformer_lm import rope

__all__ = ["Glm4Config", "Glm4MoeLite", "LatentAttention", "glm4_moe_lite",
           "NO_DECODE"]

NO_DECODE = (
    "glm4_moe_lite has no decode path: serving it needs a latent cache row "
    "(the compressed key-value features and the shared rotary key of a "
    "position), which neither the decode caches nor LMEngine have")


@dataclasses.dataclass(frozen=True)
class Glm4Config:
    """The model's sizes.  Field names follow the public ``config.json``
    where it has one; ``experts_held`` is ``(first, count)`` of the
    ``n_routed_experts`` whose weights live here (None: all)."""

    vocab: int
    dim: int = 2048
    num_layers: int = 47
    num_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    intermediate_size: int = 10240
    moe_intermediate_size: int = 1536
    n_routed_experts: int = 64
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.8
    norm_topk_prob: bool = True
    first_k_dense_replace: int = 1
    num_nextn_predict_layers: int = 0
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    # the config is silent on both: DeepSeek-V3's values
    bias_update_rate: float = 0.001
    mtp_weight: float = 0.3
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas
    attn_block_q: int = 128
    attn_block_k: int = 128
    remat: bool = False


class LatentAttention(nn.Module):
    """Multi-head latent attention, training forward only."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas (the flash kernels)
    block_q: int = 128
    block_k: int = 128

    @nn.compact
    def __call__(self, x):
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r} (xla|pallas)")
        b, t, d = x.shape
        h, nope, rot = self.num_heads, self.qk_nope_head_dim, self.qk_rope_head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, dtype=self.dtype, use_bias=False, name=name)
        heads = lambda f, name: nn.DenseGeneral(  # noqa: E731
            (h, f), axis=-1, dtype=self.dtype, use_bias=False, name=name)
        norm = partial(rms_norm, self.dtype, self.norm_eps)

        c_q = norm("q_a_norm")(dense(self.q_lora_rank, "q_a")(x))
        q = heads(nope + rot, "q_b")(c_q)  # [B, T, H, nope + rot]
        kv = dense(self.kv_lora_rank + rot, "kv_a")(x)
        c_kv = norm("kv_a_norm")(kv[..., :self.kv_lora_rank])
        kn_v = heads(nope + self.v_head_dim, "kv_b")(c_kv)
        k_nope, v = kn_v[..., :nope], kn_v[..., nope:]
        pos = jnp.arange(t)
        # the rotary key is one vector a position, shared by the heads
        k_rot = rope(kv[..., self.kv_lora_rank:][:, :, None, :], pos,
                     base=self.rope_theta)
        q = jnp.concatenate(
            [q[..., :nope], rope(q[..., nope:], pos, base=self.rope_theta)],
            axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rot, (b, t, h, rot))], axis=-1)
        if self.attention_impl == "pallas":
            # one head size for q, k and v is what the kernel takes
            if v.shape[-1] != q.shape[-1]:
                raise ValueError(
                    "attention_impl='pallas' needs v_head_dim == "
                    "qk_nope_head_dim + qk_rope_head_dim "
                    f"({v.shape[-1]} != {q.shape[-1]})")
            from ..ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v, True, self.block_q, self.block_k)
        else:
            out = dot_product_attention(q, k, v, causal=True)
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=self.dtype,
                               use_bias=False, name="o")(out)


class Glm4Block(nn.Module):
    """Pre-norm block: latent attention, then the dense SwiGLU
    (``dense_dim``) or the experts with their shared expert."""

    cfg: Glm4Config
    dense: bool

    @nn.compact
    def __call__(self, x, train: bool = True):
        c = self.cfg
        norm = partial(rms_norm, c.dtype, c.rms_norm_eps)
        with jax.named_scope("fdtpu/mla"):
            x = x + LatentAttention(
                c.num_heads, c.q_lora_rank, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                rope_theta=c.rope_theta, norm_eps=c.rms_norm_eps,
                dtype=c.dtype, attention_impl=c.attention_impl,
                block_q=c.attn_block_q, block_k=c.attn_block_k,
                name="attn")(norm("attn_norm")(x))
        y = norm("mlp_norm")(x)
        if self.dense:
            return x + SwiGLU(c.intermediate_size, c.dtype, name="mlp")(y)
        routed = ExpertMLP(
            c.moe_intermediate_size, c.n_routed_experts,
            tuple(c.experts_held or (0, c.n_routed_experts)),
            c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob,
            c.bias_update_rate, c.dtype, name="moe")(y, train)
        with jax.named_scope("fdtpu/moe_shared"):
            shared = SwiGLU(c.moe_intermediate_size * c.n_shared_experts,
                            c.dtype, name="shared")(y)
        return x + routed + shared


class MultiTokenModule(nn.Module):
    """One multi-token-prediction depth: ``W_eh [rms(h) ; rms(emb)]``,
    one expert layer, a final norm of its own."""

    cfg: Glm4Config

    @nn.compact
    def __call__(self, h, emb, train: bool = True):
        c = self.cfg
        norm = partial(rms_norm, c.dtype, c.rms_norm_eps)
        both = jnp.concatenate([norm("hnorm")(h), norm("enorm")(emb)], axis=-1)
        x = nn.Dense(c.dim, dtype=c.dtype, use_bias=False, name="eh_proj")(both)
        block = maybe_remat(Glm4Block, c.remat, train_argnum=2)
        x = block(c, False, name="block")(x, train)
        return x, norm("final_norm")(x)


class Glm4MoeLite(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32 (position t
    predicts token t+1)."""

    cfg: Glm4Config
    decode: bool = False

    #: what ``decode=True``, ``generate`` and ``LMEngine`` raise
    no_decode = NO_DECODE

    #: ``lm_loss_fn`` weighs the sown multi-token terms by this
    @property
    def mtp_weight(self) -> float:
        return self.cfg.mtp_weight

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
        embed = nn.Embed(c.vocab, c.dim, dtype=c.dtype, name="embed")
        head = nn.Dense(c.vocab, dtype=c.dtype, use_bias=False, name="head")

        def logits_of(x):
            with jax.named_scope("fdtpu/head"):
                return jnp.asarray(head(x), jnp.float32)

        block = maybe_remat(Glm4Block, c.remat, train_argnum=2)
        x = embed(tokens)
        for i in range(c.num_layers):
            x = block(c, i < c.first_k_dense_replace, name=f"layer{i}")(x, train)
        logits = logits_of(
            rms_norm(c.dtype, c.rms_norm_eps, "final_norm")(x))
        if c.num_nextn_predict_layers and (train or self.is_initializing()):
            from .transformer_lm import next_token_loss

            h = x
            for j in range(c.num_nextn_predict_layers):
                # position i pairs h_i with the embedding of token i+j+1
                # and predicts token i+j+2
                emb = embed(tokens[:, j + 1:])
                h, out = MultiTokenModule(c, name=f"mtp{j}")(
                    h[:, :emb.shape[1]], emb, train)
                term = next_token_loss(logits_of(out), tokens[:, j + 1:])
                if not self.is_initializing():  # no stale term in the init
                    self.sow("losses", f"mtp_loss{j}", term)
        return logits


def glm4_moe_lite(**kw) -> Glm4MoeLite:
    """The model from plain JSON: ``dtype`` may be a string and
    ``experts_held`` a list."""
    return Glm4MoeLite(Glm4Config(**json_kwargs(kw, "experts_held")))
