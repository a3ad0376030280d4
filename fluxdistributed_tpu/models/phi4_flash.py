"""Phi-4-mini-flash (``model_type: phi4flash``): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607), for the training path.

Every layer is ``h = x + Op(LN1(x))``, ``y = h + MLP(LN2(h))`` with
LayerNorm (scale and bias), both sums in float32 beside products in
``dtype``; ``MLP(x) = (silu(x W_g) * x W_u) W_d`` with gate and up one
fused ``fc1``, gate first; one more LayerNorm, then a head that is the
embedding's transpose.  ``Op`` is chosen by the layer's published index
``l`` (``layer_offset + i``) in a stack of ``published_layers``, ``half``
of them in the self-decoder:

* ``l % mb_per_layer == 0`` and ``l < half + 2``: :class:`MambaMixer`
  (Mamba-1: ``[u | z] = x W_in``, a causal depthwise filter with bias and
  SiLU, ``[dt | B | C] = u W_x``, ``delta = softplus(dt W_dt + b)``, the
  selective scan of :mod:`..ops.pallas_scan`, ``(y * silu(z)) W_out``).
  At ``l == half`` its scan's ``y`` is kept as the **memory**;
* other ``l < half``: :class:`DiffAttention` over a causal window of
  ``sliding_window`` keys; ``l == half + 1``: the same over every earlier
  key, its ``k`` and ``v`` kept;
* ``l >= half + 2``, Mamba's slots: :class:`GatedMemoryUnit`, ``(m *
  silu(x W_1)) W_2`` on the memory ``m``; the others: :class:`DiffAttention`
  as cross attention, ``q = x W_q`` over the kept ``k`` and ``v``.

Differential attention pairs heads: query heads ``2i``, ``2i + 1`` are
head ``i``'s two queries, key heads ``2j``, ``2j + 1`` its two keys and
their values side by side one value of twice the width; ``(A1 - lambda
A2) V`` is normed per head (``subln``) and scaled by ``1 -
lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)``.
``attention_impl="pallas"`` makes it two flash calls a layer (each of
the pair's softmaxes over the shared value, ``v`` twice ``q``'s width)
and runs the scan's Pallas kernels; ``"xla"`` is the plain path for the
CPU.

Serving is not built: ``decode=True`` and ``LMEngine`` raise
:data:`NO_DECODE`.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from ..obs.metrics import get_registry
from ..ops.attention import dot_product_attention
from ..ops.pallas_scan import selective_scan, selective_scan_xla
from .common import json_kwargs, maybe_remat
from .lfm2_moe import causal_depthwise

__all__ = ["Phi4FlashConfig", "Phi4Flash", "MambaMixer", "DiffAttention",
           "GatedMemoryUnit", "phi4_flash", "layer_kind", "lambda_init",
           "diff_lambda", "NO_DECODE", "LAYER_KINDS"]

NO_DECODE = (
    "phi4_flash has no decode path: serving it needs a Mamba layer's scan "
    "state and last filter inputs, a ring of a window's keys and values, "
    "one key-value cache that every cross-attention layer reads, and the "
    "memory that the gated memory units read, which neither the decode "
    "caches nor LMEngine have")

#: the kinds of operator a layer may hold
LAYER_KINDS = ("mamba", "window", "full", "gmu", "cross")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    """The model's sizes, named as ``Lfm2Config`` names them where they
    mean the same.  ``num_layers`` are held, from published layer
    ``layer_offset`` of ``published_layers``."""

    vocab: int = 200064
    dim: int = 2560
    num_layers: int = 32
    layer_offset: int = 0
    published_layers: int = 32
    num_heads: int = 40
    num_kv_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None  # None: ceil(dim / 16), Mamba's "auto"
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention_impl: str = "xla"  # xla | pallas
    attn_block_q: int = 128
    attn_block_k: int = 128
    remat: bool = False

    def __post_init__(self):
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r} "
                             "(xla|pallas)")
        if self.dim % self.num_heads or self.num_heads % 2 or self.num_kv_heads % 2:
            raise ValueError(f"dim ({self.dim}) must be a multiple of an even "
                             f"num_heads ({self.num_heads}), and num_kv_heads "
                             f"({self.num_kv_heads}) even")
        held = range(self.layer_offset, self.layer_offset + self.num_layers)
        if held.stop > self.published_layers:
            raise ValueError(f"layers {held.start}-{held.stop - 1} are not all "
                             f"among {self.published_layers}")
        half = self.published_layers // 2
        for l in held:
            kind = layer_kind(self, l)
            source = {"gmu": half, "cross": half + 1}.get(kind)
            if source is not None and source not in held:
                raise ValueError(f"layer {l} ({kind}) reads layer {source}, "
                                 f"which is not held ({held.start}-{held.stop - 1})")

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def rank(self) -> int:
        return self.dt_rank or math.ceil(self.dim / 16)


def layer_kind(cfg: Phi4FlashConfig, index: int) -> str:
    """The operator of published layer ``index``."""
    half = cfg.published_layers // 2
    if index % cfg.mb_per_layer == 0:
        return "mamba" if index < half + 2 else "gmu"
    if index < half:
        return "window"
    return "full" if index < half + 2 else "cross"


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def diff_lambda(q1, k1, q2, k2, init: float):
    """``lambda = exp(q1 . k1) - exp(q2 . k2) + lambda_init``: how much of
    the second softmax a differential attention layer takes away."""
    return jnp.exp(jnp.sum(q1 * k1)) - jnp.exp(jnp.sum(q2 * k2)) + init


class LayerNorm(nn.Module):
    """LayerNorm with scale and bias: float32 inside, the result in
    ``dtype``."""

    dtype: Any
    epsilon: float

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return ((x - mean) * jax.lax.rsqrt(var + self.epsilon) * scale
                + bias).astype(self.dtype)


def _dense(features, dtype, name):
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


def _dt_bias_init(key, shape, dtype):
    """softplus^-1 of a step log-uniform in [0.001, 0.1] (Mamba's)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt + jnp.log(-jnp.expm1(-dt))


class MambaMixer(nn.Module):
    """Mamba-1 over ``[rows, T, dim]``: ``(out, y)``, ``y`` the scan's
    float32 result before the gate."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        di, n, f32 = c.d_inner, c.d_state, jnp.float32
        uz = _dense(2 * di, c.dtype, "in_proj")(x)
        w = self.param("conv_weight", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-1, out_axis=-2), (di, c.d_conv), f32)
        b = self.param("conv_bias", nn.initializers.zeros, (di,), f32)
        u = jax.nn.silu(causal_depthwise(uz[..., :di].astype(f32), w) + b)
        dbc = _dense(c.rank + 2 * n, c.dtype, "x_proj")(u.astype(c.dtype)).astype(f32)
        delta = jax.nn.softplus(nn.Dense(di, dtype=f32, name="dt_proj", bias_init=(
            _dt_bias_init))(dbc[..., :c.rank]))
        a_log = self.param("A_log", lambda k, s, t: jnp.broadcast_to(
            jnp.log(jnp.arange(1, s[1] + 1, dtype=t)), s), (di, n), f32)
        d = self.param("D", nn.initializers.ones, (di,), f32)
        scan_args = (u, delta, -jnp.exp(a_log), dbc[..., c.rank:c.rank + n],
                     dbc[..., c.rank + n:], d)
        with jax.named_scope("fdtpu/scan"):
            y = (selective_scan(*scan_args) if c.attention_impl == "pallas"
                 else selective_scan_xla(*scan_args))
        gated = (y * jax.nn.silu(uz[..., di:].astype(f32))).astype(c.dtype)
        return _dense(c.dim, c.dtype, "out_proj")(gated), y


class GatedMemoryUnit(nn.Module):
    """``(m * silu(x W_1)) W_2`` on the memory ``m`` ``[rows, T,
    d_inner]``."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x, memory):
        c = self.cfg
        gate = _dense(c.d_inner, c.dtype, "in_proj")(x).astype(jnp.float32)
        return _dense(c.dim, c.dtype, "out_proj")(
            (memory * jax.nn.silu(gate)).astype(c.dtype))


class DiffAttention(nn.Module):
    """Differential attention at published layer ``index``: over its own
    ``k``, ``v`` (``kind`` ``window`` or ``full``), or over ``kv`` handed
    in (``cross``).  ``(out, (k, v))``, the keys and values it read."""

    cfg: Phi4FlashConfig
    index: int
    kind: str

    @nn.compact
    def __call__(self, x, kv=None):
        c = self.cfg
        b, t, _ = x.shape
        h, hkv, hd = c.num_heads, c.num_kv_heads, c.head_dim
        if self.kind == "cross":
            q = _dense(h * hd, c.dtype, "Wq")(x)
            k, v = kv
        else:
            qkv = _dense((h + 2 * hkv) * hd, c.dtype, "Wqkv")(x)
            q = qkv[..., :h * hd]
            k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, t, hkv, hd)
            v = qkv[..., (h + hkv) * hd:].reshape(b, t, hkv, hd)
        q = q.reshape(b, t, h // 2, 2, hd)
        keys = k.reshape(b, t, hkv // 2, 2, hd)
        values = v.reshape(b, t, hkv // 2, 2 * hd)
        window = c.sliding_window if self.kind == "window" else None
        outs = []
        for a in range(2):
            if c.attention_impl == "pallas":
                from ..ops.pallas_attention import flash_attention

                outs.append(flash_attention(
                    q[..., a, :], keys[..., a, :], values, True,
                    c.attn_block_q, c.attn_block_k, window))
            else:
                outs.append(dot_product_attention(
                    q[..., a, :], keys[..., a, :], values, causal=True,
                    window=window))
        init = lambda_init(self.index)
        lam = diff_lambda(*(self.param(f"lambda_{p}{a}", nn.initializers.normal(0.1),
                                       (hd,), jnp.float32)
                            for a in (1, 2) for p in ("q", "k")), init)
        o = outs[0].astype(jnp.float32) - lam * outs[1].astype(jnp.float32)
        scale = self.param("subln", nn.initializers.ones, (2 * hd,), jnp.float32)
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + c.norm_eps) * scale * (1.0 - init)
        out = _dense(c.dim, c.dtype, "out_proj")(
            o.astype(c.dtype).reshape(b, t, h * hd))
        return out, (k, v)


class MLP(nn.Module):
    """``(silu(x W_g) * x W_u) W_d``, gate and up one ``fc1``, gate
    first."""

    cfg: Phi4FlashConfig

    @nn.compact
    def __call__(self, x):
        c = self.cfg
        gu = _dense(2 * c.intermediate_size, c.dtype, "fc1")(x).astype(jnp.float32)
        g, u = jnp.split(gu, 2, axis=-1)
        return _dense(c.dim, c.dtype, "fc2")((jax.nn.silu(g) * u).astype(c.dtype))


class Phi4FlashBlock(nn.Module):
    """One layer at published ``index``; hands on the memory and the
    kept keys and values, with what it adds to them."""

    cfg: Phi4FlashConfig
    index: int

    @nn.compact
    def __call__(self, x, memory, kv, train: bool = True):
        c = self.cfg
        kind, half = layer_kind(c, self.index), c.published_layers // 2
        y = LayerNorm(c.dtype, c.norm_eps, name="ln1")(x)
        if kind == "mamba":
            with jax.named_scope("fdtpu/mamba"):
                out, scanned = MambaMixer(c, name="mamba")(y)
            if self.index == half:
                memory = scanned
        elif kind == "gmu":
            with jax.named_scope("fdtpu/gmu"):
                out = GatedMemoryUnit(c, name="gmu")(y, memory)
        else:
            scope = "fdtpu/cross_attn" if kind == "cross" else "fdtpu/diff_attn"
            with jax.named_scope(scope):
                out, read = DiffAttention(c, self.index, kind, name="attn")(y, kv)
            if kind == "full":
                kv = read
        x = x + out.astype(jnp.float32)
        x = x + MLP(c, name="mlp")(
            LayerNorm(c.dtype, c.norm_eps, name="ln2")(x)).astype(jnp.float32)
        return x, memory, kv


class Phi4Flash(nn.Module):
    """tokens [B, T] int32 -> logits [B, T, vocab] f32 (position t
    predicts token t+1)."""

    cfg: Phi4FlashConfig
    decode: bool = False

    #: what ``decode=True``, ``generate`` and ``LMEngine`` raise
    no_decode = NO_DECODE

    def __post_init__(self):
        if self.decode:
            raise NotImplementedError(NO_DECODE)
        super().__post_init__()

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        c = self.cfg
        held = range(c.layer_offset, c.layer_offset + c.num_layers)
        gauge = get_registry().gauge(
            "fdtpu_layer_kinds", "layers of the model traced last, by the "
            "kind of their operator", ("kind",))
        for kind in LAYER_KINDS:
            gauge.labels(kind).set(sum(layer_kind(c, l) == kind for l in held))
        embed = nn.Embed(c.vocab, c.dim, dtype=jnp.float32, name="embed")
        block = maybe_remat(Phi4FlashBlock, c.remat, train_argnum=4)
        x, memory, kv = embed(tokens), None, None
        for i, l in enumerate(held):
            x, memory, kv = block(c, l, name=f"layer{i}")(x, memory, kv, train)
        x = LayerNorm(c.dtype, c.norm_eps, name="final_norm")(x)
        with jax.named_scope("fdtpu/head"):
            return jnp.einsum("btd,vd->btv", x, embed.embedding.astype(c.dtype),
                              preferred_element_type=jnp.float32)


def phi4_flash(**kw) -> Phi4Flash:
    """The model from plain JSON: ``dtype`` may be a string."""
    return Phi4Flash(Phi4FlashConfig(**json_kwargs(kw)))
