"""Decoder-only transformer language model (GPT-style).

Scope beyond the reference (vision-only — ResNet on ImageNet,
src/ddp_tasks.jl:275): this family exists to make the framework's
long-context machinery first-class on a model that actually has a long
sequence axis.  The design choices are TPU-first:

* **Pluggable core attention** (the ViT pattern, models/vit.py): pass
  ``attn_fn=make_ring_attention(mesh, causal=True)`` and the SAME module
  trains sequence-parallel over a ``seq`` mesh axis, or
  ``ops.pallas_attention.flash_attention`` for the fused kernel — the
  default is the XLA-fused ``dot_product_attention(causal=True)``.
* **RoPE positions** computed on the global token axis — applied before
  the attention call, so under GSPMD sequence sharding every shard still
  rotates by its true global position (no per-shard offset bookkeeping).
* **Pre-LN blocks, bf16 compute, f32 logits** — the residual stream and
  softmax/CE stay accurate while matmuls ride the MXU in bf16.
* **Tied input/output embeddings** by default (halves embedding memory —
  the vocab table is usually the largest single tensor at small scale).

``lm_loss_fn`` adapts the model to the framework's loss signature, so
every training path — DP (``make_train_step``), FSDP, TP, SP — applies
unchanged: the batch is ``{"tokens": int32 [B, T]}`` and the loss is
next-token cross-entropy.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from ..mesh import EXPERT_AXIS, PIPE_AXIS
from ..ops.attention import dot_product_attention
from .common import maybe_remat

__all__ = [
    "TransformerLM",
    "lm_loss_fn",
    "next_token_loss",
    "rope",
    "YaRN",
    "yarn_inv_freq",
    "generate",
    "make_decode_cache",
    "lm_pp",
    "MoEDecoderBlock",
    "moe_expert_fn",
    "lm_moe_specs",
    "lm_tiny",
    "lm_small",
    "lm_medium",
]

AttnFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]


class YaRN(NamedTuple):
    """YaRN's rotary frequencies (arXiv:2309.00071), as the published
    recipe (transformers' ``_compute_yarn_parameters``) takes them from a
    config: the ``factor`` by which the context was stretched over
    ``original_max_positions``, the ramp's ends in rotations over that
    length (``beta_fast``, ``beta_slow``), and the factor on ``cos`` and
    ``sin`` (``attention_factor``)."""

    factor: float
    original_max_positions: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0


def yarn_inv_freq(dim: int, base: float, yarn: YaRN) -> np.ndarray:
    """The ``dim / 2`` inverse frequencies of a rotary over ``dim``
    features: pair ``i`` keeps its own ``base^(-2i/dim)`` below the
    ramp, takes it over ``factor`` above it, and blends the two along a
    linear ramp from the pair that turns ``beta_fast`` times over the
    original positions (floored) to the one that turns ``beta_slow``
    times (ceiled).  Computed on the host, float64, handed on as
    float32."""
    def pair_of(turns):
        return (dim * math.log(yarn.original_max_positions / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(pair_of(yarn.beta_fast)), 0)
    high = min(math.ceil(pair_of(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    own = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    return (own / yarn.factor * ramp + own * (1.0 - ramp)).astype(np.float32)


def rope(x: jax.Array, positions: jax.Array, base: float = 10000.0,
         rotary_dim: Optional[int] = None,
         yarn: Optional[YaRN] = None) -> jax.Array:
    """Rotary position embedding on ``x``: [B, T, H, D] with D even.

    ``positions``: [T] (or [B, T]) global token indices.  Pairs feature
    ``2i`` with ``2i+1`` and rotates by ``pos / base^(2i/D)`` — relative
    offsets become phase differences, so attention scores depend only on
    key/query distance.  Computed in f32 and cast back (bf16 phase
    accumulation loses precision at long context).

    ``rotary_dim`` (even): only the first ``rotary_dim`` features turn,
    over frequencies of that width, and the rest pass unchanged (a
    partial rotary).  ``yarn``: the frequencies of :func:`yarn_inv_freq`,
    and ``cos`` and ``sin`` times its ``attention_factor``.
    """
    if rotary_dim is not None and rotary_dim < x.shape[-1]:
        turned = rope(x[..., :rotary_dim], positions, base, yarn=yarn)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    assert d % 2 == 0, "rope needs an even head dim"
    if yarn is None:
        inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        inv_freq = jnp.asarray(yarn_inv_freq(d, base, yarn))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [..., T, D/2]
    # broadcast over batch/head axes: positions [T] -> [1, T, 1, D/2]
    while ang.ndim < x.ndim:
        ang = ang[None] if ang.ndim < x.ndim - 1 else ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


#: KV-cache storage scenarios: full-precision, int8 (4x smaller than
#: f32, 2x smaller than bf16), fp8 e4m3 (same bytes as int8, no rounding
#: step — hardware-dependent, stubbed behind dtype availability)
KV_QUANTS = ("none", "int8", "fp8")

#: ``valid_len`` cache sentinel meaning "every write is real" — decode
#: steps and unpadded prefills (models.generate) run ungated.  The
#: serving engine sets ``valid_len`` to the REAL token count per padded
#: prefill/chunk call (a dynamic operand, pure cache DATA), so pad
#: positions never write into the windowed ring — which is what lets
#: the ring be sized exactly ``sinks + window``, no ``ring_slack``
#: over-allocation.  2**30 keeps ``cursor + VALID_UNGATED`` inside
#: int32 for any reachable cursor.
VALID_UNGATED = 2 ** 30


def _kv_store_dtype(kv_quant: str):
    """The cache leaf dtype for a quant scenario (None = model dtype)."""
    if kv_quant == "int8":
        return jnp.int8
    if kv_quant == "fp8":
        dt = getattr(jnp, "float8_e4m3fn", None)
        if dt is None:
            raise ValueError(
                "kv_quant='fp8' needs jnp.float8_e4m3fn, which this "
                "jax/jaxlib build does not provide — use kv_quant='int8'")
        return dt
    return None


def quantize_kv(x: jax.Array, kv_quant: str):
    """Quantize new K/V rows for cache storage: per-row-per-head absmax
    scaling over the head dim.  ``x`` [..., H, D] → ``(stored [..., H, D]
    in the storage dtype, scale [..., H] f32)``.  The scale rides in the
    cache next to its rows (dense: per slot row; paged: per pool block
    row), so every read path — XLA dequant-after-gather or the decode
    kernel's in-kernel dequant — sees the same numbers."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    if kv_quant == "int8":
        scale = jnp.maximum(amax, 1e-12) / 127.0
        q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    else:  # fp8 e4m3: max normal 448
        scale = jnp.maximum(amax, 1e-12) / 448.0
        q = xf / scale[..., None]
    return q.astype(_kv_store_dtype(kv_quant)), scale


def dequantize_kv(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """Invert :func:`quantize_kv` into the model's compute dtype."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)[..., None]
            ).astype(dtype)


def _norm_layer(kind: str, dtype, name: Optional[str] = None,
                eps: float = 1e-6):
    """``layernorm`` (GPT-2 style, default) or ``rmsnorm`` (Llama
    style: no mean-centering, no bias — one fewer reduction per norm on
    the VPU and a smaller param tree).  ``eps`` matters for weight
    interop: HF GPT-2 uses 1e-5 where flax defaults to 1e-6."""
    if kind == "layernorm":
        return nn.LayerNorm(dtype=dtype, name=name, epsilon=eps)
    if kind == "rmsnorm":
        return nn.RMSNorm(dtype=dtype, name=name, epsilon=eps)
    raise ValueError(f"unknown norm {kind!r} (layernorm|rmsnorm)")


class CausalSelfAttention(nn.Module):
    """QKV projection + RoPE + pluggable causal core + output projection.

    ``decode=True`` switches to single-token autoregressive mode with a
    KV cache: the cache buffers are created at ``init`` time (which
    traces the full target length, fixing the static cache shape — no
    dynamic shapes under jit), and each ``apply`` writes the new K/V at
    ``cache_index`` via ``dynamic_update_slice`` and attends the one
    query against the filled prefix.  O(T) per generated token instead
    of O(T²) re-prefill.
    """

    num_heads: int
    dtype: Any = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None
    use_rope: bool = True
    decode: bool = False
    num_kv_heads: Optional[int] = None  # GQA: None/num_heads → MHA
    window: Optional[int] = None  # sliding-window attention (causal)
    sinks: int = 0  # StreamingLLM attention sinks (first `sinks` keys)
    # continuous-batching mode (serve/engine.py): each batch row is an
    # independent request SLOT with its own cache cursor — cache_index
    # becomes [B] and the windowed ring's slot_pos becomes [B, cache_len],
    # so slots at different depths decode together in ONE fixed-shape
    # compiled step.  Only single-token steps are supported post-init
    # (prefill runs through the scalar-index path on a batch-1 model and
    # the engine splices the result into the slot).
    slot_decode: bool = False
    # LEGACY extra windowed-ring capacity beyond sinks+window.  Padded
    # prefill used to need slack >= the largest pad run so a pad write
    # could not evict an in-band key; the dynamic ``valid_len`` cache
    # operand (see VALID_UNGATED) now gates pad positions out of the
    # ring write entirely, so the serving engine runs with slack 0 and
    # an exactly-sized ring.  The knob is kept for callers that want a
    # larger retention ring: band semantics are untouched — a larger
    # ring only RETAINS more, and retained out-of-band keys are
    # mask-excluded anyway.
    ring_slack: int = 0
    # paged KV cache (serve/engine.py layout="paged"): instead of one
    # contiguous [B, rows] cache per layer, K/V live in a shared pool of
    # ``kv_blocks`` fixed-size blocks ([blocks, kv_block_size, hkv, dh])
    # and each batch row carries a page table of int32 block ids.  The
    # indirection is DATA, never shape (arXiv:1810.09868's full-program
    # lesson): page-table updates feed the same compiled program, so
    # HBM scales with live tokens while the ONE-decode-compile invariant
    # holds.  A -1 page-table entry means "unallocated": reads through
    # it are mask-excluded, writes are dropped — which is also what
    # parks a freed slot safely.  0 = dense (the default layout).
    kv_block_size: int = 0
    kv_blocks: int = 0
    # decode attention implementation: "xla" (mask/gather over the cache,
    # the reference path) or "pallas" (ops/pallas_decode.py flash-decode
    # kernel — single-token steps only; prefill chunks stay XLA).  The
    # kernel consumes every cache layout natively (cursor block-skip,
    # windowed ring + sinks via slot_pos, paged page-table walk) and
    # falls back to an XLA rendering of the same block-walk schedule on
    # non-TPU backends (interpreter mode covers CPU kernel tests).
    attention_impl: str = "xla"
    # KV-cache storage quantization: "none" | "int8" | "fp8" — stored
    # values carry per-row-per-head scales in sibling cache leaves
    # (cached_k_scale/cached_v_scale); every attention read (XLA gather
    # or the decode kernel) dequantizes the SAME stored numbers, so all
    # impls agree token-for-token at a given quant setting.
    kv_quant: str = "none"

    @nn.compact
    def __call__(self, x):
        if self.attention_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown attention_impl {self.attention_impl!r} "
                "(xla|pallas)")
        if self.kv_quant not in KV_QUANTS:
            raise ValueError(
                f"unknown kv_quant {self.kv_quant!r} ({'|'.join(KV_QUANTS)})")
        if self.kv_quant != "none":
            if not self.decode:
                raise ValueError(
                    "kv_quant quantizes the decode KV cache; build the "
                    "model with decode=True (the training forward has no "
                    "cache to quantize)")
            _kv_store_dtype(self.kv_quant)  # fp8 availability check
        if self.slot_decode and not self.decode:
            raise ValueError("slot_decode=True requires decode=True (it is "
                             "a mode OF the KV-cache path)")
        if self.decode and self.attn_fn is not None:
            # the KV-cache path below always attends with the dense
            # core; silently dropping a mesh-sharded attn_fn (e.g. ring
            # attention) would change sharding semantics without warning
            raise ValueError(
                "decode=True ignores attn_fn: the KV-cache path uses the "
                "dense attention core. Generate with attn_fn=None (the "
                "math is identical for sequence-parallel-trained weights "
                "once gathered), or run a full forward without decode."
            )
        b, t, d = x.shape
        assert d % self.num_heads == 0, "embed dim must divide num_heads"
        # validate window/sinks ONCE, up front: without this the training
        # forward rejects sinks-without-window deep inside
        # dot_product_attention while the decode-cache path silently
        # ignores sinks — the same misconfiguration must fail identically
        # and early on both paths
        if self.sinks < 0:
            raise ValueError(f"sinks must be >= 0, got {self.sinks}")
        if self.sinks and self.window is None:
            raise ValueError(
                f"sinks={self.sinks} requires a sliding window: attention "
                "sinks pin the first keys OUTSIDE the window (StreamingLLM); "
                "without window= every key is attendable and sinks have no "
                "meaning. Pass window=<int> or sinks=0."
            )
        if self.window is not None and self.window < 1:
            raise ValueError(f"window must be >= 1, got {self.window}")
        if (self.kv_block_size > 0) != (self.kv_blocks > 0):
            raise ValueError(
                f"paged KV needs BOTH kv_block_size ({self.kv_block_size}) "
                f"and kv_blocks ({self.kv_blocks}) positive (or both 0 for "
                "the dense layout)")
        if self.kv_block_size and not self.decode:
            raise ValueError(
                "kv_block_size > 0 (paged KV) is a layout OF the decode "
                "cache; build the model with decode=True")
        head_dim = d // self.num_heads
        hkv = self.num_kv_heads or self.num_heads
        if self.num_heads % hkv:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({hkv})")
        if hkv != self.num_heads:
            # Grouped-query attention: separate projections so K/V carry
            # only hkv heads — the KV cache (and decode HBM traffic)
            # shrinks by num_heads/hkv, and the attention cores consume
            # the grouped layout directly (the Pallas kernel natively,
            # the XLA cores by a fused broadcast).
            q = nn.DenseGeneral(
                (self.num_heads, head_dim), axis=-1, dtype=self.dtype,
                name="q",
            )(x)
            kv = nn.DenseGeneral(
                (2, hkv, head_dim), axis=-1, dtype=self.dtype, name="kv"
            )(x)
            k, v = kv[:, :, 0], kv[:, :, 1]
        else:
            qkv = nn.DenseGeneral(
                (3, self.num_heads, head_dim), axis=-1, dtype=self.dtype,
                name="qkv",
            )(x)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]

        if self.decode and self.kv_block_size:
            # ---- paged block-pool KV layout -----------------------------
            # K/V live in a shared pool of fixed-size blocks; each batch
            # row carries a page table of int32 block ids (-1 =
            # unallocated: reads masked, writes dropped).  ONE code path
            # serves any (B, t): the all-slot decode step (B=max_slots,
            # t=1) and batch-1 (chunked) prefill are the same program at
            # different argument shapes — every row advances from its own
            # cursor, writes route through its page-table row, reads
            # gather the row's pages back into a contiguous view.  The
            # indirection is carried as DATA, so page-table churn never
            # retraces a compiled program.
            is_init = not self.has_variable("cache", "cached_k")
            cache_len = (
                t if self.window is None
                else min(self.window + self.sinks + self.ring_slack, t)
            )
            bs_kv = self.kv_block_size
            pages = -(-cache_len // bs_kv)
            r_pad = pages * bs_kv
            quant = self.kv_quant != "none"
            store_dt = _kv_store_dtype(self.kv_quant)
            cached_k = self.variable(
                "cache", "cached_k", jnp.zeros,
                (self.kv_blocks, bs_kv, hkv, head_dim), store_dt or k.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_v", jnp.zeros,
                (self.kv_blocks, bs_kv, hkv, head_dim), store_dt or v.dtype,
            )
            k_scale = v_scale = None
            if quant:
                # per-row-per-head scales, pool-shaped like their rows
                k_scale = self.variable(
                    "cache", "cached_k_scale", jnp.zeros,
                    (self.kv_blocks, bs_kv, hkv), jnp.float32)
                v_scale = self.variable(
                    "cache", "cached_v_scale", jnp.zeros,
                    (self.kv_blocks, bs_kv, hkv), jnp.float32)
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))
            page_table = self.variable(
                "cache", "page_table",
                lambda: jnp.full((b, pages), -1, jnp.int32))
            # per-row write gate: 0 = parked or mid-prefill, 1 = live.
            # The all-slot decode step rides EVERY row and drifts the
            # cursors of rows it does not own; a mid-prefill row has
            # bound pages (claimed prefix blocks, earlier chunks), so
            # unlike a parked row its drift writes would LAND — into a
            # shared prefix block, or over a windowed ring's in-band
            # keys once the drift outruns the ring slack.  Gating the
            # write on slot_live (the chunk program runs its batch-1
            # view with the gate forced open) makes any decode/prefill
            # interleaving safe; the gate is cache DATA, so flipping it
            # never retraces.
            slot_live = self.variable(
                "cache", "slot_live", lambda: jnp.zeros((b,), jnp.int32))
            slot_pos = None
            valid_len = None
            if self.window is not None:
                slot_pos = self.variable(
                    "cache", "slot_pos",
                    lambda: jnp.full((b, r_pad), -1, jnp.int32))
                # per-row valid-token count for the CURRENT call (see
                # VALID_UNGATED): padded prefill chunks gate their pad
                # positions out of the ring write
                valid_len = self.variable(
                    "cache", "valid_len",
                    lambda: jnp.full((b,), VALID_UNGATED, jnp.int32))
            if not is_init:
                # post-init, t is a CHUNK length (1 for the decode step);
                # the page count is fixed by the stored table, not by t
                pages = page_table.value.shape[1]
                r_pad = pages * bs_kv
                idx = cache_index.value  # [B] per-row cursors
                wpos = idx[:, None] + jnp.arange(t)[None, :]  # [B, T]
                if self.use_rope:
                    q, k = rope(q, wpos), rope(k, wpos)
                pt = page_table.value  # [B, pages]
                rows = jnp.arange(b)[:, None]  # [B, 1]
                live = slot_live.value[:, None] > 0  # [B, 1] write gate
                # the flash-decode kernel serves single-token steps only
                # (chunked prefill is matmul-dense and stays XLA)
                use_kernel = self.attention_impl == "pallas" and t == 1
                if quant:
                    k_store, k_sc = quantize_kv(k, self.kv_quant)
                    v_store, v_sc = quantize_kv(v, self.kv_quant)
                else:
                    k_store, v_store = k, v

                def write(phys, off):
                    cached_k.value = cached_k.value.at[phys, off].set(
                        k_store, mode="drop")
                    cached_v.value = cached_v.value.at[phys, off].set(
                        v_store, mode="drop")
                    if quant:
                        k_scale.value = k_scale.value.at[phys, off].set(
                            k_sc, mode="drop")
                        v_scale.value = v_scale.value.at[phys, off].set(
                            v_sc, mode="drop")

                def gather_view(pool, scale):
                    # -1 ("unallocated") clamps to block 0 purely to
                    # keep the gather in bounds; every such row is
                    # mask-excluded below
                    g = pool[jnp.maximum(pt, 0)]
                    g = g.reshape(b, r_pad, hkv, head_dim)
                    if scale is None:
                        return g
                    s = scale.value[jnp.maximum(pt, 0)].reshape(
                        b, r_pad, hkv)
                    return dequantize_kv(g, s, self.dtype)

                def kernel_out(cursor, sp):
                    from ..ops.pallas_decode import flash_decode_paged

                    return flash_decode_paged(
                        q, cached_k.value, cached_v.value, pt, cursor,
                        slot_pos=sp, window=self.window, sinks=self.sinks,
                        k_scale=k_scale.value if quant else None,
                        v_scale=v_scale.value if quant else None)

                if self.window is None:
                    # logical row == global position.  Write first,
                    # gather after: the chunk's own keys must be in the
                    # attendable view (the dense prefill path's
                    # write-then-read order).
                    keep = (wpos < r_pad) & live  # live rows, in range
                    page = jnp.minimum(wpos // bs_kv, pages - 1)
                    phys = pt[rows, page]
                    phys = jnp.where(keep & (phys >= 0), phys,
                                     self.kv_blocks)
                    off = wpos % bs_kv
                    write(phys, off)
                    if use_kernel:
                        out = kernel_out(wpos[:, 0], None)
                    else:
                        attn_k = gather_view(cached_k.value, k_scale)
                        attn_v = gather_view(cached_v.value, v_scale)
                        allow = (jnp.arange(r_pad)[None, None, :]
                                 <= wpos[:, :, None])  # [B, T, keys]
                        out = dot_product_attention(
                            q, attn_k, attn_v, mask=allow[:, None])
                else:
                    # the logical ring spans ALL paged rows: rounding
                    # cache_len up to a block multiple only RETAINS
                    # more, and retained out-of-band keys are
                    # mask-excluded anyway
                    ring = max(r_pad - self.sinks, 1)
                    # survival window relative to the last REAL position
                    # of this call: per row, one past it is idx + veff
                    # (veff = t when ungated — decode steps, unpadded
                    # prefills — which reduces to the classic
                    # newest-ring-of-the-chunk rule).  Gating on veff
                    # means a padded chunk's pad positions neither write
                    # nor evict, so the ring needs NO slack beyond
                    # sinks + window.
                    veff = jnp.minimum(valid_len.value, t)  # [B]
                    limit = (idx + veff)[:, None]  # [B, 1]
                    keep = (wpos > limit - 1 - ring) & (wpos < limit)
                    if self.sinks:
                        # pinned sinks keep too — but never a pad
                        keep |= (wpos < self.sinks) & (wpos < limit)
                        ring_slot = self.sinks + (wpos - self.sinks) % ring
                        lrow = jnp.where(wpos < self.sinks, wpos, ring_slot)
                    else:
                        lrow = wpos % ring
                    keep &= live  # mid-prefill/parked rows never write
                    phys = pt[rows, lrow // bs_kv]
                    phys = jnp.where(keep & (phys >= 0), phys,
                                     self.kv_blocks)
                    off = lrow % bs_kv
                    if use_kernel:
                        # write-then-attend: at t == 1 the only key the
                        # rolling write can evict sits a full ring
                        # behind the cursor — out of band by
                        # construction (ring >= window) — so the
                        # post-write ring + slot_pos hold exactly the
                        # attendable set, no concat needed
                        write(phys, off)
                        slot_pos.value = slot_pos.value.at[
                            rows, jnp.where(keep, lrow, r_pad)].set(
                            wpos, mode="drop")
                        out = kernel_out(idx, slot_pos.value)
                    else:
                        # read [pages ∥ this chunk] BEFORE the rolling
                        # write — the dense ring's order, so a key this
                        # chunk evicts stays attendable for its own
                        # earlier queries.  Under quantization the
                        # chunk's own keys are attended through their
                        # STORED (dequantized) values so every impl and
                        # the sequential reference see identical math.
                        k_at = (dequantize_kv(k_store, k_sc, self.dtype)
                                if quant else k)
                        v_at = (dequantize_kv(v_store, v_sc, self.dtype)
                                if quant else v)
                        attn_k = jnp.concatenate(
                            [gather_view(cached_k.value, k_scale), k_at],
                            axis=1)
                        attn_v = jnp.concatenate(
                            [gather_view(cached_v.value, v_scale), v_at],
                            axis=1)
                        sp = jnp.concatenate(
                            [slot_pos.value, wpos], axis=1)[:, None, :]
                        qg = wpos[:, :, None]  # [B, T, 1]
                        allow = (sp >= 0) & (sp <= qg)
                        in_band = sp > qg - self.window
                        if self.sinks:
                            in_band |= sp < self.sinks
                        allow &= in_band
                        write(phys, off)
                        slot_pos.value = slot_pos.value.at[
                            rows, jnp.where(keep, lrow, r_pad)].set(
                            wpos, mode="drop")
                        out = dot_product_attention(
                            q, attn_k, attn_v, mask=allow[:, None])
                cache_index.value = idx + t
                return nn.DenseGeneral(
                    d, axis=(-2, -1), dtype=self.dtype, name="out"
                )(out)
            # fall through at init: trace the normal full-length path so
            # every param/cache shape is fixed
        elif self.decode:
            is_init = not self.has_variable("cache", "cached_k")
            # at init, t is the FULL target length -> static cache shape.
            # With a window the cache is `sinks` PINNED slots plus a
            # ROLLING ring of `window` slots (O(sinks + window) memory
            # regardless of generation length); slot positions live in a
            # side buffer so the mask can recover global causality after
            # wraparound.
            cache_len = (
                t if self.window is None
                else min(self.window + self.sinks + self.ring_slack, t)
            )
            quant = self.kv_quant != "none"
            store_dt = _kv_store_dtype(self.kv_quant)
            cached_k = self.variable(
                "cache", "cached_k", jnp.zeros,
                (b, cache_len, hkv, head_dim), store_dt or k.dtype,
            )
            cached_v = self.variable(
                "cache", "cached_v", jnp.zeros,
                (b, cache_len, hkv, head_dim), store_dt or v.dtype,
            )
            k_scale = v_scale = None
            if quant:
                k_scale = self.variable(
                    "cache", "cached_k_scale", jnp.zeros,
                    (b, cache_len, hkv), jnp.float32)
                v_scale = self.variable(
                    "cache", "cached_v_scale", jnp.zeros,
                    (b, cache_len, hkv), jnp.float32)
            # slot mode: one cursor (and one ring position table) PER
            # batch row, so every slot advances independently
            idx_shape = (b,) if self.slot_decode else ()
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros(idx_shape, jnp.int32)
            )
            slot_pos = None
            valid_len = None
            if self.window is not None:
                sp_shape = (
                    (b, cache_len) if self.slot_decode else (cache_len,)
                )
                slot_pos = self.variable(
                    "cache", "slot_pos",
                    lambda: jnp.full(sp_shape, -1, jnp.int32),
                )
                # valid-token count for the CURRENT call (VALID_UNGATED
                # = every write real).  Shaped like cache_index; read by
                # the scalar-index prefill path only — slot decode steps
                # one real token per row by construction.
                valid_len = self.variable(
                    "cache", "valid_len",
                    lambda: jnp.full(idx_shape, VALID_UNGATED, jnp.int32),
                )
            if not is_init and self.slot_decode:
                # ONE token per slot, every slot at its own depth.  The
                # math mirrors the scalar-index path exactly (same write
                # layout, same mask algebra) so a slot's token stream is
                # bit-identical to a batch-1 sequential decode.
                if t != 1:
                    raise ValueError(
                        f"slot_decode steps one token per slot (t=1), got "
                        f"t={t}; prefill runs through a batch-1 scalar-index "
                        "model and is spliced into the slot by the engine")
                idx = cache_index.value  # [B] per-slot cursors
                total = cached_k.value.shape[1]
                if self.use_rope:
                    pos = idx[:, None]  # [B, 1] global positions
                    q, k = rope(q, pos), rope(k, pos)
                rows = jnp.arange(b)
                use_kernel = self.attention_impl == "pallas"
                if quant:
                    k_store, k_sc = quantize_kv(k, self.kv_quant)
                    v_store, v_sc = quantize_kv(v, self.kv_quant)
                else:
                    k_store, v_store = k, v

                def write(slot_idx, mode=None):
                    kw = dict(mode=mode) if mode else {}
                    cached_k.value = cached_k.value.at[rows, slot_idx].set(
                        k_store[:, 0], **kw)
                    cached_v.value = cached_v.value.at[rows, slot_idx].set(
                        v_store[:, 0], **kw)
                    if quant:
                        k_scale.value = k_scale.value.at[rows, slot_idx].set(
                            k_sc[:, 0], **kw)
                        v_scale.value = v_scale.value.at[rows, slot_idx].set(
                            v_sc[:, 0], **kw)

                def kernel_out(sp):
                    from ..ops.pallas_decode import flash_decode

                    return flash_decode(
                        q, cached_k.value, cached_v.value, idx,
                        slot_pos=sp, window=self.window, sinks=self.sinks,
                        k_scale=k_scale.value if quant else None,
                        v_scale=v_scale.value if quant else None)

                if self.window is None:
                    # parked slots may have run past the cache end; their
                    # writes drop harmlessly (output is discarded and the
                    # engine resets the cursor on re-admission)
                    write(idx, mode="drop")
                    if use_kernel:
                        out = kernel_out(None)
                        cache_index.value = idx + 1
                        return nn.DenseGeneral(
                            d, axis=(-2, -1), dtype=self.dtype, name="out"
                        )(out)
                    allow = jnp.arange(total)[None, :] <= idx[:, None]
                    attn_k = (dequantize_kv(
                        cached_k.value, k_scale.value, self.dtype)
                        if quant else cached_k.value)
                    attn_v = (dequantize_kv(
                        cached_v.value, v_scale.value, self.dtype)
                        if quant else cached_v.value)
                else:
                    ring = max(total - self.sinks, 1)
                    if self.sinks:
                        ring_slot = self.sinks + (idx - self.sinks) % ring
                        slot = jnp.where(idx < self.sinks, idx, ring_slot)
                    else:
                        slot = idx % ring
                    if use_kernel:
                        # write-then-attend (see the paged branch: the
                        # evicted key is a full ring behind the cursor,
                        # out of band by construction)
                        write(slot)
                        slot_pos.value = slot_pos.value.at[rows, slot].set(
                            idx)
                        out = kernel_out(slot_pos.value)
                        cache_index.value = idx + 1
                        return nn.DenseGeneral(
                            d, axis=(-2, -1), dtype=self.dtype, name="out"
                        )(out)
                    # read [ring ∥ new token] BEFORE the rolling write —
                    # the same order as the scalar path, so the key this
                    # token evicts stays attendable for this very step
                    # (quantized: attend the stored numbers, like every
                    # other read path)
                    ring_k = (dequantize_kv(
                        cached_k.value, k_scale.value, self.dtype)
                        if quant else cached_k.value)
                    ring_v = (dequantize_kv(
                        cached_v.value, v_scale.value, self.dtype)
                        if quant else cached_v.value)
                    k_at = (dequantize_kv(k_store, k_sc, self.dtype)
                            if quant else k)
                    v_at = (dequantize_kv(v_store, v_sc, self.dtype)
                            if quant else v)
                    attn_k = jnp.concatenate([ring_k, k_at], axis=1)
                    attn_v = jnp.concatenate([ring_v, v_at], axis=1)
                    sp = jnp.concatenate(
                        [slot_pos.value, idx[:, None]], axis=1)  # [B, total+1]
                    qg = idx[:, None]
                    allow = (sp >= 0) & (sp <= qg)
                    in_band = sp > qg - self.window
                    if self.sinks:
                        in_band |= sp < self.sinks
                    allow &= in_band
                    write(slot)
                    slot_pos.value = slot_pos.value.at[rows, slot].set(idx)
                cache_index.value = idx + 1
                allow = allow[:, None, None, :]  # [B, 1, 1, keys]
                out = dot_product_attention(q, attn_k, attn_v, mask=allow)
                return nn.DenseGeneral(
                    d, axis=(-2, -1), dtype=self.dtype, name="out"
                )(out)
            if not is_init:
                # t == 1: one sampling step.  t > 1: batched PREFILL — the
                # whole prompt's K/V written in one parallel pass (one
                # matmul-dense forward) instead of t sequential steps.
                idx = cache_index.value
                total = cached_k.value.shape[1]
                if self.use_rope:
                    pos = idx + jnp.arange(t)  # global positions
                    q, k = rope(q, pos), rope(k, pos)
                q_glob = (idx + jnp.arange(t))[:, None]
                use_kernel = self.attention_impl == "pallas" and t == 1
                if quant:
                    k_store, k_sc = quantize_kv(k, self.kv_quant)
                    v_store, v_sc = quantize_kv(v, self.kv_quant)
                else:
                    k_store, v_store = k, v

                def kernel_out(sp):
                    from ..ops.pallas_decode import flash_decode

                    # scalar mode: one shared cursor (and ring position
                    # table) for every batch row — broadcast both into
                    # the kernel's per-slot layout
                    return flash_decode(
                        q, cached_k.value, cached_v.value,
                        jnp.broadcast_to(idx, (b,)).astype(jnp.int32),
                        slot_pos=(None if sp is None else jnp.broadcast_to(
                            sp[None], (b, total))),
                        window=self.window, sinks=self.sinks,
                        k_scale=k_scale.value if quant else None,
                        v_scale=v_scale.value if quant else None)

                if self.window is None:
                    cached_k.value = jax.lax.dynamic_update_slice(
                        cached_k.value, k_store, (0, idx, 0, 0)
                    )
                    cached_v.value = jax.lax.dynamic_update_slice(
                        cached_v.value, v_store, (0, idx, 0, 0)
                    )
                    if quant:
                        k_scale.value = jax.lax.dynamic_update_slice(
                            k_scale.value, k_sc, (0, idx, 0))
                        v_scale.value = jax.lax.dynamic_update_slice(
                            v_scale.value, v_sc, (0, idx, 0))
                    if use_kernel:
                        out = kernel_out(None)
                        cache_index.value = idx + t
                        return nn.DenseGeneral(
                            d, axis=(-2, -1), dtype=self.dtype, name="out"
                        )(out)
                    # query i (global position idx+i) attends keys [0, idx+i]
                    allow = jnp.arange(total)[None, :] <= q_glob
                    attn_k = (dequantize_kv(
                        cached_k.value, k_scale.value, self.dtype)
                        if quant else cached_k.value)
                    attn_v = (dequantize_kv(
                        cached_v.value, v_scale.value, self.dtype)
                        if quant else cached_v.value)
                else:
                    # `total` is the ring length (the STORED cache's
                    # shape — cache_len above is only meaningful at init,
                    # where t is the full target length).  Reads go
                    # against [old ring ∥ this chunk]: a chunked
                    # prefill's EARLY queries need band keys that the
                    # chunk's own newest tokens are about to overwrite,
                    # so the read precedes the rolling write.  Positions
                    # are disjoint (ring < idx ≤ chunk); -1 marks
                    # unwritten slots, never attendable.
                    wpos = idx + jnp.arange(t)
                    # write layout: position p lives at slot p while
                    # p < sinks (pinned, never evicted), else at
                    # sinks + (p - sinks) % ring.  Only sink positions
                    # and the call's newest `ring` REAL tokens survive a
                    # read-back (veff gates padded prefill — see
                    # VALID_UNGATED: pads neither write nor evict, which
                    # is what lets the ring be exactly sinks + window),
                    # so everything else routes to the out-of-range slot
                    # and mode="drop" discards it — this also keeps the
                    # scatter duplicate-free.
                    ring = max(total - self.sinks, 1)
                    veff = jnp.minimum(valid_len.value, t)
                    limit = idx + veff  # one past the last REAL position
                    keep = (wpos > limit - 1 - ring) & (wpos < limit)
                    if self.sinks:
                        # pinned sinks keep too — but never a pad
                        keep |= (wpos < self.sinks) & (wpos < limit)
                        ring_slot = self.sinks + (wpos - self.sinks) % ring
                        slot = jnp.where(wpos < self.sinks, wpos, ring_slot)
                    else:
                        slot = wpos % ring
                    slots = jnp.where(keep, slot, total)  # total = dropped

                    def write():
                        cached_k.value = cached_k.value.at[:, slots].set(
                            k_store, mode="drop")
                        cached_v.value = cached_v.value.at[:, slots].set(
                            v_store, mode="drop")
                        if quant:
                            k_scale.value = k_scale.value.at[:, slots].set(
                                k_sc, mode="drop")
                            v_scale.value = v_scale.value.at[:, slots].set(
                                v_sc, mode="drop")
                        slot_pos.value = slot_pos.value.at[slots].set(
                            wpos, mode="drop")

                    if use_kernel:
                        # write-then-attend: at t == 1 the evicted key is
                        # a full ring behind the cursor — out of band
                        write()
                        out = kernel_out(slot_pos.value)
                        cache_index.value = idx + t
                        return nn.DenseGeneral(
                            d, axis=(-2, -1), dtype=self.dtype, name="out"
                        )(out)
                    k_at = (dequantize_kv(k_store, k_sc, self.dtype)
                            if quant else k)
                    v_at = (dequantize_kv(v_store, v_sc, self.dtype)
                            if quant else v)
                    ring_k = (dequantize_kv(
                        cached_k.value, k_scale.value, self.dtype)
                        if quant else cached_k.value)
                    ring_v = (dequantize_kv(
                        cached_v.value, v_scale.value, self.dtype)
                        if quant else cached_v.value)
                    attn_k = jnp.concatenate([ring_k, k_at], axis=1)
                    attn_v = jnp.concatenate([ring_v, v_at], axis=1)
                    sp = jnp.concatenate([slot_pos.value, wpos])[None, :]
                    allow = (sp >= 0) & (sp <= q_glob)
                    in_band = sp > q_glob - self.window
                    if self.sinks:
                        in_band |= sp < self.sinks
                    allow &= in_band
                    write()
                cache_index.value = idx + t
                allow = allow[None, None]  # [1, 1, t, keys]
                out = dot_product_attention(q, attn_k, attn_v, mask=allow)
                return nn.DenseGeneral(
                    d, axis=(-2, -1), dtype=self.dtype, name="out"
                )(out)
            # fall through at init: trace the normal full-length path so
            # every param/cache shape is fixed

        if self.use_rope:
            pos = jnp.arange(t)
            q, k = rope(q, pos), rope(k, pos)
        attn = (
            self.attn_fn
            if self.attn_fn is not None
            else partial(dot_product_attention, causal=True,
                         window=self.window, sinks=self.sinks)
        )
        # a custom attn_fn owns its own windowing (attention_core(...,
        # window=...) builds one); the model only windows the defaults
        out = attn(q, k, v)  # [B, T, H, Dh]
        return nn.DenseGeneral(d, axis=(-2, -1), dtype=self.dtype, name="out")(out)


def swiglu_mlp(y, mlp_dim: int, dtype, dropout: float = 0.0,
               train: bool = True):
    """Llama-style gated MLP in the scope of the compact module that
    calls it (``gate``, ``up``, ``down`` become that module's children):
    gate/up column matmuls fused by XLA, SiLU gating on the VPU,
    biasless (explicit names keep the TP rules exact: gate/up
    column-sharded, down row-sharded)."""
    d = y.shape[-1]
    gate = nn.Dense(mlp_dim, dtype=dtype, use_bias=False, name="gate")(y)
    up = nn.Dense(mlp_dim, dtype=dtype, use_bias=False, name="up")(y)
    y = nn.silu(gate) * up
    y = nn.Dropout(dropout, deterministic=not train)(y)
    return nn.Dense(d, dtype=dtype, use_bias=False, name="down")(y)


class DecoderBlock(nn.Module):
    num_heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    attn_fn: Optional[AttnFn] = None
    use_rope: bool = True
    decode: bool = False
    num_kv_heads: Optional[int] = None
    window: Optional[int] = None
    sinks: int = 0
    norm: str = "layernorm"
    mlp: str = "gelu"
    norm_eps: float = 1e-6
    slot_decode: bool = False
    ring_slack: int = 0
    kv_block_size: int = 0
    kv_blocks: int = 0
    attention_impl: str = "xla"  # decode core: xla | pallas flash-decode
    kv_quant: str = "none"  # KV-cache storage: none | int8 | fp8

    @nn.compact
    def __call__(self, x, train: bool = True):
        # train is positional-or-keyword (unlike the package's other
        # blocks) so nn.remat can mark it static via static_argnums
        y = _norm_layer(self.norm, self.dtype, eps=self.norm_eps)(x)
        y = CausalSelfAttention(
            self.num_heads, dtype=self.dtype, attn_fn=self.attn_fn,
            use_rope=self.use_rope, decode=self.decode,
            num_kv_heads=self.num_kv_heads, window=self.window,
            sinks=self.sinks, slot_decode=self.slot_decode,
            ring_slack=self.ring_slack, kv_block_size=self.kv_block_size,
            kv_blocks=self.kv_blocks, attention_impl=self.attention_impl,
            kv_quant=self.kv_quant,
        )(y)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = _norm_layer(self.norm, self.dtype, eps=self.norm_eps)(x)
        d = x.shape[-1]
        if self.mlp == "swiglu":
            y = swiglu_mlp(y, self.mlp_dim, self.dtype, self.dropout, train)
        elif self.mlp == "gelu":
            y = nn.Dense(self.mlp_dim, dtype=self.dtype)(y)
            y = nn.gelu(y, approximate=True)
            y = nn.Dropout(self.dropout, deterministic=not train)(y)
            y = nn.Dense(d, dtype=self.dtype)(y)
        else:
            raise ValueError(f"unknown mlp {self.mlp!r} (gelu|swiglu)")
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        return x + y


class MoEDecoderBlock(nn.Module):
    """DecoderBlock with the MLP replaced by a Switch/GShard MoE layer:
    the capacity path of ``parallel/ep.py`` (softmax top-k, one-hot
    dispatch, drops over capacity, GELU experts of ``mlp_dim``, a load
    term in the loss).  It keeps its users (``TransformerLM(moe_every=)``,
    ``spmd="ep"``).  A new model with many gated experts takes the other
    path there (``sigmoid_route`` + ``held_experts_apply``: no capacity,
    no drops, a grouped product), as ``models/glm4_moe_lite.py`` does.

    ``moe_fn`` comes from ``parallel.ep.moe_apply(expert_fn, mesh, ...)``
    with the matching ``expert_fn`` being this block's per-expert MLP
    (``w1/b1/w2/b2`` — see :func:`moe_expert_fn`): experts live sharded
    on the ``expert`` mesh axis, tokens are dispatched by the in-block
    router, and the load-balance auxiliary loss is sown into the
    ``"losses"`` collection (``lm_loss_fn`` adds it, weighted by the
    model's ``moe_aux_weight``).
    """

    num_heads: int
    mlp_dim: int
    num_experts: int
    moe_fn: Callable
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    attn_fn: Optional[AttnFn] = None
    use_rope: bool = True
    decode: bool = False
    num_kv_heads: Optional[int] = None
    window: Optional[int] = None
    sinks: int = 0
    norm: str = "layernorm"
    norm_eps: float = 1e-6
    slot_decode: bool = False
    ring_slack: int = 0
    kv_block_size: int = 0
    kv_blocks: int = 0
    attention_impl: str = "xla"  # decode core: xla | pallas flash-decode
    kv_quant: str = "none"  # KV-cache storage: none | int8 | fp8

    @nn.compact
    def __call__(self, x, train: bool = True):
        y = _norm_layer(self.norm, self.dtype, eps=self.norm_eps)(x)
        y = CausalSelfAttention(
            self.num_heads, dtype=self.dtype, attn_fn=self.attn_fn,
            use_rope=self.use_rope, decode=self.decode,
            num_kv_heads=self.num_kv_heads, window=self.window,
            sinks=self.sinks, slot_decode=self.slot_decode,
            ring_slack=self.ring_slack, kv_block_size=self.kv_block_size,
            kv_blocks=self.kv_blocks, attention_impl=self.attention_impl,
            kv_quant=self.kv_quant,
        )(y)
        y = nn.Dropout(self.dropout, deterministic=not train)(y)
        x = x + y
        y = _norm_layer(self.norm, self.dtype, eps=self.norm_eps)(x)
        b, t, d = y.shape
        e, m = self.num_experts, self.mlp_dim
        init = nn.initializers.lecun_normal()
        router = self.param("router", init, (d, e), jnp.float32)
        experts = {
            "w1": self.param("w1", init, (e, d, m), jnp.float32),
            "b1": self.param("b1", nn.initializers.zeros, (e, m), jnp.float32),
            "w2": self.param("w2", init, (e, m, d), jnp.float32),
            "b2": self.param("b2", nn.initializers.zeros, (e, d), jnp.float32),
        }
        experts = jax.tree.map(lambda p: jnp.asarray(p, self.dtype), experts)
        toks = y.reshape(b * t, d)
        out, aux = self.moe_fn(experts, jnp.asarray(router, jnp.float32), toks)
        self.sow("losses", "moe_aux", aux)
        out = nn.Dropout(self.dropout, deterministic=not train)(out.reshape(b, t, d))
        return x + out


def moe_expert_fn(p, x):
    """The per-expert MLP matching ``MoEDecoderBlock``'s params — pass to
    ``parallel.ep.moe_apply`` when building the block's ``moe_fn``."""
    return jax.nn.gelu(x @ p["w1"] + p["b1"], approximate=True) @ p["w2"] + p["b2"]


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [B, T] int32 → logits [B, T, vocab] f32.

    Position t's logits predict token t+1 (standard autoregressive
    convention; ``next_token_loss`` does the shift).  With
    ``tie_embeddings`` the output head reuses the input table
    (logits = h @ E^T).
    """

    vocab: int
    depth: int = 4
    dim: int = 256
    num_heads: int = 4
    mlp_dim: int = 1024
    dtype: Any = jnp.bfloat16
    dropout: float = 0.0
    attn_fn: Optional[AttnFn] = None
    use_rope: bool = True
    tie_embeddings: bool = True
    decode: bool = False
    # continuous-batching decode (serve/engine.py): per-slot cache
    # cursors so independent requests at different depths share ONE
    # compiled single-token step.  Requires decode=True.
    slot_decode: bool = False
    # LEGACY extra windowed-ring capacity (see CausalSelfAttention
    # .ring_slack) — the serving engine no longer needs it: the dynamic
    # valid_len operand gates pad writes out of the exactly-sized ring
    ring_slack: int = 0
    # paged KV cache (serve/engine.py layout="paged"): per-layer K/V in
    # a shared pool of kv_blocks fixed-size blocks, indexed through a
    # per-row page table carried as device data (see
    # CausalSelfAttention.kv_block_size).  0/0 = dense layout.
    kv_block_size: int = 0
    kv_blocks: int = 0
    attention_impl: str = "xla"  # decode core: xla | pallas flash-decode
    kv_quant: str = "none"  # KV-cache storage: none | int8 | fp8
    num_kv_heads: Optional[int] = None  # GQA: grouped KV heads
    window: Optional[int] = None  # sliding-window attention
    sinks: int = 0  # StreamingLLM attention sinks (with window)
    norm: str = "layernorm"  # layernorm | rmsnorm
    norm_eps: float = 1e-6  # 1e-5 for HF GPT-2 weight interop
    mlp: str = "gelu"  # gelu | swiglu (MoE blocks keep their expert MLP)
    # learned-positions (use_rope=False) table length; REQUIRED for
    # decode with use_rope=False (later calls see t=1, but the param
    # shape is fixed at creation)
    max_len: Optional[int] = None
    # rematerialize each block in the backward pass: activations for only
    # ~one block live at a time, trading ~1 extra forward of FLOPs for
    # O(depth)x less activation memory -> longer sequences / bigger
    # batches per chip (jax.checkpoint, the TPU HBM lever)
    remat: bool = False
    # MoE: every ``moe_every``-th block swaps its MLP for a routed expert
    # layer (0 = dense everywhere).  ``moe_fn`` is built by the caller
    # via parallel.ep.moe_apply(models.moe_expert_fn, mesh, ...) so the
    # expert mesh axis stays a caller decision; the router's
    # load-balance aux loss is added by lm_loss_fn with weight
    # ``moe_aux_weight``.
    moe_every: int = 0
    num_experts: int = 0
    moe_fn: Optional[Callable] = None
    moe_aux_weight: float = 0.01

    @nn.compact
    def __call__(self, tokens, train: bool = True):
        embed = nn.Embed(self.vocab, self.dim, dtype=self.dtype, name="embed")
        x = embed(tokens)
        if not self.use_rope:
            t = tokens.shape[-1]
            # the table length must be call-shape-independent once the
            # param exists (flax shape-checks reuse): max_len pins it for
            # decode (where later calls see t=1); default = first-call t
            pos_tab = self.param(
                "pos_embedding", nn.initializers.normal(0.02),
                (self.max_len or t, self.dim),
            )
            if self.decode:
                # KV-cache decoding sees t=1 (or a prompt chunk): take the
                # rows at the CURRENT global positions, tracked by a
                # cursor in the cache — x + pos_tab[None] would silently
                # broadcast the whole table over the short chunk.  Slot
                # mode keeps one cursor per row (each slot is its own
                # request at its own depth).
                pos_index = self.variable(
                    "cache", "pos_index",
                    lambda: jnp.zeros(
                        (tokens.shape[0],) if self.slot_decode else (),
                        jnp.int32),
                )
                if not self.is_initializing():
                    if self.slot_decode:
                        if t != 1 and not self.kv_block_size:
                            raise ValueError(
                                "slot_decode with use_rope=False steps one "
                                f"token per slot (t=1), got t={t}")
                        # each row reads its own t rows of the table from
                        # its cursor (t=1 for the decode step; paged
                        # chunked prefill feeds t=chunk).  The gather
                        # clamps parked slots past the table end — their
                        # output is discarded by the engine anyway
                        pos = (pos_index.value[:, None]
                               + jnp.arange(t)[None, :])  # [B, T]
                        rows = jnp.take(
                            jnp.asarray(pos_tab), pos, axis=0
                        )  # [B, T, dim]
                        pos_index.value = pos_index.value + t
                        x = x + jnp.asarray(rows, self.dtype)
                    else:
                        rows = jax.lax.dynamic_slice(
                            jnp.asarray(pos_tab), (pos_index.value, 0),
                            (t, self.dim),
                        )
                        pos_index.value = pos_index.value + t
                        x = x + jnp.asarray(rows, self.dtype)[None]
                else:
                    x = x + jnp.asarray(pos_tab, self.dtype)[None, :t]
            else:
                x = x + jnp.asarray(pos_tab, self.dtype)[None, :t]
        if self.moe_every:
            # validate up front: a silently-dense "MoE" model (moe_every >
            # depth) or a late per-block error would mask misconfiguration
            if self.moe_fn is None or self.num_experts < 1:
                raise ValueError(
                    "moe_every > 0 needs moe_fn (parallel.ep.moe_apply("
                    "models.moe_expert_fn, mesh, ...)) and num_experts"
                )
            if self.moe_every > self.depth:
                raise ValueError(
                    f"moe_every ({self.moe_every}) > depth ({self.depth}): "
                    "no block would be MoE"
                )
            # decode note: each cache step routes only B tokens (not a
            # mesh multiple) — build the decode moe_fn with
            # pad_tokens=True and an explicit capacity sized for B plus
            # padding headroom (moe_apply enforces both)
        block_cls = maybe_remat(
            DecoderBlock, self.remat and not self.decode, train_argnum=2
        )
        moe_cls = maybe_remat(
            MoEDecoderBlock, self.remat and not self.decode, train_argnum=2
        )
        for i in range(self.depth):
            if self.moe_every and (i + 1) % self.moe_every == 0:
                x = moe_cls(
                    self.num_heads, self.mlp_dim, self.num_experts,
                    self.moe_fn, dtype=self.dtype, dropout=self.dropout,
                    attn_fn=self.attn_fn, use_rope=self.use_rope,
                    decode=self.decode, num_kv_heads=self.num_kv_heads,
                    window=self.window, sinks=self.sinks, norm=self.norm,
                    norm_eps=self.norm_eps, name=f"block{i}",
                    slot_decode=self.slot_decode, ring_slack=self.ring_slack,
                    kv_block_size=self.kv_block_size, kv_blocks=self.kv_blocks,
                    attention_impl=self.attention_impl,
                    kv_quant=self.kv_quant,
                )(x, train)
            else:
                x = block_cls(
                    self.num_heads, self.mlp_dim, dtype=self.dtype,
                    dropout=self.dropout, attn_fn=self.attn_fn,
                    use_rope=self.use_rope, decode=self.decode,
                    num_kv_heads=self.num_kv_heads, window=self.window,
                    sinks=self.sinks, norm=self.norm, mlp=self.mlp,
                    norm_eps=self.norm_eps, name=f"block{i}",
                    slot_decode=self.slot_decode, ring_slack=self.ring_slack,
                    kv_block_size=self.kv_block_size, kv_blocks=self.kv_blocks,
                    attention_impl=self.attention_impl,
                    kv_quant=self.kv_quant,
                )(x, train)
        x = _norm_layer(self.norm, self.dtype, name="final_ln", eps=self.norm_eps)(x)
        if self.tie_embeddings:
            logits = embed.attend(x)  # h @ E^T
        else:
            logits = nn.Dense(self.vocab, dtype=self.dtype, name="head")(x)
        return jnp.asarray(logits, jnp.float32)


def next_token_loss(logits, tokens, mask=None):
    """Mean next-token cross-entropy.

    ``logits`` [B, T, V] (position t predicts token t+1), ``tokens``
    [B, T] int; ``mask`` optional [B, T] (True = count this *target*
    position).  f32 log-softmax regardless of model compute dtype.
    """
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]  # [B, T-1]
    if mask is not None:
        m = mask[:, 1:].astype(nll.dtype)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1)
    return nll.mean()


def lm_loss_fn(model) -> Callable:
    """Adapt an LM to the framework loss signature
    (``fn(params, model_state, batch, train, rng=None)``) so every
    compiled step maker — DP/FSDP/TP — accepts it unchanged.  The batch
    is ``{"tokens": [B, T]}`` with optional ``{"mask": [B, T]}``.

    The loss is the next-token cross-entropy plus, in training, what the
    model sows into ``"losses"``: the routers' load-balance terms
    (``moe_aux``; their mean times ``moe_aux_weight``) and the
    multi-token-prediction terms (``mtp_loss*``; their mean times
    ``mtp_weight``).  A model whose routers balance by a selection bias
    sows no load term.  The model's mutable collections (a router's bias
    and load) are updated by a training step and handed on, as
    ``flax_loss_fn`` hands on batch-norm statistics."""

    def fn(params, model_state, batch, train: bool, rng=None):
        rngs = {"dropout": rng} if (train and rng is not None) else None
        # "losses" is sown anew by every apply; an init leaves a stale
        # copy of it among the model's collections, which is not state
        carried = [k for k in model_state if k != "losses"]
        logits, mutated = model.apply(
            {"params": params, **{k: model_state[k] for k in carried}},
            batch["tokens"], train=train, rngs=rngs,
            mutable=["losses", *(carried if train else ())],
        )
        new_state = ({**model_state, **{k: mutated[k] for k in carried}}
                     if train else model_state)
        loss = next_token_loss(logits, batch["tokens"], batch.get("mask"))
        if train:
            aux, mtp = [], []
            for path, term in jax.tree_util.tree_flatten_with_path(
                    mutated.get("losses", {}))[0]:
                name = str(getattr(path[-2], "key", path[-2]))
                (mtp if name.startswith("mtp_loss") else aux).append(term)
            if mtp and batch.get("mask") is not None:
                raise NotImplementedError(
                    "the multi-token-prediction term takes no mask yet")
            if aux:
                loss = loss + model.moe_aux_weight * sum(aux) / len(aux)
            if mtp:
                loss = loss + model.mtp_weight * sum(mtp) / len(mtp)
        return loss, (new_state, logits)

    if hasattr(model, "step_metrics"):
        # what the step maker reports of the state a step leaves
        fn.step_metrics = model.step_metrics
    return fn


def make_decode_cache(model: TransformerLM, batch: int, total_len: int):
    """Fresh zero KV cache for a ``decode=True`` model, shaped for
    ``batch`` rows out to ``total_len`` tokens.

    Shapes come from an abstract init trace of the FULL length — no
    forward pass, no throwaway parameter materialization.  Shared by
    :func:`generate` (one cache per sampling call) and the continuous-
    batching engine (``serve/engine.py`` — one slot cache plus a batch-1
    prefill template).  Zero-fill is right for K/V and every cursor, but
    the windowed ring's ``slot_pos`` initializer is -1 ("unwritten, never
    attendable") — a zero there would masquerade as a written position-0
    key.
    """
    spec = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0), jnp.zeros((batch, total_len), jnp.int32),
            train=False,
        )
    )["cache"]

    def _cache_leaf(path, s):
        name = getattr(path[-1], "key", None)
        # -1 sentinels: slot_pos ("unwritten, never attendable") and the
        # paged page_table ("unallocated: reads masked, writes dropped")
        if name in ("slot_pos", "page_table"):
            return jnp.full(s.shape, -1, s.dtype)
        # valid_len zero would gate EVERY write out — fresh caches run
        # ungated (decode steps, unpadded prefills); the serving engine
        # arms the gate per padded prefill call
        if name == "valid_len":
            return jnp.full(s.shape, VALID_UNGATED, s.dtype)
        return jnp.zeros(s.shape, s.dtype)

    return jax.tree_util.tree_map_with_path(_cache_leaf, spec)


def generate(
    model: TransformerLM,
    params,
    prompt,
    total_len: int,
    temperature: float = 0.0,
    rng=None,
    top_k: int = 0,
    top_p: float = 1.0,
):
    """Autoregressive sampling with the KV cache, as ONE compiled program.

    ``model`` must be constructed with ``decode=True``.  Learned
    positions (``use_rope=False``, e.g. imported GPT-2) decode through
    the cache's ``pos_index`` cursor and additionally need ``max_len``
    set (and ``total_len <= max_len``).  The prompt [B, P] int32 is
    PREFILLED in one parallel
    full-width forward (writing all P keys/values into the cache at
    once), then a ``lax.scan`` of single-token cache steps samples out
    to ``total_len``: greedy at ``temperature=0``, else softmax
    sampling with ``rng``.  ``top_k`` keeps only the k highest logits
    and ``top_p`` keeps the smallest nucleus with cumulative probability
    >= p (both compose with temperature; 0 / 1.0 disable).  Static
    shapes throughout — one compile per (B, P, total_len).

    Returns tokens [B, total_len] (prompt included).
    """
    if not model.decode:
        raise ValueError("generate() needs a model built with decode=True")
    if model.kv_block_size:
        raise ValueError(
            "generate() decodes through the dense contiguous cache; paged "
            "KV (kv_block_size > 0) is the serving engine's layout — drop "
            "kv_block_size/kv_blocks here, or serve through "
            "serve.LMEngine(layout='paged')")
    if not model.use_rope:
        # learned positions decode via the pos_index cursor — but the
        # table is finite, and dynamic_slice would silently CLAMP past
        # its end (wrong positions, no error); bound it here, host-side
        if model.max_len is None:
            raise ValueError(
                "generate() with use_rope=False needs max_len set on the "
                "model (the learned positional table's length)")
        if total_len > model.max_len:
            raise ValueError(
                f"total_len ({total_len}) exceeds the learned positional "
                f"table (max_len={model.max_len})")
    prompt = jnp.asarray(prompt, jnp.int32)
    bsz, plen = prompt.shape
    if not (0 < plen <= total_len):
        raise ValueError(f"need 0 < prompt len ({plen}) <= total_len ({total_len})")
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 samples stochastically — pass rng "
                         "(a jax.random.PRNGKey) or use temperature=0 for greedy")
    if top_k < 0 or not (0.0 < top_p <= 1.0):
        raise ValueError(f"need top_k >= 0 and 0 < top_p <= 1, got {top_k}, {top_p}")
    if (top_k or top_p < 1.0) and temperature == 0.0:
        raise ValueError("top_k/top_p filter a sampling distribution — "
                         "set temperature > 0 (greedy ignores them)")
    if total_len == plen:
        # score-only: nothing to sample, so skip the prefill forward
        # entirely (its cache and first-token draw would be discarded)
        return prompt
    cache = make_decode_cache(model, bsz, total_len)
    key = rng if rng is not None else jax.random.PRNGKey(0)

    vocab = model.vocab
    k_eff = top_k if 0 < top_k < vocab else 0  # k >= V keeps everything

    def sample(logits, sub):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # filter math in f32: a bf16 cumsum rounds tail probabilities
        # away and saturates below 1.0, silently disabling the nucleus
        # cutoff at realistic vocab sizes (same reason the loss path
        # upcasts its log-softmax)
        logits = logits.astype(jnp.float32) / temperature
        if k_eff or top_p < 1.0:
            # ONE descending sort serves both filters
            sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
            cutoff = jnp.full((logits.shape[0], 1), -jnp.inf, jnp.float32)
            if k_eff:
                cutoff = sorted_logits[:, k_eff - 1 : k_eff]
            if top_p < 1.0:
                # nucleus: keep the smallest prefix (by descending prob)
                # with cumulative probability >= top_p; the first token
                # past the threshold stays in (inclusive convention)
                probs = jax.nn.softmax(sorted_logits, axis=-1)
                cum = jnp.cumsum(probs, axis=-1)
                keep = cum - probs < top_p
                p_cut = jnp.min(
                    jnp.where(keep, sorted_logits, jnp.inf), axis=-1, keepdims=True
                )
                cutoff = jnp.maximum(cutoff, p_cut)
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return jax.random.categorical(sub, logits, axis=-1).astype(jnp.int32)

    # prefill: one parallel pass over the whole prompt
    logits_p, mut = model.apply(
        {"params": params, "cache": cache}, prompt, train=False, mutable=["cache"]
    )
    cache = mut["cache"]
    key, sub = jax.random.split(key)
    first = sample(logits_p[:, -1], sub)

    def step(carry, _):
        cache, tok, key = carry
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            train=False, mutable=["cache"],
        )
        key, sub = jax.random.split(key)
        nxt = sample(logits[:, 0], sub)
        return (mut["cache"], nxt, key), nxt

    (_, _, _), toks = jax.lax.scan(
        step, (cache, first, key), None, length=total_len - plen - 1
    )
    out = jnp.concatenate([prompt, first[:, None], toks.T], axis=1)
    return out


def _validate_pp_boundaries(boundaries, S: int, depth: int, what: str):
    """Planner boundaries sanity: S+1 monotone cut points covering the
    whole stack with >= 1 block per stage.  Returns them as a tuple."""
    b = tuple(int(x) for x in boundaries)
    if len(b) != S + 1:
        raise ValueError(
            f"{what}: boundaries needs S+1 = {S + 1} cut points for the "
            f"{S}-stage pipe axis, got {len(b)} ({list(b)})")
    if b[0] != 0 or b[-1] != depth:
        raise ValueError(
            f"{what}: boundaries must span the whole stack "
            f"(0 .. depth={depth}), got {list(b)}")
    if any(b[s + 1] <= b[s] for s in range(S)):
        raise ValueError(
            f"{what}: every stage needs >= 1 block (strictly increasing "
            f"boundaries), got {list(b)}")
    return b


def _pp_validate_and_stage(model: "TransformerLM", mesh, pipe_axis: str, what: str,
                           blocked: bool = True, boundaries=None):
    """Shared lm_pp/lm_pp_1f1b front half: validate the model is
    pipelineable, and build the per-stage callable.  Returns
    ``(S, V, stage_fn)`` — V logical blocks hosted per pipe device
    (``max(counts)`` under planner ``boundaries``, whose non-uniform
    splits ride a counts-aware ``chunk_stages``).  ``blocked=True``
    wraps V > 1 into one ``chunk_stages`` scan per tick (GPipe / plain
    1F1B); ``blocked=False`` returns the single-block callable for the
    interleaved 1F1B schedule, which applies one logical block per tick
    itself."""
    from ..parallel.pp import chunk_stages

    if not model.use_rope:
        raise ValueError(f"{what} needs use_rope=True (a positional table "
                         "would have to enter mid-pipeline)")
    if model.dropout:
        raise ValueError(f"{what} supports dropout=0 only (no rng stream "
                         "threads through the pipeline schedule)")
    if model.moe_every:
        raise ValueError(
            f"{what} does not support moe_every > 0: MoE and dense blocks "
            "have different param trees, so blocks cannot stack as "
            "homogeneous pipe stages"
        )
    S = mesh.shape[pipe_axis]
    if boundaries is not None:
        if not blocked:
            raise ValueError(
                f"{what}: planner boundaries use the blocked chunk "
                "layout and cannot combine with interleave=True (the "
                "round-robin placement has no contiguous stage ranges)")
        boundaries = _validate_pp_boundaries(boundaries, S, model.depth, what)
        counts = [boundaries[s + 1] - boundaries[s] for s in range(S)]
        V = max(counts)
    else:
        if model.depth % S:
            raise ValueError(
                f"model.depth ({model.depth}) must be a multiple of the "
                f"'{pipe_axis}' axis size ({S}) — or pass a pp plan, "
                "whose non-uniform boundaries lift the divisibility "
                "requirement"
            )
        V = model.depth // S
        counts = None

    blk = DecoderBlock(
        model.num_heads, model.mlp_dim, dtype=model.dtype,
        dropout=0.0, use_rope=model.use_rope, attn_fn=model.attn_fn,
        num_kv_heads=model.num_kv_heads, window=model.window,
        sinks=model.sinks, norm=model.norm, mlp=model.mlp,
        norm_eps=model.norm_eps,
    )

    def base_fn(p, x):
        return blk.apply({"params": p}, x, train=False)

    if counts is not None and V > 1 and any(c != V for c in counts):
        # non-uniform planner split: idle pad chunks cond-skipped per
        # device off the static counts table
        return S, V, chunk_stages(base_fn, counts=counts, axis=pipe_axis)
    return S, V, (base_fn if V == 1 or not blocked else chunk_stages(base_fn))


def _pp_split_params(model: "TransformerLM", mesh, pipe_axis: str, S: int, V: int,
                     placement: str = "blocked", boundaries=None):
    """Shared splitter: full param tree -> ``{"outer", "stages"}`` with
    block trees stacked (``(S, V, ...)`` when V > 1) on a leading dim
    sharded over ``pipe_axis``.

    ``placement`` fixes which logical block lands at ``[device, chunk]``:
    ``"blocked"`` (device s hosts consecutive blocks ``s·V … s·V+V-1`` —
    the ``chunk_stages`` layout both GPipe and plain 1F1B scan over) or
    ``"interleaved"`` (device i's chunk c hosts block ``c·S + i`` — the
    round-robin layout ``pipeline_grads_1f1b(interleave=V)`` schedules).
    Within one placement the two schedules share the tree, so their
    checkpoints/shardings are interchangeable.

    Planner ``boundaries`` replace the uniform blocked grouping with
    the plan's contiguous ranges; devices hosting fewer than
    ``V = max(counts)`` blocks are padded with zero-param chunks the
    counts-aware ``chunk_stages`` never executes (zero grads in, zero
    updates out — the optimizer cannot move them)."""
    from ..parallel.pp import stack_stage_params

    def split_params(params):
        stages = [params[f"block{i}"] for i in range(model.depth)]
        outer = {k: v for k, v in params.items() if not k.startswith("block")}
        if boundaries is not None:
            groups = [list(stages[boundaries[s]:boundaries[s + 1]])
                      for s in range(S)]
            if V > 1:
                pad = jax.tree.map(jnp.zeros_like, stages[0])
                groups = [g + [pad] * (V - len(g)) for g in groups]
                stages = [jax.tree.map(lambda *xs: jnp.stack(xs), *g)
                          for g in groups]
            else:
                stages = [g[0] for g in groups]
        elif V > 1:
            if placement == "interleaved":
                groups = [[stages[c * S + s] for c in range(V)] for s in range(S)]
            else:
                groups = [stages[s * V : (s + 1) * V] for s in range(S)]
            stages = [
                jax.tree.map(lambda *xs: jnp.stack(xs), *g) for g in groups
            ]
        return {
            "outer": outer,
            "stages": stack_stage_params(stages, mesh, pipe_axis),
        }

    return split_params


def _pp_state_shardings(mesh, pipe_axis: str):
    """Shared TrainState sharding builder for the split tree — the
    single implementation lives with the schedule that compiles against
    it (``parallel.pp_1f1b.split_state_shardings``)."""
    from ..parallel.pp_1f1b import split_state_shardings

    return split_state_shardings(mesh, pipe_axis)


def lm_pp(
    model: TransformerLM,
    mesh,
    pipe_axis: str = PIPE_AXIS,
    batch_axis: Optional[str] = None,
    num_microbatches: Optional[int] = None,
    remat: bool = False,
    boundaries=None,
):
    """Pipeline-parallelize the LM: blocks ride the GPipe schedule.

    The decoder stack is the textbook pipeline body — every
    ``DecoderBlock`` preserves the residual-stream shape, so block *i*
    becomes pipe stage *i* (``parallel.pp.pipeline_apply``); the
    embedding lookup, final LayerNorm, and (tied) logits projection
    compose outside the pipelined middle, replicated.

    Returns ``(split_params, loss_fn, state_shardings)``:

    * ``split_params(params)`` maps a full-model param tree to
      ``{"outer": ..., "stages": ...}`` with the S block trees stacked
      on a leading dim sharded over ``pipe_axis``;
    * ``loss_fn`` follows the framework loss signature on the split
      tree (so ``dp.make_train_step`` compiles it unchanged);
    * ``state_shardings(state)`` builds the ``TrainState`` sharding tree
      (outer replicated, stages pipe-sharded, optimizer state
      following its param) to pass as ``state_shardings=``.

    ``batch_axis`` composes data parallelism on a ``(data, pipe)`` mesh.
    Constraints: ``use_rope`` (positions live inside the blocks) and
    ``dropout == 0`` (no rng stream threads through the pipeline ticks).
    ``boundaries`` (a planner's S+1 cut points, ``parallel/pp_plan.py``)
    replaces the uniform block split with the plan's non-uniform stage
    ranges — and lifts the ``depth % S == 0`` requirement.
    """
    from ..parallel.pp import pipeline_apply

    S, V, stage_fn = _pp_validate_and_stage(
        model, mesh, pipe_axis, "lm_pp", boundaries=boundaries)
    fwd = pipeline_apply(
        stage_fn, mesh, axis=pipe_axis, num_microbatches=num_microbatches,
        batch_axis=batch_axis, remat=remat,
    )
    embed = nn.Embed(model.vocab, model.dim, dtype=model.dtype)
    ln = _norm_layer(model.norm, model.dtype, eps=model.norm_eps)
    split_params = _pp_split_params(
        model, mesh, pipe_axis, S, V, boundaries=boundaries)

    def loss_fn(params, model_state, batch, train: bool, rng=None):
        tokens = batch["tokens"]
        outer = params["outer"]
        x = embed.apply({"params": outer["embed"]}, tokens)
        x = fwd(params["stages"], x)
        x = ln.apply({"params": outer["final_ln"]}, x)
        if model.tie_embeddings:
            logits = embed.apply({"params": outer["embed"]}, x, method="attend")
        else:
            logits = nn.Dense(model.vocab, dtype=model.dtype).apply(
                {"params": outer["head"]}, x
            )
        logits = jnp.asarray(logits, jnp.float32)
        return next_token_loss(logits, tokens, batch.get("mask")), (
            model_state, logits,
        )

    return split_params, loss_fn, _pp_state_shardings(mesh, pipe_axis)


class LMPipelineWiring(NamedTuple):
    """Everything ``parallel.pp_1f1b.make_train_step_1f1b`` needs, with
    the interleave factor attached so callers never recompute
    ``depth // S`` by hand (``interleave`` is 1 for blocked placement,
    where the V surplus blocks ride inside ``chunk_stages``)::

        w = lm_pp_1f1b(model, mesh, interleave=True)
        step = make_train_step_1f1b(*w.fns, opt, mesh,
                                    interleave=w.interleave, ...)(state)
        state = TrainState.create(w.split_params(params), opt)
    """

    split_params: Callable
    fns: tuple  # (stage_fn, embed_fn, head_fn)
    state_shardings: Callable
    interleave: int = 1


def lm_pp_1f1b(
    model: TransformerLM,
    mesh,
    pipe_axis: str = PIPE_AXIS,
    interleave: bool = False,
    boundaries=None,
):
    """Pipeline-parallelize the LM on the hand-scheduled 1F1B schedule
    (``parallel.pp_1f1b``) instead of GPipe-via-AD (``lm_pp``).

    Same stage decomposition and the SAME ``split_params`` tree as
    ``lm_pp`` — checkpoints and shardings are interchangeable between
    the two schedules — but activation memory is O(S) ring slots per
    device instead of O(M·ticks) scan residuals, so the microbatch
    count (and with it the bubble (S-1)/(M+S-1)) can grow freely.

    ``interleave=True`` switches the V = depth/S surplus blocks from the
    blocked ``chunk_stages`` layout to the Megatron interleaved
    placement (device i hosts blocks ``c·S + i``): the fill/drain
    bubble shrinks ~V-fold.  NOTE the param layouts differ (round-robin
    vs consecutive), so blocked and interleaved split trees are NOT
    interchangeable.

    Because 1F1B interleaves forwards and backwards, the embedding and
    the final-norm/logits/loss run INSIDE the schedule, per microbatch,
    on pipe devices 0 and S-1; their ("outer") grads are psum'd across
    the pipe axis, which also makes tied embeddings sum correctly.

    Returns an ``LMPipelineWiring`` — feed ``w.fns`` and
    ``w.interleave`` to ``parallel.pp_1f1b.make_train_step_1f1b``
    (``num_microbatches`` and ``batch_axis`` also go THERE: they
    parameterize the schedule, not the stage decomposition).
    Constraints are ``lm_pp``'s (rope, no dropout, no MoE) plus: no
    ``batch["mask"]`` support (the per-microbatch loss reads tokens
    only).  ``boundaries`` (planner cut points) selects a non-uniform
    blocked split exactly as in ``lm_pp`` — the two schedules keep
    sharing one split tree — and cannot combine with ``interleave``.
    """
    S, V, stage_fn = _pp_validate_and_stage(
        model, mesh, pipe_axis, "lm_pp_1f1b", blocked=not interleave,
        boundaries=boundaries)
    embed = nn.Embed(model.vocab, model.dim, dtype=model.dtype)
    ln = _norm_layer(model.norm, model.dtype, eps=model.norm_eps)

    def embed_fn(outer, tokens_mb):
        return embed.apply({"params": outer["embed"]}, tokens_mb)

    def head_fn(outer, y, tokens_mb):
        x = ln.apply({"params": outer["final_ln"]}, y)
        if model.tie_embeddings:
            logits = embed.apply({"params": outer["embed"]}, x, method="attend")
        else:
            logits = nn.Dense(model.vocab, dtype=model.dtype).apply(
                {"params": outer["head"]}, x
            )
        return next_token_loss(jnp.asarray(logits, jnp.float32), tokens_mb)

    return LMPipelineWiring(
        _pp_split_params(model, mesh, pipe_axis, S, V,
                         placement="interleaved" if interleave else "blocked",
                         boundaries=boundaries),
        (stage_fn, embed_fn, head_fn),
        _pp_state_shardings(mesh, pipe_axis),
        V if interleave else 1,
    )


def lm_moe_specs(params, axis: str = EXPERT_AXIS):
    """PartitionSpec tree for an MoE LM's params: expert-stacked leaves
    (``w1/b1/w2/b2`` inside MoE blocks, leading dim E) sharded over
    ``axis``; routers and every dense leaf replicated.  Feed through
    ``parallel.rules.train_state_specs`` + ``sharding.make_shardings`` to get the
    ``state_shardings=`` for ``make_train_step``."""
    from jax.sharding import PartitionSpec as P

    def f(kp, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in kp]
        if len(names) >= 2 and names[-1] in ("w1", "b1", "w2", "b2"):
            return P(axis, *([None] * (leaf.ndim - 1)))
        return P()

    return jax.tree_util.tree_map_with_path(f, params)


def lm_tiny(vocab: int = 256, **kw) -> TransformerLM:
    """Test/CI scale: 4 layers, d=128."""
    kw = {"depth": 4, "dim": 128, "num_heads": 4, "mlp_dim": 512, **kw}
    return TransformerLM(vocab=vocab, **kw)


def lm_small(vocab: int = 32000, **kw) -> TransformerLM:
    """GPT-2-small scale: 12 layers, d=768 (~124M with a 32k vocab)."""
    kw = {"depth": 12, "dim": 768, "num_heads": 12, "mlp_dim": 3072, **kw}
    return TransformerLM(vocab=vocab, **kw)


def lm_medium(vocab: int = 32000, **kw) -> TransformerLM:
    """GPT-2-medium scale: 24 layers, d=1024."""
    kw = {"depth": 24, "dim": 1024, "num_heads": 16, "mlp_dim": 4096, **kw}
    return TransformerLM(vocab=vocab, **kw)
