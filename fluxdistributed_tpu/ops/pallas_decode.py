"""Pallas TPU flash-decode attention: one query row vs the KV cache.

The serve engine's hot loop is the all-slot decode step — every live
request contributes ONE query row attending against its cache — and
``ops/pallas_attention.py``'s training kernel is the wrong shape for it
(its whole schedule amortizes over many query rows; a decode call would
pay a full [block_q, block_k] tile for one live row).  This module is
the decode-shaped member of the kernel family, and it understands the
engine's cache layouts NATIVELY (ROADMAP Open item 2):

* **dense slot cache** ``[B, R, Hkv, D]`` with per-slot cursors
  ``idx [B]`` — KV blocks wholly above a slot's cursor are skipped
  (no MXU work, data-dependent ``pl.when``), so cost tracks the LIVE
  prefix, not the reserved ``max_len``;
* **windowed ring + attention sinks** — the ring is already compact
  (``sinks + window`` rows), so the kernel iterates the ring
  blocks directly and recovers causality from the ``slot_pos`` side
  buffer: no gather, no scatter, and no dead full-length cache rows to
  mask (the band mask is over ring slots, not absolute positions);
* **paged block pool** ``[NB, bs, Hkv, D]`` — the kernel WALKS the
  per-slot int32 page table: each grid step DMAs the physical block the
  table names (scalar-prefetch index map), unbound pages (``-1``) are
  skipped, and the gather/reshape the XLA path pays per step never
  happens.

Grouped-query attention is native: the grid runs over ``B × blocks``,
each program holds one whole-row K/V block (all ``Hkv`` heads — the
only block of ``[.., Hkv, D]`` a TPU can tile) and, per KV head,
attends all ``H/Hkv`` query heads of its group against the SHARED KV
slice ([group, block] score tiles — decode's MXU utilization comes from
the group dimension).  Quantized caches (int8 / fp8 K/V with
per-row-per-head scales, ``models/transformer_lm.py``) dequantize
INSIDE the kernel — HBM traffic shrinks by the storage dtype, and the
f32 dequant rides the VPU between the DMA and the MXU.

Three implementations behind one call (``impl=``):

* ``"pallas"`` — the compiled TPU kernel (default on TPU);
* ``"interpret"`` — the SAME kernel under the Pallas interpreter (what
  the CPU parity tests run, so kernel code is exercised off-TPU);
* ``"xla"`` — a fallback that executes the kernel's exact block-walk
  schedule (same online softmax, same block skipping, same page-table
  walk, `lax.cond`-guarded per block) as plain XLA ops.  This is the
  default off TPU: the Pallas interpreter copies whole buffers per grid
  step and is orders of magnitude slower, while this fallback keeps the
  algorithmic wins — block skip beyond the cursor and no dead-page
  gather — measurable on CPU (benchmarks/attention_bench.py --decode).

Numerics match ``dot_product_attention`` to f32 accumulation on every
path (the shared ``online_softmax_update``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_INF, online_softmax_update
from .pallas_attention import interpret_mode

__all__ = ["flash_decode", "flash_decode_paged", "resolve_decode_impl"]

_LANES = 128


def resolve_decode_impl(impl: str | None = None) -> str:
    """``None``/``"auto"`` → ``"pallas"`` on TPU, the ``"xla"``
    block-walk fallback elsewhere (the interpreter is for parity tests,
    never the default — it is slower than either real path).  Pass
    ``impl="interpret"`` explicitly to run the real kernel under the
    interpreter anywhere (how the CPU kernel-parity tests drive it)."""
    if impl in (None, "auto"):
        return "pallas" if not interpret_mode() else "xla"
    if impl not in ("pallas", "interpret", "xla"):
        raise ValueError(
            f"unknown decode impl {impl!r} (pallas|interpret|xla|auto)")
    return impl


def _validate(window, sinks, slot_pos, k_scale, v_scale):
    if (window is None) != (slot_pos is None):
        raise ValueError(
            "windowed decode needs BOTH window= and slot_pos= (the ring's "
            "position side buffer); plain decode needs neither")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if sinks and window is None:
        raise ValueError("sinks only make sense with a window")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("quantized decode needs BOTH k_scale and v_scale")


def _gqa_fold(q):
    """[B, 1, H, D] → [B, Hkv-major] layout pieces: (q4, b, h, d)."""
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(
            f"flash decode takes one query row per slot: q must be "
            f"[B, 1, H, D], got {q.shape}")
    b, _, h, d = q.shape
    return q[:, 0], b, h, d


def _group_dims(h, hkv):
    if h % hkv:
        raise ValueError(
            f"num query heads ({h}) must be a multiple of num KV heads "
            f"({hkv}) for grouped-query attention")
    return h // hkv


# ---------------------------------------------------------------------------
# The XLA fallback: the kernel's schedule as plain ops
# ---------------------------------------------------------------------------


def _xla_block_walk(qh, idx, nblocks, block_rows, get_block, get_mask):
    """Shared fallback loop: online softmax over KV blocks with a
    ``lax.cond`` skip per block — dead blocks (beyond every cursor /
    unbound pages / unwritten ring slots) cost one predicate, not a
    gather + matmul.  ``qh``: [B, Hkv, G, D] f32, pre-scaled."""
    b, hkv, g, d = qh.shape
    acc = jnp.zeros((b, hkv, g, d), jnp.float32)
    m = jnp.full((b, hkv, g), NEG_INF, jnp.float32)
    l = jnp.zeros((b, hkv, g), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        allow = get_mask(j)  # [B, block_rows] bool — cheap (no K/V touch)

        def live(carry):
            acc, m, l = carry
            kb, vb = get_block(j)  # [B, block_rows, Hkv, D] f32 each
            s = jnp.einsum("bhgd,bkhd->bhgk", qh, kb,
                           preferred_element_type=jnp.float32)
            p, corr, m2, l2 = online_softmax_update(
                s, m, l, mask=allow[:, None, None, :])
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bhgk,bkhd->bhgd", p, vb, preferred_element_type=jnp.float32)
            return acc2, m2, l2

        return jax.lax.cond(jnp.any(allow), live, lambda c: c, carry)

    if nblocks <= 4:
        # compact caches (windowed rings, short reserved rows): the
        # loop/cond dispatch overhead outweighs any skip — unroll and
        # let XLA fuse the handful of block updates into one program
        carry = (acc, m, l)
        for j in range(nblocks):
            carry = body(j, carry)
        acc, m, l = carry
    else:
        acc, m, l = jax.lax.fori_loop(0, nblocks, body, (acc, m, l))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _dequant(x, scale):
    x = x.astype(jnp.float32)
    return x if scale is None else x * scale.astype(jnp.float32)[..., None]


# ---------------------------------------------------------------------------
# Pallas kernels (dense + paged share the body via masking closures)
# ---------------------------------------------------------------------------


def _decode_kernel(refs, *, scale, window, sinks, hkv, d, block_rows,
                   windowed, quant, paged):
    """One (slot, KV-block) grid step of flash decode, all KV heads.

    ``refs`` is the flat pallas argument list: scalar-prefetch refs
    first (idx; page table too when paged), then inputs (q, k, v
    [, slot_pos][, k_scale, v_scale]), then the output and the
    (acc, m, l) scratch.  KV innermost — the grid is sequential per
    core, so scratch carries the online softmax across blocks.

    K/V blocks arrive as ``[block_rows, Hkv*D]`` — the cache's own row
    layout with the two minor axes merged (a free reshape), because a
    one-head ``(block_rows, 1, D)`` block of ``[.., Hkv, D]`` is not
    (8, 128)-tileable on TPU.  Each head is a static lane slice.
    """
    i = 0
    if paged:
        pt_ref = refs[i]; i += 1
    idx_ref = refs[i]; i += 1
    q_ref = refs[i]; i += 1
    k_ref = refs[i]; i += 1
    v_ref = refs[i]; i += 1
    sp_ref = None
    if windowed:
        sp_ref = refs[i]; i += 1
    ks_ref = vs_ref = None
    if quant:
        ks_ref = refs[i]; i += 1
        vs_ref = refs[i]; i += 1
    o_ref = refs[i]; i += 1
    acc_ref, m_ref, l_ref = refs[i:]

    b = pl.program_id(0)
    j = pl.program_id(1)
    nk = pl.num_programs(1)
    group = q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    cursor = idx_ref[b]
    if windowed:
        # ring slots carry their global position (-1 = unwritten); band
        # semantics are recovered from positions, never from slot order
        sp = sp_ref[...].reshape(1, block_rows)  # int32 row
        allow = (sp >= 0) & (sp <= cursor)
        band = sp > cursor - window
        if sinks:
            band |= sp < sinks
        allow &= band
        allow = jnp.broadcast_to(allow, (group, block_rows))
    else:
        pos = j * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, (group, block_rows), 1)
        allow = pos <= cursor
    if paged:
        allow &= pt_ref[b, j] >= 0  # unbound page: every row dead

    def _body():
        for h in range(hkv):  # static: one lane slice per KV head
            q = q_ref[0, h]  # [group, D]
            k = k_ref[0, :, h * d:(h + 1) * d]  # [block_rows, D]
            v = v_ref[0, :, h * d:(h + 1) * d]
            if quant:
                k = k.astype(jnp.float32) * ks_ref[0, :, h:h + 1]
                v = v.astype(jnp.float32) * vs_ref[0, :, h:h + 1]
            s = scale * jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)  # [group, block_rows]
            p, corr, m_new, l_new = online_softmax_update(
                s, m_ref[h, :, 0], l_ref[h, :, 0], mask=allow)
            acc_ref[h] = acc_ref[h] * corr[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new[:, None], m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new[:, None], l_ref.shape[1:])

    # dead blocks (above every cursor / out of band / unbound page)
    # skip the MXU entirely — this is where decode cost becomes
    # O(live tokens) instead of O(reserved rows)
    pl.when(jnp.any(allow))(_body)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _pad_rows(x, block, fill=0):
    pad = -x.shape[1] % block
    if pad:
        cfg = [(0, 0)] * x.ndim
        cfg[1] = (0, pad)
        x = jnp.pad(x, cfg, constant_values=fill)
    return x


# ---------------------------------------------------------------------------
# dense slot cache
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("window", "sinks", "block_k", "impl"),
)
def _flash_decode_impl(q, k, v, idx, slot_pos, k_scale, v_scale,
                       window, sinks, block_k, impl):
    qh, b, h, d = _gqa_fold(q)
    hkv = k.shape[2]
    group = _group_dims(h, hkv)
    r = k.shape[1]
    scale = 1.0 / (d ** 0.5)
    block_k = min(block_k, r)
    idx = idx.astype(jnp.int32)

    if impl == "xla":
        q4 = qh.reshape(b, hkv, group, d).astype(jnp.float32) * scale
        nb = -(-r // block_k)

        def get_block(j):
            kb = jax.lax.dynamic_slice_in_dim(k, j * block_k, block_k, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, j * block_k, block_k, 1)
            ks = vs = None
            if k_scale is not None:
                ks = jax.lax.dynamic_slice_in_dim(
                    k_scale, j * block_k, block_k, 1)
                vs = jax.lax.dynamic_slice_in_dim(
                    v_scale, j * block_k, block_k, 1)
            return _dequant(kb, ks), _dequant(vb, vs)

        def get_mask(j):
            if window is None:
                pos = j * block_k + jnp.arange(block_k)
                return pos[None, :] <= idx[:, None]
            sp = jax.lax.dynamic_slice_in_dim(
                slot_pos, j * block_k, block_k, 1)
            qg = idx[:, None]
            allow = (sp >= 0) & (sp <= qg)
            band = sp > qg - window
            if sinks:
                band |= sp < sinks
            return allow & band

        if r % block_k:  # pad once so the loop's slices are uniform
            k = _pad_rows(k, block_k)
            v = _pad_rows(v, block_k)
            if slot_pos is not None:
                slot_pos = _pad_rows(slot_pos, block_k, fill=-1)
            if k_scale is not None:
                k_scale = _pad_rows(k_scale, block_k)
                v_scale = _pad_rows(v_scale, block_k)
        out = _xla_block_walk(q4, idx, nb, block_k, get_block, get_mask)
        return out.reshape(b, 1, h, d).astype(q.dtype)

    # pallas / interpret: pad the row axis to whole blocks (pad slot_pos
    # with -1 = never attendable; pad positions exceed any cursor)
    kp = _pad_rows(k, block_k)
    vp = _pad_rows(v, block_k)
    nb = kp.shape[1] // block_k
    q4 = qh.reshape(b, hkv, group, d)
    windowed = window is not None
    quant = k_scale is not None

    # whole-row blocks over the merged [.., Hkv*D] minor axis (see
    # _decode_kernel); the grid walks slots x KV blocks
    qo_spec = pl.BlockSpec((1, hkv, group, d), lambda b_, j, idx: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, hkv * d), lambda b_, j, idx: (b_, j, 0))
    in_specs = [qo_spec, kv_spec, kv_spec]
    args = [q4, kp.reshape(b, -1, hkv * d), vp.reshape(b, -1, hkv * d)]
    if windowed:
        # [B, 1, R] rows: a (1, block_k) block of [B, R] is not tileable
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b_, j, idx: (b_, 0, j)))
        args.append(_pad_rows(slot_pos, block_k, fill=-1)
                    .astype(jnp.int32)[:, None])
    if quant:
        spec = pl.BlockSpec((1, block_k, hkv), lambda b_, j, idx: (b_, j, 0))
        in_specs += [spec, spec]
        args += [_pad_rows(k_scale, block_k).astype(jnp.float32),
                 _pad_rows(v_scale, block_k).astype(jnp.float32)]

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, sinks=sinks, hkv=hkv,
        d=d, block_rows=block_k, windowed=windowed, quant=quant, paged=False)
    out = pl.pallas_call(
        lambda *refs: kernel(refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=in_specs,
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, group, d), jnp.float32),
                pltpu.VMEM((hkv, group, _LANES), jnp.float32),
                pltpu.VMEM((hkv, group, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=impl == "interpret",
    )(idx, *args)
    return out.reshape(b, 1, h, d)


def flash_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    idx: jax.Array,
    *,
    slot_pos: jax.Array | None = None,
    window: int | None = None,
    sinks: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    block_k: int = 128,
    impl: str | None = None,
) -> jax.Array:
    """Flash decode over a dense slot cache.

    ``q`` [B, 1, H, D] (ONE query row per slot), ``k``/``v``
    [B, R, Hkv, D] (the slot cache AFTER this step's write), ``idx``
    [B] int32 per-slot cursors (the position of this step's token).
    Plain caches attend positions ``<= idx`` with KV blocks beyond the
    cursor skipped; windowed rings pass ``slot_pos`` [B, R] (+
    ``window``/``sinks``) and the band mask runs over ring slots.
    Quantized caches pass ``k_scale``/``v_scale`` [B, R, Hkv] — dequant
    happens inside the kernel.  → [B, 1, H, D]; slots with nothing
    attendable return exactly 0.
    """
    _validate(window, sinks, slot_pos, k_scale, v_scale)
    return _flash_decode_impl(
        q, k, v, idx, slot_pos, k_scale, v_scale,
        window=window, sinks=sinks, block_k=block_k,
        impl=resolve_decode_impl(impl))


# ---------------------------------------------------------------------------
# paged block pool
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("window", "sinks", "impl"),
)
def _flash_decode_paged_impl(q, k_pool, v_pool, page_table, idx, slot_pos,
                             k_scale, v_scale, window, sinks, impl):
    qh, b, h, d = _gqa_fold(q)
    nb_pool, bs, hkv, _ = k_pool.shape
    group = _group_dims(h, hkv)
    pages = page_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    idx = idx.astype(jnp.int32)
    pt = page_table.astype(jnp.int32)

    if impl == "xla":
        q4 = qh.reshape(b, hkv, group, d).astype(jnp.float32) * scale

        def get_block(j):
            blk = jnp.maximum(pt[:, j], 0)
            kb, vb = k_pool[blk], v_pool[blk]  # [B, bs, Hkv, D]
            ks = vs = None
            if k_scale is not None:
                ks, vs = k_scale[blk], v_scale[blk]
            return _dequant(kb, ks), _dequant(vb, vs)

        def get_mask(j):
            bound = pt[:, j] >= 0
            if window is None:
                pos = j * bs + jnp.arange(bs)
                allow = pos[None, :] <= idx[:, None]
            else:
                sp = jax.lax.dynamic_slice_in_dim(slot_pos, j * bs, bs, 1)
                qg = idx[:, None]
                allow = (sp >= 0) & (sp <= qg)
                band = sp > qg - window
                if sinks:
                    band |= sp < sinks
                allow &= band
            return allow & bound[:, None]

        out = _xla_block_walk(q4, idx, pages, bs, get_block, get_mask)
        return out.reshape(b, 1, h, d).astype(q.dtype)

    q4 = qh.reshape(b, hkv, group, d)
    windowed = window is not None
    quant = k_scale is not None

    def kv_map(b_, j, pt, idx):
        # THE page-table walk: the physical block this grid step DMAs
        # is named by the slot's page table (clamped for -1; the kernel
        # masks the whole block via pt[b, j] < 0)
        return (jnp.maximum(pt[b_, j], 0), 0, 0)

    # whole-row blocks over the merged [.., Hkv*D] minor axis (see
    # _decode_kernel); the grid walks slots x pages
    qo_spec = pl.BlockSpec(
        (1, hkv, group, d), lambda b_, j, pt, idx: (b_, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, bs, hkv * d), kv_map)
    in_specs = [qo_spec, kv_spec, kv_spec]
    args = [q4, k_pool.reshape(nb_pool, bs, hkv * d),
            v_pool.reshape(nb_pool, bs, hkv * d)]
    if windowed:
        # [B, P, 1, bs]: one page's positions as a full (1, bs) tile
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, bs), lambda b_, j, pt, idx: (b_, j, 0, 0)))
        args.append(slot_pos.astype(jnp.int32).reshape(b, pages, 1, bs))
    if quant:
        spec = pl.BlockSpec((1, bs, hkv), kv_map)
        in_specs += [spec, spec]
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]

    kernel = functools.partial(
        _decode_kernel, scale=scale, window=window, sinks=sinks, hkv=hkv,
        d=d, block_rows=bs, windowed=windowed, quant=quant, paged=True)
    out = pl.pallas_call(
        lambda *refs: kernel(refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, pages),
            in_specs=in_specs,
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((hkv, group, d), jnp.float32),
                pltpu.VMEM((hkv, group, _LANES), jnp.float32),
                pltpu.VMEM((hkv, group, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, d), q.dtype),
        interpret=impl == "interpret",
    )(pt, idx, *args)
    return out.reshape(b, 1, h, d)


def flash_decode_paged(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    page_table: jax.Array,
    idx: jax.Array,
    *,
    slot_pos: jax.Array | None = None,
    window: int | None = None,
    sinks: int = 0,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    impl: str | None = None,
) -> jax.Array:
    """Flash decode over the paged block pool.

    ``q`` [B, 1, H, D]; ``k_pool``/``v_pool`` [NB, bs, Hkv, D] (the
    shared per-layer pools AFTER this step's write); ``page_table``
    [B, P] int32 (-1 = unbound: the block is skipped, not gathered);
    ``idx`` [B] cursors.  Windowed rings pass ``slot_pos`` [B, P*bs];
    quantized pools pass ``k_scale``/``v_scale`` [NB, bs, Hkv].  The
    page indirection stays DATA (scalar-prefetched index maps), so one
    compiled kernel serves every allocation decision — the engine's
    ONE-decode-compile invariant extends into the kernel.
    """
    _validate(window, sinks, slot_pos, k_scale, v_scale)
    return _flash_decode_paged_impl(
        q, k_pool, v_pool, page_table, idx, slot_pos, k_scale, v_scale,
        window=window, sinks=sinks, impl=resolve_decode_impl(impl))
