"""Pallas TPU flash-attention kernel (forward AND backward).

Net-new TPU scope (the reference has no attention and no custom kernels;
its native compute all comes from CUDNN via dependencies — SURVEY §2
"native dependencies").  This is the framework's hand-written hot-op:
fused flash attention that keeps the [block_q, block_k] score tile in
VMEM, accumulates the online softmax in f32 scratch, and never
materializes the [Tq, Tk] score matrix in HBM.

Design (standard TPU flash schedule):

* forward grid = (batch*heads, Tq/block_q, Tk/block_k), KV innermost —
  the TPU grid is sequential per core, so VMEM scratch (acc, m, l)
  carries the online-softmax state across the KV dimension; the kernel
  also emits the per-row logsumexp (LSE) so the backward can recompute
  the block softmax without a second online pass;
* Q/K/V blocks are DMA'd HBM→VMEM by ``pallas_call`` per the BlockSpecs;
  the two matmuls (q·kᵀ and p·v) hit the MXU with f32 accumulation;
* every grid step is classed by where its tile lies against the band of
  attendable pairs (``_tile_class``: the causal diagonal, a window's
  lower edge, sinks, the padded last key block), from scalars alone:
  - **outside** the band: no body, and nothing fetched — the BlockSpec
    index maps name the nearest live block of the same row of the grid
    (``_kv_block_index``, ``_q_block_index``), which the pipeline
    already holds;
  - **inside** it: the body without a mask (no iotas, no compares, no
    selects);
  - **across** its edge: the masked body, and on the plain causal
    diagonal of square blocks the quarter above the diagonal is left
    out (``_across_parts``).
  ``tile_census`` counts the classes for a call's shapes, and the gauge
  ``fdtpu_flash_tiles{kernel, kind}`` holds the census of the call
  traced last;
* backward = two dedicated Pallas kernels (FlashAttention-2 schedule):
  - dQ kernel, grid (BH, Tq/bq, Tk/bk) with KV innermost: recomputes
    p = exp(s − LSE) per tile, folds dS·K into a VMEM f32 accumulator,
    writes dQ once on the last KV step;
  - dK/dV kernel, grid (BH, Tk/bk, Tq/bq) with Q innermost: same tile
    recompute, accumulates Pᵀ·dO and dSᵀ·Q in VMEM, writes dK/dV once
    on the last Q step.
  ``delta = rowsum(dO ∘ O)`` is a cheap XLA elementwise-reduce done
  outside the kernels.  Padded query rows are self-masking: their LSE is
  padded to +1e30 so exp(s − LSE) is exactly 0.  Padded key rows are
  zero, so their dQ contribution (dS·K) vanishes without a mask; their
  dK/dV rows are garbage that the caller slices off.

On non-TPU backends the same kernels run in interpreter mode, so tests
exercise identical code on the CPU CI mesh.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.metrics import get_registry
from .attention import NEG_INF, online_softmax_update

__all__ = [
    "KEPT_OUT",
    "KEPT_LSE",
    "flash_attention",
    "flash_attention_lse",
    "interpret_mode",
    "tile_census",
]

# m/l scratch rows are replicated across the VPU lane width.
_LANES = 128

#: the three kernels' names in a compiled program and so in a device
#: trace's ``XLA Ops`` (forward, dQ, dK/dV): fixed here, so that a trace
#: reducer finds them wherever the call sits (under jvp, remat, a scan)
KERNEL_NAMES = ("fdtpu_flash_fwd", "fdtpu_flash_dq", "fdtpu_flash_dkv")

#: what a forward call made and its backward reads besides q, k, v, under
#: the names ``jax.ad_checkpoint.checkpoint_name`` gives them in the
#: ``custom_vjp`` forward rules: a rematerialised block that saves these
#: two (``models.common.maybe_remat``) never runs the forward kernel again
KEPT_OUT = "fdtpu_flash_out"
KEPT_LSE = "fdtpu_flash_lse"


def interpret_mode() -> bool:
    """Whether Pallas kernels in this process run under the interpreter.

    A pure function of the backend — a per-process constant — resolved
    at TRACE time inside the jitted kernel wrappers, so the flag is NOT
    an argument of any compiled program: it never enters a jit cache
    key or an AOT argument-signature digest
    (``compilation.abstract_signature``), and toggling backends cannot
    retrace anything (there is nothing to toggle within a process).
    CPU-built and TPU-built executables are still keyed apart, by the
    *platform* field of ``compilation.topology_fingerprint`` — the
    correct split: interpretation is a consequence of the platform, not
    an independent axis.  (To run a specific kernel interpreted on TPU,
    use the decode kernels' explicit ``impl="interpret"`` argument or
    ``pltpu.force_tpu_interpret_mode()``.)
    """
    return jax.default_backend() != "tpu"
# LSE pad value for rows beyond Tq: exp(s - 1e30) == 0, so padded query
# rows contribute exactly nothing to dK/dV (and can never produce inf*0
# NaNs the way a garbage LSE could).
_LSE_PAD = 1e30


class _Band(NamedTuple):
    """What decides which (query, key) pairs attend: static for a call.

    Query ``q`` attends key ``k`` when ``k < tk_valid`` and, if
    ``causal``, ``k <= q + causal_offset`` and (with a ``window``) ``k``
    is among the row's ``window`` most recent keys or one of the first
    ``sinks``.  ``causal_offset = Tk - Tq`` end-aligns the diagonal, the
    KV-cache-decode convention of ``dot_product_attention``.  ``padded``
    says the last key block holds columns beyond ``tk_valid``."""

    causal: bool
    causal_offset: int
    window: int | None
    sinks: int
    tk_valid: int
    padded: bool


def _band(tq, tk, block_k, causal, window, sinks):
    return _Band(causal, tk - tq, window, sinks, tk, tk % block_k != 0)


def _tile_class(q_start, k_start, block_q, block_k, band):
    """Where the ``[block_q, block_k]`` tile at ``(q_start, k_start)``
    lies against the band: ``(live, full)``, exact, from scalar
    arithmetic alone (Python ints in ``tile_census``, program ids in the
    kernels and their index maps).

    * **outside** (``not live``): no pair attends — no body, and the
      index maps name a block already in VMEM, so nothing is fetched;
    * **inside** (``full``): every pair attends — the body without a mask;
    * **across** (``live and not full``): the diagonal, a window's lower
      edge, a sink boundary, the padded last block — the masked body.

    With a window this is also where the FLOPs saving comes from:
    far-past key blocks never touch the MXU; ``sinks`` keep the blocks
    of the first keys live from everywhere."""
    k_last = k_start + block_k - 1
    live = True  # a grid's tile always holds one real key column
    full = k_last < band.tk_valid if band.padded else True
    if band.causal:
        hi_first = q_start + band.causal_offset  # last key row 0 sees
        hi_last = hi_first + block_q - 1
        live = k_start <= hi_last
        full &= k_last <= hi_first
        if band.window is not None:
            lo_first = hi_first - (band.window - 1)
            lo_last = hi_last - (band.window - 1)
            in_band = (k_last >= lo_first) & (band.tk_valid - 1 >= lo_first)
            all_in_band = k_start >= lo_last
            if band.sinks:
                in_band |= k_start < band.sinks
                all_in_band |= (band.sinks >= lo_last) | (k_last < band.sinks)
            live &= in_band
            full &= all_in_band
    return live, full


def tile_census(tq, tk, block_q, block_k, causal, window=None, sinks=0):
    """How many tiles of one (row, head)'s grid fall in each class:
    ``{"outside", "inside", "across"}``.  The three kernels walk the same
    tiles (dK/dV in the other order, once per query head), so one census
    serves each; static for a call's shapes, counted at trace time."""
    block_q, block_k = min(block_q, tq), min(block_k, tk)
    band = _band(tq, tk, block_k, causal, window, sinks)
    census = {"outside": 0, "inside": 0, "across": 0}
    for q_start in range(0, tq, block_q):
        for k_start in range(0, tk, block_k):
            live, full = _tile_class(q_start, k_start, block_q, block_k, band)
            census["outside" if not live else
                   "inside" if full else "across"] += 1
    return census


def _publish_census(kernels, *call):
    """``fdtpu_flash_tiles{kernel, kind}``: ``tile_census(*call)`` of the
    call traced last, per (row, head)."""
    census = tile_census(*call)
    gauge = get_registry().gauge(
        "fdtpu_flash_tiles",
        "tiles of one (row, head) by class, of the flash kernel call "
        "traced last", ("kernel", "kind"))
    for kernel in kernels:
        for kind, n in census.items():
            gauge.labels(kernel, kind).set(n)


def _kept(out, lse):
    """A forward rule's two results under their names (``KEPT_OUT``,
    ``KEPT_LSE``), and ``fdtpu_flash_kept_bytes{kernel}``: the bytes of
    the two, of the call traced last."""
    get_registry().gauge(
        "fdtpu_flash_kept_bytes",
        "bytes of out and lse that the flash forward call traced last "
        "keeps for its backward", ("kernel",),
    ).labels(KERNEL_NAMES[0]).set(
        out.size * out.dtype.itemsize + lse.size * lse.dtype.itemsize)
    return checkpoint_name(out, KEPT_OUT), checkpoint_name(lse, KEPT_LSE)


def _kv_block_index(i, j, block_q, block_k, nk, band):
    """The key block that forward / dQ grid step ``(i, j)`` names: ``j``
    on a live step, and on a dead one the nearest live block of row
    ``i`` at or before ``j`` (after it, below a window without sinks) —
    the block the pipeline already holds, so a dead step copies nothing."""
    if not band.causal:
        return j
    hi_first = i * block_q + band.causal_offset
    last = jnp.minimum(
        jnp.maximum(hi_first + block_q - 1, 0) // block_k, nk - 1)
    jj = jnp.minimum(j, last)
    if band.window is not None:
        first = jnp.minimum(
            jnp.maximum(hi_first - (band.window - 1), 0) // block_k, last)
        below = first
        if band.sinks:  # the gap between sinks and band: the last sink block
            below = jnp.minimum(
                j, jnp.minimum((band.sinks - 1) // block_k, last))
        jj = jnp.where(j < first, below, jj)
    return jj


def _q_block_index(j, qi, block_q, block_k, nq, band):
    """The query block that dK/dV grid step ``(j, qi)`` names, the same
    way: the first live query block of key block ``j`` before its run,
    the last one after it (a window's; sink blocks stay live to the end)."""
    if not band.causal:
        return qi
    k_start = j * block_k
    first = jnp.minimum(
        jnp.maximum(k_start - band.causal_offset, 0) // block_q, nq - 1)
    qq = jnp.maximum(qi, first)
    if band.window is not None:
        k_hi = jnp.minimum(k_start + block_k, band.tk_valid) - 1
        reach = k_hi - band.causal_offset + band.window - 1
        last = jnp.clip(jnp.maximum(reach, 0) // block_q, first, nq - 1)
        if band.sinks:
            last = jnp.where(k_start < band.sinks, nq - 1, last)
        qq = jnp.minimum(qq, last)
    return qq


def _band_mask(shape, q_start, k_start, band):
    """The shared fwd/bwd attend-mask of the (sub-)tile whose first pair
    is ``(q_start, k_start)``, for a tile across the band's edge (so the
    call is causal or padded).  Positions are taken relative to the
    tile, so the tile's place enters through scalars only."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = col < band.tk_valid - k_start if band.padded else None
    if band.causal:
        # k <= q + offset  <=>  (k - k_start) - (q - q_start) <= shift
        rel = col - jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        shift = q_start + band.causal_offset - k_start
        ok = rel <= shift
        if band.window is not None:
            in_band = rel >= shift - (band.window - 1)
            if band.sinks:
                # sinks stay attendable (still causally: ``ok`` above)
                in_band |= col < band.sinks - k_start
            ok &= in_band
        mask = ok if mask is None else mask & ok
    return mask


def _across_parts(block_q, block_k, band, by_cols):
    """The (rows, cols) sub-tiles an across tile is computed over.  The
    plain causal diagonal of square blocks leaves out its quarter above
    the diagonal (rows of the first half see nothing in the columns of
    the second), split so that each accumulator row is written once: by
    rows where the kernel accumulates per query (forward, dQ), by
    columns where per key (dK/dV).  Halves stay (16, 128)-tileable from
    blocks of 256 up, also along the lanes the rows' statistics ride;
    every other across tile is one masked whole."""
    if not (band.causal and band.window is None and block_q == block_k
            and block_k % 256 == 0 and band.causal_offset % block_k == 0):
        return ((slice(0, block_q), slice(0, block_k)),)
    h = block_q // 2
    if by_cols:
        return ((slice(0, block_q), slice(0, h)),
                (slice(h, block_q), slice(h, block_k)))
    return ((slice(0, h), slice(0, h)),
            (slice(h, block_q), slice(0, block_k)))


def _by_tile_class(update, q_start, k_start, block_q, block_k, band,
                   by_cols=False):
    """Run ``update(rows, cols, masked)`` as the tile's class asks:
    nothing outside the band, the whole tile unmasked inside it, the
    masked parts of ``_across_parts`` across it."""
    whole = (slice(0, block_q), slice(0, block_k))
    if not (band.causal or band.padded):
        update(*whole, masked=False)  # every tile of the grid is inside
        return
    live, full = _tile_class(q_start, k_start, block_q, block_k, band)
    pl.when(live & full)(lambda: update(*whole, masked=False))

    @pl.when(live & ~full)
    def _across():
        for rows, cols in _across_parts(block_q, block_k, band, by_cols):
            update(rows, cols, masked=True)


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, scale, band,
):
    _, block_q, _ = q_ref.shape
    _, block_k, _ = k_ref.shape
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    def _update(rows, cols, masked):
        # Operands stay in their stored dtype: bf16 inputs ride the
        # MXU's native bf16×bf16→f32-accumulate path (casting to f32
        # first would halve MXU throughput).  The scale multiplies the
        # f32 scores, not the inputs, so no precision is lost to it.
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        s = scale * jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [rows, cols] f32
        mask = _band_mask(
            s.shape, q_start + rows.start, k_start + cols.start, band,
        ) if masked else None
        p, corr, m_new, l_new = online_softmax_update(
            s, m_ref[rows, 0], l_ref[rows, 0], mask=mask
        )
        acc_ref[rows] = acc_ref[rows] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        width = (m_new.shape[0], m_ref.shape[1])
        m_ref[rows] = jnp.broadcast_to(m_new[:, None], width)
        l_ref[rows] = jnp.broadcast_to(l_new[:, None], width)

    _by_tile_class(_update, q_start, k_start, block_q, block_k, band)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # LSE of a fully-masked row is ~NEG_INF; its backward tiles are
        # all-masked anyway, so the value is never observed.
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
              rows, cols, masked, *, scale, band, q_start, k_start):
    """Shared dQ/dKV (sub-)tile recompute: returns (p, ds), both
    [rows, cols] f32.

    ``p`` is the exact forward block softmax, rebuilt from LSE;
    ``ds = p * (dP - delta)`` is the score gradient.  Masked positions
    are zeroed in ``p`` (NEG_INF-before-exp alone is unsafe: a fully-
    masked row has LSE ~ NEG_INF, making exp(s - LSE) explode).  Padded
    K columns are re-masked too: their K rows are zero so a FINITE p
    contributes nothing to dQ, but their score is 0 and exp(0 - LSE)
    can overflow to inf when a row's LSE < ~-88, and inf · 0 = NaN.
    A tile inside the band has neither, so it takes no mask.
    """
    # native-dtype operands → bf16 MXU path, f32 accumulation (see fwd)
    q = q_ref[0, rows]
    k = k_ref[0, cols]
    v = v_ref[0, cols]
    do = do_ref[0, rows]
    lse = lse_ref[0, 0, rows]
    delta = delta_ref[0, 0, rows]
    s = scale * jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [rows, cols] f32
    p = jnp.exp(s - lse[:, None])
    if masked:
        p = jnp.where(_band_mask(
            s.shape, q_start + rows.start, k_start + cols.start, band,
        ), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [rows, cols]
    ds = p * (dp - delta[:, None])
    return p, ds


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref,
    *, scale, band,
):
    _, block_q, _ = q_ref.shape
    _, block_k, _ = k_ref.shape
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[:] = jnp.zeros_like(dq_acc_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    def _update(rows, cols, masked):
        _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            rows, cols, masked,
            scale=scale, band=band, q_start=q_start, k_start=k_start,
        )
        k = k_ref[0, cols]
        dq_acc_ref[rows] += scale * jax.lax.dot_general(
            ds.astype(k.dtype), k,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    _by_tile_class(_update, q_start, k_start, block_q, block_k, band)

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc_ref, dv_acc_ref,
    *, scale, band, nq,
):
    """Inner grid axis t = member * nq + qi: with GQA, each KV head's
    accumulator folds the q-blocks of all `group` query heads sharing
    it (group == 1 degenerates to t == qi)."""
    _, block_q, _ = q_ref.shape
    _, block_k, _ = k_ref.shape
    kj = pl.program_id(1)
    t = pl.program_id(2)
    ntot = pl.num_programs(2)
    qi = t % nq

    @pl.when(t == 0)
    def _init():
        dk_acc_ref[:] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[:] = jnp.zeros_like(dv_acc_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    def _update(rows, cols, masked):
        p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            rows, cols, masked,
            scale=scale, band=band, q_start=q_start, k_start=k_start,
        )
        do = do_ref[0, rows]
        q = q_ref[0, rows]
        dv_acc_ref[cols] += jax.lax.dot_general(
            p.astype(do.dtype), do,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # pᵀ·dO: contract over the q dimension → [cols, d]
        dk_acc_ref[cols] += scale * jax.lax.dot_general(
            ds.astype(q.dtype), q,
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # dSᵀ·Q → [cols, d]

    _by_tile_class(_update, q_start, k_start, block_q, block_k, band,
                   by_cols=True)

    @pl.when(t == ntot - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[:].astype(dv_ref.dtype)


def _pad_seq(x, block):
    pad = -x.shape[1] % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _fold(x):
    """[B, T, H, D] → [B*H, T, D] (the kernels' layout)."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold(x, b, h, t):
    return x[:, :t].reshape(b, h, t, x.shape[-1]).transpose(0, 2, 1, 3)


def _gqa_dims(q, k):
    """(h, hkv, group) with the divisibility check — GQA folds q heads
    into batch as usual while the BlockSpec index maps point each group
    of query heads at its SHARED KV head, so grouped KV is never
    repeated in HBM (the whole point of GQA's memory saving)."""
    h, hkv = q.shape[2], k.shape[2]
    if h % hkv:
        raise ValueError(
            f"num query heads ({h}) must be a multiple of num KV heads "
            f"({hkv}) for grouped-query attention")
    return h, hkv, h // hkv


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "window", "sinks"),
)
def _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                    window=None, sinks=0):
    # trace-time constant (per-process) — deliberately NOT an argument,
    # so it cannot enter jit/AOT signature digests (see interpret_mode)
    interpret = interpret_mode()
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    h, hkv, group = _gqa_dims(q, k)
    scale = 1.0 / (d**0.5)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)

    # Fold heads into batch: kernel operates on [BH, T, D].
    qf = _pad_seq(_fold(q), block_q)
    kf = _pad_seq(_fold(k), block_k)
    vf = _pad_seq(_fold(v), block_k)
    tq_p, tk_p = qf.shape[1], kf.shape[1]

    def kv_bh(bh):  # query-head program → its KV head's fold index
        return (bh // h) * hkv + (bh % h) // group

    nk = tk_p // block_k
    band = _band(tq, tk, block_k, causal, window, sinks)
    _publish_census(
        KERNEL_NAMES[:1], tq, tk, block_q, block_k, causal, window, sinks)
    kv_spec = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_k, width), lambda bh, i, j: (
            kv_bh(bh), _kv_block_index(i, j, block_q, block_k, nk, band), 0))
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, band=band),
        grid=(b * h, tq_p // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            kv_spec(d),
            kv_spec(dv),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda bh, i, j: (bh, i, 0)),
            # per-row stats ride as [BH, 1, Tq] rows: a (1, block_q)
            # block of a 2-D [BH, Tq] array is not (8, 128)-tileable
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, tq_p), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES[0],
    )(qf, kf, vf)
    return _unfold(out, b, h, tq), lse[:, 0, :tq]


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "window", "sinks"),
)
def _flash_bwd_impl(q, k, v, o, lse, g, causal, block_q, block_k,
                    g_lse=None, window=None, sinks=0):
    interpret = interpret_mode()
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]
    h, hkv, group = _gqa_dims(q, k)
    scale = 1.0 / (d**0.5)
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)

    qf = _pad_seq(_fold(q), block_q)
    kf = _pad_seq(_fold(k), block_k)
    vf = _pad_seq(_fold(v), block_k)
    dof = _pad_seq(_fold(g), block_q)
    of = _pad_seq(_fold(o), block_q)
    tq_p, tk_p = qf.shape[1], kf.shape[1]

    # delta_i = Σ_d dO ∘ O — one XLA fusion; zero on padded rows (dO pad).
    delta = (dof.astype(jnp.float32) * of.astype(jnp.float32)).sum(-1)
    if g_lse is not None:
        # Upstream gradient into the LSE output: ∂lse_r/∂s_rc = p_rc, so
        # ds = p∘(dP − delta + g_lse) — fold it into delta, the kernels
        # are untouched.  g_lse: [BH, tq] f32.
        delta = delta - jnp.pad(
            g_lse.astype(jnp.float32), ((0, 0), (0, tq_p - tq))
        )
    lse_p = jnp.pad(
        lse, ((0, 0), (0, tq_p - tq)), constant_values=_LSE_PAD
    )[:, None]
    delta = delta[:, None]

    nq, nk = tq_p // block_q, tk_p // block_k
    bh = b * h

    def kv_bh(bh_):  # query-head program → its KV head's fold index
        return (bh_ // h) * hkv + (bh_ % h) // group

    def q_bh(bh_, t):  # (KV-head program, inner step) → q-head fold index
        return (bh_ // hkv) * h + (bh_ % hkv) * group + t // nq

    band = _band(tq, tk, block_k, causal, window, sinks)
    _publish_census(
        KERNEL_NAMES[1:], tq, tk, block_q, block_k, causal, window, sinks)

    def inner_q(j, t):  # dK/dV step → the query block it names
        return _q_block_index(j, t % nq, block_q, block_k, nq, band)

    q_spec_i = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_q, width), lambda bh_, i, j: (bh_, i, 0))
    kv_spec_j = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_k, width), lambda bh_, i, j: (
            kv_bh(bh_), _kv_block_index(i, j, block_q, block_k, nk, band), 0))
    # lse/delta as [BH, 1, Tq] rows (see the forward's LSE out_spec)
    row_spec_i = pl.BlockSpec(
        (1, 1, block_q), lambda bh_, i, j: (bh_, 0, i))
    # dKV grid is (b*hkv, j, t) where the inner axis t enumerates the
    # nq q-blocks of each of the `group` query heads sharing this KV
    # head: t = member * nq + qi.
    q_spec_inner = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_q, width), lambda bh_, j, t: (q_bh(bh_, t), inner_q(j, t), 0))
    kv_spec_outer = lambda width: pl.BlockSpec(  # noqa: E731
        (1, block_k, width), lambda bh_, j, t: (bh_, j, 0))
    row_spec_inner = pl.BlockSpec(
        (1, 1, block_q), lambda bh_, j, t: (q_bh(bh_, t), 0, inner_q(j, t)))

    common = dict(scale=scale, band=band)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=[q_spec_i(d), kv_spec_j(d), kv_spec_j(dv), q_spec_i(dv),
                  row_spec_i, row_spec_i],
        out_specs=q_spec_i(d),
        out_shape=jax.ShapeDtypeStruct((bh, tq_p, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name=KERNEL_NAMES[1],
    )(qf, kf, vf, dof, lse_p, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, **common, nq=nq),
        grid=(b * hkv, nk, nq * group),
        in_specs=[q_spec_inner(d), kv_spec_outer(d), kv_spec_outer(dv),
                  q_spec_inner(dv), row_spec_inner, row_spec_inner],
        out_specs=[kv_spec_outer(d), kv_spec_outer(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, tk_p, d), k.dtype),
            jax.ShapeDtypeStruct((b * hkv, tk_p, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=interpret,
        name=KERNEL_NAMES[2],
    )(qf, kf, vf, dof, lse_p, delta)

    return (
        _unfold(dq, b, h, tq),
        _unfold(dk, b, hkv, tk),
        _unfold(dv, b, hkv, tk),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    window: int | None = None,
    sinks: int = 0,
) -> jax.Array:
    """Fused flash attention, [B, T, H, D] → [B, T, H, Dv].

    Runs the Pallas TPU kernels on TPU and the same kernels under the
    Pallas interpreter elsewhere (so CPU tests cover the real kernels),
    forward and backward.  Numerics match ``dot_product_attention`` to
    f32 accumulation.  Grouped-query KV ([B, T, Hkv, D]) is consumed
    natively (never repeated in HBM).  ``v`` may have a feature width of
    its own (``Dv``), which the output and ``v``'s gradient take; ``q``
    and ``k`` share ``D``, which sets the scale.  ``window`` (requires ``causal``)
    restricts each query to its ``window`` most recent keys — KV blocks
    outside the band are neither fetched nor computed, so long-T cost is
    O(T·window), not O(T²).  ``sinks`` (StreamingLLM attention sinks;
    needs ``window``) keeps the first ``sinks`` key positions always
    attendable — their blocks stay live while everything between sink
    and band is skipped.
    """
    _validate_window(causal, window, sinks)
    out, _ = _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                             window=window, sinks=sinks)
    return out


def _validate_window(causal, window, sinks):
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal-LM construct)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if sinks:
        if sinks < 0:
            raise ValueError(f"sinks must be >= 0, got {sinks}")
        if window is None:
            raise ValueError("sinks only make sense with a window "
                             "(unwindowed causal attention already "
                             "attends every past position)")


def _fwd(q, k, v, causal, block_q, block_k, window, sinks):
    # custom_vjp skips the primal body under jax.grad — re-validate here
    # or invalid combos would silently trace through in training steps
    _validate_window(causal, window, sinks)
    out, lse = _kept(*_flash_fwd_impl(q, k, v, causal, block_q, block_k,
                                      window=window, sinks=sinks))
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, window, sinks, res, g):
    q, k, v, o, lse = res
    return _flash_bwd_impl(
        q, k, v, o, lse, g, causal, block_q, block_k,
        window=window, sinks=sinks,
    )


flash_attention.defvjp(_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    window: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Flash attention that ALSO returns the per-row logsumexp.

    → ``(out [B, Tq, H, Dv], lse [B, H, Tq] f32)`` where
    ``lse = log Σ_k exp(q·kᵀ/√D)``.  The LSE output is differentiable
    (its gradient folds into the same Pallas backward kernels), which is
    what lets ring attention use this kernel as its per-hop block
    compute and combine hops by LSE weighting.  Rows with no attendable
    position have ``lse ≈ -1e30`` (their combine weight underflows to
    exactly 0).
    """
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal-LM construct)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                               window=window)
    b, tq, h, _ = q.shape
    return out, lse.reshape(b, h, tq)


def _fwd_lse(q, k, v, causal, block_q, block_k, window):
    out, lse = _kept(*_flash_fwd_impl(q, k, v, causal, block_q, block_k,
                                      window=window))
    b, tq, h, _ = q.shape
    return (out, lse.reshape(b, h, tq)), (q, k, v, out, lse)


def _bwd_lse(causal, block_q, block_k, window, res, g):
    q, k, v, o, lse = res
    g_out, g_lse = g
    b, tq, h, _ = q.shape
    return _flash_bwd_impl(
        q, k, v, o, lse, g_out, causal, block_q, block_k,
        g_lse=g_lse.reshape(b * h, tq), window=window,
    )


flash_attention_lse.defvjp(_fwd_lse, _bwd_lse)
