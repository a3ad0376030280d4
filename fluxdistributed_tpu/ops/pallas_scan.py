"""The selective scan of a Mamba layer (Gu & Dao, arXiv:2312.00752), as
Pallas TPU kernels, forward and backward.

For each row, channel ``c`` and state ``n``::

    s[t, c, n] = exp(delta[t, c] A[c, n]) s[t-1, c, n]
                 + delta[t, c] u[t, c] B[t, n]
    y[t, c]    = sum_n s[t, c, n] C[t, n] + D[c] u[t, c]

with ``s[-1] = 0``, all in float32.  The state of every position,
``[T, C, N]`` a row, is what a plain ``jax.lax.associative_scan`` would
make (2.7 GB a layer and direction at a Mamba layer's ``d_inner`` of
5,120 over 8,192 tokens); these kernels keep it in VMEM instead:

* **forward** (``fdtpu_scan_fwd``): grid ``(rows, channel blocks, time
  chunks)``, the chunks innermost and in order.  A block's ``[N, block]``
  state lives in VMEM scratch across the chunks; inside a chunk the
  kernel walks the positions in order, ``UNROLL`` a loop step
  (``_walk``), the channels on the vector lanes and the states on the
  sublanes.  It writes ``y`` and
  the state each chunk starts from: ``T / chunk`` states a row, not
  ``T``.
* **backward** (``fdtpu_scan_bwd``): the same grid with the chunks in
  reverse.  A chunk's states are recomputed into VMEM from the kept
  state it starts from, then walked back, carrying the state's gradient
  across chunks in scratch.  It writes the gradients of ``u`` and
  ``delta``, and each (row, channel block)'s part of those of ``A``,
  ``B``, ``C`` and ``D``, which XLA sums.

``B`` and ``C`` reach the kernels as ``[N, T]`` (the time on lanes): a
position's column is picked by a lane mask and a lane sum, then
broadcast along the channels.  Positions past ``T`` (to a whole chunk)
are zeros: ``delta = 0`` leaves the state as it is and adds nothing.

:func:`selective_scan` is the ``custom_vjp`` over the two kernels; off
the TPU they run in the Pallas interpreter (the CPU tests).
:func:`selective_scan_xla` is the plain path: a ``lax.scan`` over time,
differentiated by jax, which keeps every position's state.

Trace-time gauges of the call traced last:
``fdtpu_scan_state_bytes{kind="kept"|"all"}`` (the states the forward
keeps for the backward, against every position's) and
``fdtpu_scan_tiles{dim="chunk"|"channels_fwd"|"channels_bwd"}``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.metrics import get_registry
from .pallas_attention import interpret_mode

__all__ = ["selective_scan", "selective_scan_xla", "scan_tiles", "ScanTiles",
           "KERNEL_NAMES"]

#: the kernels' names in a compiled program and so in a device trace
KERNEL_NAMES = ("fdtpu_scan_fwd", "fdtpu_scan_bwd")

#: positions a kernel's grid step walks (a multiple of 128, as ``B`` and
#: ``C`` have the time on lanes); the forward keeps one state a chunk
CHUNK = 256
#: channels a grid step holds, by direction: the backward also keeps a
#: chunk's states, ``(chunk + 1) x N x block`` float32 in VMEM (4.2 MB)
BLOCK_FWD = 512
BLOCK_BWD = 256
#: positions a loop step of a kernel walks (``_walk``); a chunk is a
#: multiple of it
UNROLL = 8
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


class ScanTiles(NamedTuple):
    chunk: int
    block_fwd: int
    block_bwd: int


def _block(c: int, most: int) -> int:
    """The largest of ``most``, ``most / 2``, .. 128 that divides ``c``;
    ``c`` where none does (a block as wide as the array)."""
    b = most
    while b >= 128:
        if c % b == 0:
            return b
        b //= 2
    return c


def scan_tiles(t: int, c: int) -> ScanTiles:
    """Chunk and channel blocks for ``T = t``, ``C = c``.  A row no
    longer than a chunk is one chunk, padded to a multiple of 8."""
    chunk = CHUNK
    if t <= chunk:
        chunk = -(-t // 8) * 8
    return ScanTiles(chunk, _block(c, BLOCK_FWD), _block(c, BLOCK_BWD))


def _publish(b, t, c, n, tiles: ScanTiles):
    reg = get_registry()
    kept = b * (-(-t // tiles.chunk)) * n * c * 4
    state = reg.gauge("fdtpu_scan_state_bytes", "bytes of the states the "
                      "selective scan traced last keeps for its backward, "
                      "and of every position's state", ("kind",))
    state.labels("kept").set(kept)
    state.labels("all").set(b * t * n * c * 4)
    g = reg.gauge("fdtpu_scan_tiles", "the selective scan traced last's "
                  "positions a chunk and channels a block", ("dim",))
    g.labels("chunk").set(tiles.chunk)
    g.labels("channels_fwd").set(tiles.block_fwd)
    g.labels("channels_bwd").set(tiles.block_bwd)


def _column(rows_by_time, pick):
    """``[N, chunk]`` -> the ``[N, 1]`` column the lane mask ``pick``
    names."""
    return jnp.sum(jnp.where(pick, rows_by_time, 0.0), axis=1, keepdims=True)


def _walk(n, body, carry, reverse=False):
    """``carry = body(t, rows, carry)`` for ``t`` in ``range(n)`` (or
    back from ``n - 1``), ``UNROLL`` positions a loop step: ``rows(ref)``
    is position ``t``'s ``[1, block]`` row of a ``[1, chunk, block]``
    block, from one aligned load of the step's ``UNROLL`` rows.  The
    step's positions are one body, so the work of one that does not wait
    on the state overlaps the chain of the one before (Mosaic unrolls a
    loop wholly or not at all)."""
    def step(g, carry):
        if reverse:
            g = n // UNROLL - 1 - g
        base = pl.multiple_of(g * UNROLL, UNROLL)
        tiles = {}

        def rows(j, ref):
            if id(ref) not in tiles:
                tiles[id(ref)] = ref[0, pl.ds(base, UNROLL), :]
            return tiles[id(ref)][j:j + 1]

        for j in (reversed(range(UNROLL)) if reverse else range(UNROLL)):
            carry = body(base + j, functools.partial(rows, j), carry)
        return carry

    return jax.lax.fori_loop(0, n // UNROLL, step, carry)


def _fwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref,
                y_ref, h_ref, s_ref):
    chunk = u_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    h_ref[0, 0] = s_ref[...]
    a, d = a_ref[...], d_ref[...]
    bt, ct = bt_ref[0], ct_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(t, row, s):
        dt, u = row(dt_ref), row(u_ref)
        pick = lane == t
        s = jnp.exp(dt * a) * s + _column(bt, pick) * (dt * u)
        y_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(_column(ct, pick) * s, axis=0, keepdims=True) + d * u)
        return s

    s_ref[...] = _walk(chunk, step, s_ref[...])


def _bwd_kernel(u_ref, dt_ref, a_ref, bt_ref, ct_ref, d_ref, h_ref, dy_ref,
                du_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dd_ref,
                st_ref, g_ref):
    """``st_ref[i]`` holds the state after the chunk's ``i``-th position
    (``st_ref[0]`` the kept state it starts from); ``g_ref`` carries
    ``exp(delta A) * ds`` of the first position of the chunk after."""
    chunk = u_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a, d = a_ref[...], d_ref[...]
    bt, ct = bt_ref[0], ct_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)
    st_ref[0] = h_ref[0, 0]

    def forward(t, row, s):
        dt, u = row(dt_ref), row(u_ref)
        s = jnp.exp(dt * a) * s + _column(bt, lane == t) * (dt * u)
        st_ref[t + 1] = s
        return s

    _walk(chunk, forward, st_ref[0])

    def back(t, row, carry):
        g_next, da, dbt, dct, dd = carry
        dt, u, dy = row(dt_ref), row(u_ref), row(dy_ref)
        pick = lane == t
        b, c = _column(bt, pick), _column(ct, pick)
        decay = jnp.exp(dt * a)
        g = c * dy + g_next                       # d loss / d s[t]
        ga = g * st_ref[t] * decay                # d loss / d (delta A)
        gb = jnp.sum(g * b, axis=0, keepdims=True)
        ddt_ref[0, pl.ds(t, 1), :] = (
            jnp.sum(ga * a, axis=0, keepdims=True) + gb * u)
        du_ref[0, pl.ds(t, 1), :] = gb * dt + d * dy
        dbt = dbt + jnp.where(
            pick, jnp.sum(g * (dt * u), axis=1, keepdims=True), 0.0)
        dct = dct + jnp.where(
            pick, jnp.sum(st_ref[t + 1] * dy, axis=1, keepdims=True), 0.0)
        return decay * g, da + ga * dt, dbt, dct, dd + dy * u

    zeros = jnp.zeros_like
    g, da, dbt, dct, dd = _walk(
        chunk, back, (g_ref[...], zeros(a), zeros(bt), zeros(bt), zeros(d)),
        reverse=True)
    g_ref[...] = g
    da_ref[0] += da
    dd_ref[0] += dd
    dbt_ref[0, 0] = dbt
    dct_ref[0, 0] = dct


def _pad_time(x, t_p):
    return jnp.pad(x, ((0, 0), (0, t_p - x.shape[1]), (0, 0)))


def _operands(u, delta, A, B, C, D, chunk):
    """The kernels' layout: float32, time padded to whole chunks, ``A``
    as ``[N, C]``, ``B`` and ``C`` as ``[rows, N, T]``, ``D`` as
    ``[1, C]``."""
    f32 = jnp.float32
    t_p = -(-u.shape[1] // chunk) * chunk
    pad = functools.partial(_pad_time, t_p=t_p)
    return (pad(u.astype(f32)), pad(delta.astype(f32)), A.astype(f32).T,
            pad(B.astype(f32)).transpose(0, 2, 1),
            pad(C.astype(f32)).transpose(0, 2, 1), D.astype(f32)[None])


@jax.jit
def _scan_fwd(u, delta, A, B, C, D):
    """``(y [rows, T, C] f32, kept states [rows, T / chunk, N, C])``."""
    b, t, c = u.shape
    n = A.shape[1]
    tiles = scan_tiles(t, c)
    _publish(b, t, c, n, tiles)
    up, dtp, at, bt, ct, dp = _operands(u, delta, A, B, C, D, tiles.chunk)
    tc, blk = tiles.chunk, tiles.block_fwd
    nk = up.shape[1] // tc
    rows = pl.BlockSpec((1, tc, blk), lambda r, j, k: (r, k, j))
    by_time = pl.BlockSpec((1, n, tc), lambda r, j, k: (r, 0, k))
    y, hs = pl.pallas_call(
        _fwd_kernel,
        grid=(b, c // blk, nk),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, blk), lambda r, j, k: (0, j)),
                  by_time, by_time,
                  pl.BlockSpec((1, blk), lambda r, j, k: (0, j))],
        out_specs=[rows,
                   pl.BlockSpec((1, 1, n, blk), lambda r, j, k: (r, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, nk, n, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, blk), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name=KERNEL_NAMES[0],
    )(up, dtp, at, bt, ct, dp)
    return y[:, :t], hs


@jax.jit
def _scan_bwd(u, delta, A, B, C, D, hs, dy):
    b, t, c = u.shape
    n = A.shape[1]
    tiles = scan_tiles(t, c)
    up, dtp, at, bt, ct, dp = _operands(u, delta, A, B, C, D, tiles.chunk)
    dyp = _pad_time(dy.astype(jnp.float32), up.shape[1])
    tc, blk = tiles.chunk, tiles.block_bwd
    nk, nc = up.shape[1] // tc, c // blk
    back = lambda k: nk - 1 - k  # noqa: E731 - the chunks in reverse
    rows = pl.BlockSpec((1, tc, blk), lambda r, j, k: (r, back(k), j))
    by_time = pl.BlockSpec((1, n, tc), lambda r, j, k: (r, 0, back(k)))
    part = pl.BlockSpec((1, 1, n, tc), lambda r, j, k: (r, j, 0, back(k)))
    du, ddt, da, dbt, dct, dd = pl.pallas_call(
        _bwd_kernel,
        grid=(b, nc, nk),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, blk), lambda r, j, k: (0, j)),
                  by_time, by_time,
                  pl.BlockSpec((1, blk), lambda r, j, k: (0, j)),
                  pl.BlockSpec((1, 1, n, blk),
                               lambda r, j, k: (r, back(k), 0, j)),
                  rows],
        out_specs=[rows, rows,
                   pl.BlockSpec((1, n, blk), lambda r, j, k: (r, 0, j)),
                   part, part,
                   pl.BlockSpec((1, 1, blk), lambda r, j, k: (r, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, n, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, n, up.shape[1]), jnp.float32),
                   jax.ShapeDtypeStruct((b, nc, n, up.shape[1]), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tc + 1, n, blk), jnp.float32),
                        pltpu.VMEM((n, blk), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name=KERNEL_NAMES[1],
    )(up, dtp, at, bt, ct, dp, hs, dyp)
    by_row = lambda x: x.sum(1).transpose(0, 2, 1)[:, :t]  # noqa: E731
    return (du[:, :t].astype(u.dtype), ddt[:, :t].astype(delta.dtype),
            da.sum(0).T.astype(A.dtype), by_row(dbt).astype(B.dtype),
            by_row(dct).astype(C.dtype), dd.sum((0, 1)).astype(D.dtype))


@jax.custom_vjp
def selective_scan(u, delta, A, B, C, D):
    """``y [rows, T, C]`` float32 of the scan above: ``u``, ``delta``
    ``[rows, T, C]`` (``delta`` after its softplus), ``A`` ``[C, N]``,
    ``B``, ``C`` ``[rows, T, N]``, ``D`` ``[C]``.  The Pallas kernels,
    interpreted off the TPU."""
    return _scan_fwd(u, delta, A, B, C, D)[0]


def _vjp_fwd(u, delta, A, B, C, D):
    y, hs = _scan_fwd(u, delta, A, B, C, D)
    return y, (u, delta, A, B, C, D, hs)


def _vjp_bwd(res, dy):
    return _scan_bwd(*res, dy)


selective_scan.defvjp(_vjp_fwd, _vjp_bwd)


def selective_scan_xla(u, delta, A, B, C, D):
    """The same scan as a ``lax.scan`` over time, float32: the plain
    path off the TPU, differentiated by jax (it keeps every position's
    state for the backward)."""
    f32 = jnp.float32
    u, delta, B, C = (x.astype(f32).swapaxes(0, 1) for x in (u, delta, B, C))
    A, D = A.astype(f32), D.astype(f32)

    def step(s, x):
        u_t, dt_t, b_t, c_t = x
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * u_t)[..., None] * b_t[:, None, :])
        return s, jnp.sum(s * c_t[:, None, :], axis=-1) + D * u_t

    s0 = jnp.zeros((u.shape[1], *A.shape), f32)
    _, y = jax.lax.scan(step, s0, (u, delta, B, C))
    return y.swapaxes(0, 1)
