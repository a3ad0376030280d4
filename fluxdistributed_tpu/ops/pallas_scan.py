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

* **forward** (``fdtpu_scan_fwd``): grid ``(rows, time chunks, channel
  blocks)``, the chunks in order.  Each block's ``[N, block]`` state
  lives in VMEM scratch across the chunks; inside a chunk the kernel
  walks the positions in order, ``UNROLL`` a loop step (``_walk``), the
  channels on the vector lanes and the states on the sublanes.  It
  writes ``y`` and the state each chunk starts from: ``T / chunk``
  states a row, not ``T``.
* **backward** (``fdtpu_scan_bwd``): the same grid with the chunks in
  reverse.  A chunk's states are recomputed into VMEM from the kept
  state it starts from, then walked back, carrying each block's state
  gradient across chunks in scratch.  It writes the gradients of ``u``,
  ``delta``, ``B`` and ``C``, and each row's part of those of ``A`` and
  ``D``, which XLA sums.

A position's traffic between VMEM and the vector registers moves once
a loop step, or once a chunk, not once a position.  ``B`` and ``C``
reach the kernels as ``[rows, T / UNROLL, N, UNROLL]``.  At a chunk's
first block one load brings a loop step's ``[N, UNROLL]`` columns, and
each position's column, taken by a static lane index, is spread along
128 lanes into VMEM (``_spread``); every block of the chunk then reads
a position's column with one aligned load, with no lane mask and no
lane sum.  The rows a loop step writes (``y`` forward, the gradients of
``u`` and ``delta`` backward) take sums over the states (``C . s``;
``g . B`` and ``(g s exp(delta A)) . A``, ``g`` the state's gradient):
each position's sum is selected into its row of an ``[UNROLL, block]``
tile (``_state_sums``), and the rows are finished on whole tiles and
leave as one aligned store.  The backward's ``B`` and ``C`` gradients (each
position's sum over a block's channels) are added to VMEM scratch as
one ``[N, UNROLL]`` tile a loop step, summed over the blocks there and
laid into the chunk's ``[N, chunk]`` block (the time on lanes) after
the last.
Positions past ``T`` (to a whole chunk) are zeros: ``delta = 0`` leaves
the state as it is and adds nothing.

:func:`selective_scan` is the ``custom_vjp`` over the two kernels; off
the TPU they run in the Pallas interpreter (the CPU tests).
:func:`selective_scan_xla` is the plain path: a ``lax.scan`` over time,
differentiated by jax, which keeps every position's state.

Trace-time gauges of the call traced last:
``fdtpu_scan_state_bytes{kind="kept"|"all"}`` (the states the forward
keeps for the backward, against every position's) and
``fdtpu_scan_tiles{dim="chunk"|"channels_fwd"|"channels_bwd"|
"columns_per_load"}`` (the last: the positions whose ``B`` and ``C``
columns one load brings, ``UNROLL``).
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

#: positions a kernel's grid step walks (a multiple of 128, as the
#: gradients of ``B`` and ``C`` have the time on lanes); the forward keeps
#: one state a chunk
CHUNK = 256
#: channels a grid step holds, by direction: the backward also keeps a
#: chunk's states, ``(chunk + 1) x N x block`` float32 in VMEM (4.2 MB),
#: beside the chunk's spread columns of ``B`` and ``C`` (2 x 2.1 MB)
BLOCK_FWD = 512
BLOCK_BWD = 256
#: positions a loop step of a kernel walks (``_walk``); a chunk is a
#: multiple of it.  8, the sublanes of a vreg: a loop step's rows of
#: ``y``, ``du`` and the ``delta`` gradient fill one tile
UNROLL = 8
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


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
                  "positions a chunk, channels a block and positions whose "
                  "B and C columns one load brings", ("dim",))
    g.labels("chunk").set(tiles.chunk)
    g.labels("channels_fwd").set(tiles.block_fwd)
    g.labels("channels_bwd").set(tiles.block_bwd)
    g.labels("columns_per_load").set(UNROLL)


def _spread(src, dst):
    """``dst[t]`` = position ``t``'s ``[N, 1]`` column of ``src`` (``[1,
    chunk / UNROLL, N, UNROLL]``) repeated along ``dst``'s lanes: one load
    of a loop step's ``[N, UNROLL]`` tile, a static lane index a
    position."""
    shape = dst.shape[1:]

    def step(g, carry):
        tile = src[0, g]
        base = pl.multiple_of(g * UNROLL, UNROLL)
        for j in range(UNROLL):
            dst[base + j] = jnp.broadcast_to(tile[:, j:j + 1], shape)
        return carry

    jax.lax.fori_loop(0, src.shape[1], step, 0)


def _column(ref, t, width):
    """Position ``t``'s spread column (``_spread``) as ``[N, width]``: one
    aligned load, its lanes repeated to the block's width."""
    col = ref[t]
    reps = width // col.shape[1]
    return col if reps == 1 else jnp.concatenate([col] * reps, axis=1)


def _state_sums(pieces):
    """``[(position, [N, width])]`` of a loop step's positions -> ``[UNROLL,
    width]`` whose row ``j`` is position ``j``'s array summed over the
    states (sublanes), selected into its row."""
    out = 0.0
    for pos, x in pieces:
        sub = jax.lax.broadcasted_iota(jnp.int32, (UNROLL, x.shape[1]), 0)
        out = jnp.where(sub == pos, jnp.sum(x, axis=0, keepdims=True), out)
    return out


def _walk(n, body, carry, finish=None, cols_out=(), reverse=False):
    """``carry, sums, cols = body(t, rows, carry)`` for ``t`` in
    ``range(n)`` (or back from ``n - 1``), ``UNROLL`` positions a loop
    step: ``rows(ref)`` is position ``t``'s ``[1, block]`` row of a ``[1,
    chunk, block]`` block, from one aligned load of the step's ``UNROLL``
    rows.  ``sums`` are ``[N, width]`` arrays to be summed over the states:
    after the step ``finish(tile, sums)`` gets them as ``[UNROLL, width]``
    tiles (``_state_sums``) with ``tile(ref)``, a ref's ``[UNROLL, block]``
    tile of the step, and returns ``(ref, tile)`` pairs, each stored as one
    aligned ``[UNROLL, block]`` store.  ``cols`` (``[N, 1]`` each) are added
    to ``cols_out``'s ``[chunk / UNROLL, N, UNROLL]`` refs as one ``[N,
    UNROLL]`` tile.  The step's positions are one body, so the work of one
    that does not wait on the state overlaps the chain of the one before
    (Mosaic unrolls a loop wholly or not at all)."""
    order = list(range(UNROLL))
    if reverse:
        order.reverse()

    def step(g, carry):
        if reverse:
            g = n // UNROLL - 1 - g
        base = pl.multiple_of(g * UNROLL, UNROLL)
        tiles = {}

        def tile(ref):
            if id(ref) not in tiles:
                tiles[id(ref)] = ref[0, pl.ds(base, UNROLL), :]
            return tiles[id(ref)]

        sums, out_cols = [], [None] * UNROLL
        for j in order:
            carry, s, out_cols[j] = body(
                base + j, lambda ref, j=j: tile(ref)[j:j + 1], carry)
            sums.append((j, s))
        if finish is not None:
            stacked = [_state_sums([(j, s[i]) for j, s in sums])
                       for i in range(len(sums[0][1]))]
            for ref, out in finish(tile, stacked):
                ref[0, pl.ds(base, UNROLL), :] = out
        for i, ref in enumerate(cols_out):
            ref[g] += jnp.concatenate([c[i] for c in out_cols], axis=1)
        return carry

    return jax.lax.fori_loop(0, n // UNROLL, step, carry)


def _fwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref,
                y_ref, h_ref, s_ref, bb_ref, cb_ref):
    """Grid (rows, chunks, channel blocks): ``s_ref[j]`` carries block
    ``j``'s state across the chunks; ``bb_ref`` / ``cb_ref`` hold the
    chunk's columns of ``B`` / ``C`` spread along the lanes, made at its
    first block and read by every block."""
    chunk, width = u_ref.shape[1:]
    j = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[j] = jnp.zeros(s_ref.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _columns():
        _spread(b_ref, bb_ref)
        _spread(c_ref, cb_ref)

    h_ref[0, 0] = s_ref[j]
    a, d = a_ref[...], d_ref[...]

    def step(t, row, s):
        dt, u = row(dt_ref), row(u_ref)
        s = jnp.exp(dt * a) * s + _column(bb_ref, t, width) * (dt * u)
        return s, (_column(cb_ref, t, width) * s,), ()

    def finish(tile, sums):
        return [(y_ref, sums[0] + d * tile(u_ref))]

    s_ref[j] = _walk(chunk, step, s_ref[j], finish)


def _bwd_kernel(u_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h_ref, dy_ref,
                du_ref, ddt_ref, da_ref, dbt_ref, dct_ref, dd_ref,
                st_ref, g_ref, da_acc, dd_acc, bb_ref, cb_ref, db_ref, dc_ref):
    """Grid (rows, chunks in reverse, channel blocks).  ``st_ref[i]`` holds
    the state after the chunk's ``i``-th position (``st_ref[0]`` the kept
    state it starts from); ``g_ref[j]`` carries block ``j``'s ``exp(delta
    A) * ds`` of the first position of the chunk after, ``da_acc[j]`` /
    ``dd_acc[j]`` its gradients of ``A`` / ``D`` over the chunks walked;
    ``db_ref`` / ``dc_ref`` sum the chunk's gradients of ``B`` / ``C`` over
    the blocks, ``[N, UNROLL]`` a loop step, laid out with the time on
    lanes after the last block."""
    chunk, width = u_ref.shape[1:]
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        g_ref[j] = jnp.zeros(g_ref.shape[1:], jnp.float32)
        da_acc[j] = jnp.zeros(da_acc.shape[1:], jnp.float32)
        dd_acc[j] = jnp.zeros(dd_acc.shape[1:], jnp.float32)

    @pl.when(j == 0)
    def _columns():
        _spread(b_ref, bb_ref)
        _spread(c_ref, cb_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    a, d = a_ref[...], d_ref[...]
    st_ref[0] = h_ref[0, 0]

    def forward(t, row, s):
        dt, u = row(dt_ref), row(u_ref)
        s = jnp.exp(dt * a) * s + _column(bb_ref, t, width) * (dt * u)
        st_ref[t + 1] = s
        return s, (), ()

    _walk(chunk, forward, st_ref[0])

    def back(t, row, carry):
        g_next, da, dd = carry
        dt, u, dy = row(dt_ref), row(u_ref), row(dy_ref)
        b, c = _column(bb_ref, t, width), _column(cb_ref, t, width)
        decay = jnp.exp(dt * a)
        g = c * dy + g_next                       # d loss / d s[t]
        ga = g * st_ref[t] * decay                # d loss / d (delta A)
        db = jnp.sum(g * (dt * u), axis=1, keepdims=True)
        dc = jnp.sum(st_ref[t + 1] * dy, axis=1, keepdims=True)
        return ((decay * g, da + ga * dt, dd + dy * u), (g * b, ga * a),
                (db, dc))

    def finish(tile, sums):
        gb, gaa = sums                            # summed over the states
        return [(ddt_ref, gaa + gb * tile(u_ref)),
                (du_ref, gb * tile(dt_ref) + d * tile(dy_ref))]

    zeros = jnp.zeros_like
    g, da, dd = _walk(chunk, back, (g_ref[j], zeros(a), zeros(d)), finish,
                      cols_out=(db_ref, dc_ref), reverse=True)
    g_ref[j] = g
    da_acc[j] += da
    dd_acc[j] += dd
    da_ref[0] = da_acc[j]
    dd_ref[0] = dd_acc[j]

    @pl.when(j == pl.num_programs(2) - 1)
    def _columns_out():
        # the time back on lanes: static lane offsets, as Mosaic takes
        # none that is dynamic and not a multiple of 128
        for i in range(chunk // UNROLL):
            lanes = slice(i * UNROLL, (i + 1) * UNROLL)
            dbt_ref[0, :, lanes] = db_ref[i]
            dct_ref[0, :, lanes] = dc_ref[i]


def _pad_time(x, t_p):
    return jnp.pad(x, ((0, 0), (0, t_p - x.shape[1]), (0, 0)))


def _operands(u, delta, A, B, C, D, chunk):
    """The kernels' layout: float32, time padded to whole chunks, ``A``
    as ``[N, C]``, ``B`` and ``C`` as ``[rows, T / UNROLL, N, UNROLL]``
    (a loop step's columns side by side), ``D`` as ``[1, C]``."""
    f32 = jnp.float32
    t_p = -(-u.shape[1] // chunk) * chunk
    pad = functools.partial(_pad_time, t_p=t_p)

    def by_step(x):
        b, _, n = x.shape
        x = pad(x.astype(f32))
        return x.reshape(b, t_p // UNROLL, UNROLL, n).transpose(0, 1, 3, 2)

    return (pad(u.astype(f32)), pad(delta.astype(f32)), A.astype(f32).T,
            by_step(B), by_step(C), D.astype(f32)[None])


def _spread_scratch(tc, n, blk):
    """Two ``[chunk, N, lanes]`` scratches for the spread columns of ``B``
    and ``C``: 128 lanes, repeated to the block in ``_column``, or the
    block's own width where it is no multiple of 128."""
    lanes = 128 if blk % 128 == 0 else blk
    return [pltpu.VMEM((tc, n, lanes), jnp.float32)] * 2


@jax.jit
def _scan_fwd(u, delta, A, B, C, D):
    """``(y [rows, T, C] f32, kept states [rows, T / chunk, N, C])``."""
    b, t, c = u.shape
    tiles = scan_tiles(t, c)
    _publish(b, t, c, A.shape[1], tiles)
    up, dtp, at, bt, ct, dp = _operands(u, delta, A, B, C, D, tiles.chunk)
    n = at.shape[0]
    tc, blk = tiles.chunk, tiles.block_fwd
    nk, nb = up.shape[1] // tc, c // blk
    rows = pl.BlockSpec((1, tc, blk), lambda r, k, j: (r, k, j))
    by_step = pl.BlockSpec((1, tc // UNROLL, n, UNROLL),
                           lambda r, k, j: (r, k, 0, 0))
    y, hs = pl.pallas_call(
        _fwd_kernel,
        grid=(b, nk, nb),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, blk), lambda r, k, j: (0, j)),
                  by_step, by_step,
                  pl.BlockSpec((1, blk), lambda r, k, j: (0, j))],
        out_specs=[rows,
                   pl.BlockSpec((1, 1, n, blk), lambda r, k, j: (r, k, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, nk, n, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((nb, n, blk), jnp.float32),
                        *_spread_scratch(tc, n, blk)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name=KERNEL_NAMES[0],
    )(up, dtp, at, bt, ct, dp)
    return y[:, :t], hs


@jax.jit
def _scan_bwd(u, delta, A, B, C, D, hs, dy):
    b, t, c = u.shape
    tiles = scan_tiles(t, c)
    up, dtp, at, bt, ct, dp = _operands(u, delta, A, B, C, D, tiles.chunk)
    n = at.shape[0]
    dyp = _pad_time(dy.astype(jnp.float32), up.shape[1])
    tc, blk = tiles.chunk, tiles.block_bwd
    nk, nb = up.shape[1] // tc, c // blk
    back = lambda k: nk - 1 - k  # noqa: E731 - the chunks in reverse
    rows = pl.BlockSpec((1, tc, blk), lambda r, k, j: (r, back(k), j))
    by_step = pl.BlockSpec((1, tc // UNROLL, n, UNROLL),
                           lambda r, k, j: (r, back(k), 0, 0))
    by_time = pl.BlockSpec((1, n, tc), lambda r, k, j: (r, 0, back(k)))
    du, ddt, da, dbt, dct, dd = pl.pallas_call(
        _bwd_kernel,
        grid=(b, nk, nb),
        in_specs=[rows, rows,
                  pl.BlockSpec((n, blk), lambda r, k, j: (0, j)),
                  by_step, by_step,
                  pl.BlockSpec((1, blk), lambda r, k, j: (0, j)),
                  pl.BlockSpec((1, 1, n, blk),
                               lambda r, k, j: (r, back(k), 0, j)),
                  rows],
        out_specs=[rows, rows,
                   pl.BlockSpec((1, n, blk), lambda r, k, j: (r, 0, j)),
                   by_time, by_time,
                   pl.BlockSpec((1, 1, blk), lambda r, k, j: (r, 0, j))],
        out_shape=[jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct(up.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, n, c), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, up.shape[1]), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, up.shape[1]), jnp.float32),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tc + 1, n, blk), jnp.float32),
                        pltpu.VMEM((nb, n, blk), jnp.float32),
                        pltpu.VMEM((nb, n, blk), jnp.float32),
                        pltpu.VMEM((nb, 1, blk), jnp.float32),
                        *_spread_scratch(tc, n, blk),
                        pltpu.VMEM((tc // UNROLL, n, UNROLL), jnp.float32),
                        pltpu.VMEM((tc // UNROLL, n, UNROLL), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret_mode(),
        name=KERNEL_NAMES[1],
    )(up, dtp, at, bt, ct, dp, hs, dyp)
    by_row = lambda x: x.transpose(0, 2, 1)[:, :t]  # noqa: E731
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
