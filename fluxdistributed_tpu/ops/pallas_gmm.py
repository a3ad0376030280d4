"""Grouped matrix products for the TPU: the experts' projections over
rows sorted by expert, on tiles chosen from the call's own shapes.

Three products, one kernel name in a compiled program and so in a device
trace's ``XLA Ops`` (``KERNEL_NAME``; the three differ in the gauges'
``product`` label):

* ``gmm``: ``[rows, K] x [groups, K, N] -> [rows, N]``, row ``r`` times
  the weights of the group it lies in;
* ``gmm_t``: the same with the weights ``[groups, N, K]`` contracted
  over their last dimension (the rows' gradient), nothing transposed
  first;
* ``tgmm``: ``[rows, K]^T [rows, N] -> [groups, K, N]``, each group's
  rows contracted (the weights' gradient), both operands row-major as
  they stand: no ``[K, rows]`` copy is made.

The bodies are the megablox kernels of the installed jax
(``jax.experimental.pallas.ops.tpu.megablox.gmm``), kept here as a copy
and not called, because the call cannot be given what this layer needs:
a kernel name of the repo's own (a trace reducer finds ``fdtpu_gmm``
whatever the jax version names its own), the weights' gradient without
its ``[K, rows]`` operand, and one walk over the row tiles
(``group_metadata``) shared by every product of a layer and direction
and by every rung of its ladder (megablox computes it inside every call,
with a histogram and two ``repeat``s).  What changed in the copy: no
accumulator pass where the contraction is whole; a step of ``gmm`` /
``gmm_t`` loops over its tile in pieces of ``ROW_CHUNK`` rows, since
Mosaic unrolls a product over its whole block and a step program of the
expert cells holds 300 of these kernels (0.71 MiB of code a kernel at
512 x 2,048 x 896 without the loop, 0.25 with it, 0.15 XLA's own; the
program's load is in every warm set-up and its code in HBM); the bodies
are ``lax`` primitives and hold one branch between the three, because
each is traced and lowered 40 times a step program and no cache skips
that.  What went: sharded groups (``group_offset``), ``existing_out``,
the masks for a contraction its tile does not divide (the rule below
only picks divisors) and the tile look-up tables.

**The tiles are derived, not set** (``tiles_for``): rows ``ROW_TILE``;
of the ``(tk, tn)`` that divide their dimension, are a multiple of 128
or the whole of it, and whose double-buffered blocks and float32
accumulator fit ``VMEM_BUDGET``, the pair that moves the fewest bytes
between HBM and VMEM by the rule's own count (``operand_reads``) at a
full buffer; ties go to the longer contraction, then the wider ``tn``.
For ``gmm`` at the expert cells' shapes (some four row tiles a group
and more) that is the contraction whole: the weights' block index is
then the same for every row tile of a group, Pallas fetches no block
whose index did not change, and a group's weights are read once, not
once a row tile; the rows are read ``N / tn`` times.  XLA's own ``ragged_dot`` kernel takes 512 x 512 x 256 by
divisibility alone, reads the rows 7 (or 4) times and an expert's
weights once a row tile, and is bound by HBM at the expert cells'
widths (PERF.md §6, PR 36).

Set at trace time, like ``fdtpu_flash_tiles``:
``fdtpu_gmm_tiles{product, dim}`` (the tiles of the call traced last)
and ``fdtpu_gmm_operand_reads{product, operand}`` (how many times that
call reads each operand whole, by the rule's count).

Inputs in the compute type, float32 accumulation, the result in the
inputs' type.  A row behind the last group is never visited: it holds
whatever the buffer held, not nought.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.metrics import get_registry

__all__ = [
    "KERNEL_NAME",
    "ROW_TILE",
    "GroupMetadata",
    "PRODUCTS",
    "ROW_CHUNK",
    "Tiles",
    "gmm",
    "group_metadata",
    "grouped_dot",
    "operand_reads",
    "tgmm",
    "tiles_for",
    "tileable",
]

#: every grouped product's name in a compiled program (as XLA's were all
#: ``ragged-dot-none``): one kind among a trace's ten, where three would
#: push a flash kernel out of them
KERNEL_NAME = "fdtpu_gmm"

#: rows a grid step: every rung of ``ep.compact_rows`` is a whole number
ROW_TILE = 512
#: rows a product inside a step of ``gmm`` / ``gmm_t``: the step loops
#: over its tile in pieces, because Mosaic unrolls a product over its
#: whole block into the kernel's code
ROW_CHUNK = 128

#: bytes the double-buffered blocks and the float32 accumulator of a call
#: may take: a v5e's default scoped VMEM (16 MiB) less 1.25 MiB for a
#: step's temporaries (Mosaic was seen to keep up to 1.14 MiB beside the
#: blocks at the expert cells' shapes).  The default it must be: a call
#: that asks for a limit of its own, even 16 MiB, makes XLA give up the
#: whole arrays it keeps in VMEM around the call (the sorted rows'
#: gradient, 72 MiB, which four gathers then read from HBM six times
#: slower: PERF.md §6, PR 36)
VMEM_BUDGET = 59 * 2 ** 18
_LANES = 128

PRODUCTS = ("gmm", "gmm_t", "tgmm")


class Tiles(NamedTuple):
    m: int
    k: int
    n: int


class GroupMetadata(NamedTuple):
    """Which tile of rows and which group each grid step along the rows
    works on: ``offsets`` (groups + 1) the row each group starts at,
    ``group_ids`` and ``m_tile_ids`` (row tiles + groups - 1) by step,
    ``visits`` () how many of them a call makes."""

    offsets: jax.Array
    group_ids: jax.Array
    m_tile_ids: jax.Array
    visits: jax.Array


def tileable(rows: int, k: int, n: int) -> bool:
    """Whether the kernels' tiles divide these shapes."""
    return rows % ROW_TILE == 0 and k % _LANES == 0 and n % _LANES == 0


def _divisors(dim: int) -> list:
    return [d for d in range(_LANES, dim + 1, _LANES) if dim % d == 0]


def _steps(rows: int, groups: int) -> int:
    """Grid steps along the rows of a full buffer: a tile a step, and a
    second visit of the tile in which a group ends and the next starts."""
    return rows // ROW_TILE + groups - 1


def operand_reads(product: str, tiles: Tiles, rows: int, k: int, n: int,
                  groups: int) -> dict:
    """How many times a call reads each operand whole between HBM and
    VMEM, ``{"rows", "weights"}``, at a full buffer.  ``gmm`` /
    ``gmm_t``: the rows once a tile of the result's width; the weights
    once where the contraction is whole (a group's block stays put while
    its row tiles pass), else once a grid step along the rows.  ``tgmm``
    reads rows only, ``[rows, K]`` once a tile of ``N`` and ``[rows,
    N]`` once a tile of ``K``; its ``weights`` is the result, written
    once."""
    if product == "tgmm":
        return {"rows": (k * (n // tiles.n) + n * (k // tiles.k)) / (k + n),
                "weights": 1.0}
    return {"rows": float(n // tiles.n),
            "weights": 1.0 if tiles.k == k else _steps(rows, groups) / groups}


def _vmem_bytes(product: str, tiles: Tiles, itemsize: int) -> int:
    """Two buffers a block (operands and result are of one type) and the
    float32 accumulator, which has the result block's shape."""
    tm, tk, tn = tiles
    result = tk * tn if product == "tgmm" else tm * tn
    return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + 4 * result


def tiles_for(product: str, rows: int, k: int, n: int, groups: int,
              itemsize: int = 2) -> Tiles:
    """The tiles of a call, from its shapes and itemsize alone (the
    module text has the rule).  ``k`` is the contraction of ``gmm`` /
    ``gmm_t`` and the first of the result's two widths in ``tgmm``."""
    if not tileable(rows, k, n):
        raise ValueError(
            f"rows {rows} must be a multiple of {ROW_TILE} and the widths "
            f"{k}, {n} of {_LANES}")
    fits = [t for t in (Tiles(ROW_TILE, tk, tn)
                        for tk in _divisors(k) for tn in _divisors(n))
            if _vmem_bytes(product, t, itemsize) <= VMEM_BUDGET]
    # 128 x 128 always fits: 0.5 MiB of blocks

    def moved(t):  # in elements: one itemsize throughout
        reads = operand_reads(product, t, rows, k, n, groups)
        if product == "tgmm":
            return reads["rows"] * rows * (k + n)
        return reads["rows"] * rows * k + reads["weights"] * groups * k * n
    return min(fits, key=lambda t: (moved(t), -t.k, -t.n))


def _publish(product, tiles, rows, k, n, groups):
    reg = get_registry()
    gauge = reg.gauge(
        "fdtpu_gmm_tiles",
        "tiles of the grouped product traced last", ("product", "dim"))
    for dim, size in zip("mkn", tiles):
        gauge.labels(product, dim).set(size)
    gauge = reg.gauge(
        "fdtpu_gmm_operand_reads",
        "times the grouped product traced last reads an operand whole, by "
        "the tile rule's count at a full buffer", ("product", "operand"))
    for operand, times in operand_reads(
            product, tiles, rows, k, n, groups).items():
        gauge.labels(product, operand).set(times)


@functools.partial(jax.jit, static_argnames=("rows",))
def group_metadata(sizes, rows: int) -> GroupMetadata:
    """The walk over ``rows`` rows (a multiple of ``ROW_TILE``) in groups
    of ``sizes`` (int32, ``sum(sizes) <= rows``): a group visits every
    tile that holds a row of it, in order, so a tile in which a group
    ends is visited again by the next; an empty group visits one tile
    (``tgmm`` writes its noughts there, ``gmm`` keeps what is there).
    Compares and counts over ``(steps, groups)``: no scatter, no sort.
    The walk also serves any shorter buffer that holds the groups (a
    multiple of ``ROW_TILE`` at or over ``sum(sizes)``): the expert
    layer makes one a layer and direction, for whichever rung of its
    ladder the step takes; the calls name no tile behind their rows."""
    groups, tiles_m = sizes.shape[0], rows // ROW_TILE
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = jnp.minimum(starts // ROW_TILE, tiles_m - 1)
    last = jnp.clip((ends - 1) // ROW_TILE, first, tiles_m - 1)
    upto = jnp.cumsum(last - first + 1, dtype=jnp.int32)
    step = jnp.arange(_steps(rows, groups), dtype=jnp.int32)
    group_ids = jnp.minimum(
        jnp.sum(upto[None, :] <= step[:, None], axis=1, dtype=jnp.int32),
        groups - 1)
    before = upto - (last - first + 1)
    m_tile_ids = jnp.minimum(
        first[group_ids] + step - before[group_ids], tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return GroupMetadata(offsets, group_ids, m_tile_ids,
                         jnp.minimum(upto[-1], step.shape[0]))


# The kernel bodies are written in ``lax`` primitives, not ``jnp``: a
# ``jnp`` function is a jitted wrapper that costs a millisecond to trace,
# each body is traced once a rung, width and direction (40 times a step
# program of the expert cells), and no cache skips a trace (PERF.md §6,
# PR 36: the set-up).

def _scalar(x):
    return jax.lax.convert_element_type(x, jnp.int32)


def _group_rows(offsets, group, first_row, shape):
    """``shape`` mask: the rows, counted from ``first_row`` on, that lie
    in ``group``."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lo = jax.lax.sub(offsets[group], first_row)
    hi = jax.lax.sub(offsets[jax.lax.add(group, _scalar(1))], first_row)
    return jax.lax.bitwise_and(
        jax.lax.ge(row, jax.lax.broadcast(lo, shape)),
        jax.lax.lt(row, jax.lax.broadcast(hi, shape)))


def _keep(mask, x, other, dtype):
    """``where(mask, x, other)`` in float32 (a v5e selects 32-bit lanes),
    then ``dtype``."""
    f32 = functools.partial(jax.lax.convert_element_type,
                            new_dtype=jnp.float32)
    return jax.lax.convert_element_type(
        jax.lax.select(mask, f32(x), f32(other)), dtype)


def _gmm_kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, *acc,
                tiles, tiles_k, transpose_rhs):
    step, k_i = pl.program_id(1), pl.program_id(2)
    group = group_ids[step]
    tile_row = jax.lax.mul(m_tile_ids[step], _scalar(tiles.m))
    contract = (((1,), (1 if transpose_rhs else 0,)), ((), ()))

    def piece(c, carry):
        """Rows ``[c * ROW_CHUNK, (c + 1) * ROW_CHUNK)`` of the tile."""
        start = jax.lax.mul(_scalar(c), _scalar(ROW_CHUNK))
        rows = pl.ds(pl.multiple_of(start, ROW_CHUNK), ROW_CHUNK)
        result = jax.lax.dot_general(
            lhs[rows, :], rhs[...], contract,
            preferred_element_type=jnp.float32)
        mask = _group_rows(offsets, group, jax.lax.add(tile_row, start),
                           (ROW_CHUNK, tiles.n))

        def store(result):
            out[rows, :] = _keep(mask, result, out[rows, :], out.dtype)

        if tiles_k == 1:  # the contraction whole: no accumulator pass
            store(result)
            return carry
        (acc_ref,) = acc
        first = jax.lax.broadcast(jax.lax.eq(k_i, _scalar(0)), result.shape)
        result = jax.lax.select(
            first, result, jax.lax.add(acc_ref[rows, :], result))
        acc_ref[rows, :] = result
        pl.when(k_i == tiles_k - 1)(functools.partial(store, result))
        return carry

    # a loop and not the tile at once: Mosaic unrolls a product over its
    # whole block, and a step program holds 225 of these kernels
    jax.lax.fori_loop(0, tiles.m // ROW_CHUNK, piece, None)


def _tile_in(tiles_m, m_tile_ids, step):
    """The row tile of ``step``, held inside a buffer of ``tiles_m``
    tiles: an empty group behind a full buffer's last row names the
    last tile (the walk may be a longer buffer's)."""
    return jax.lax.min(m_tile_ids[step], _scalar(tiles_m - 1))


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


# jitted, as the flash wrappers are: the trace of a jitted function starts
# a name stack of its own, so the kernel keeps its name under jvp, under
# a transpose and in a switch's branch (else it is ``jvp_fdtpu_gmm_`` ...)
@functools.partial(jax.jit,
                   static_argnames=("transpose_rhs", "tiles", "interpret"))
def gmm(lhs, rhs, meta: GroupMetadata, *, transpose_rhs: bool = False,
        tiles: Tiles | None = None, interpret: bool = False):
    """``out[r] = lhs[r] @ rhs[group of r]`` for the rows of the groups
    of ``meta``: ``lhs`` ``[rows, K]``, ``rhs`` ``[groups, K, N]`` or,
    with ``transpose_rhs``, ``[groups, N, K]``; the result ``[rows, N]``
    in ``lhs``'s type."""
    rows, k = lhs.shape
    groups, n = rhs.shape[0], rhs.shape[1 if transpose_rhs else 2]
    product = "gmm_t" if transpose_rhs else "gmm"
    itemsize = lhs.dtype.itemsize
    if tiles is None:
        tiles = tiles_for(product, rows, k, n, groups, itemsize)
    _publish(product, tiles, rows, k, n, groups)
    tm, tk, tn = tiles
    tiles_k, tiles_n = k // tk, n // tn
    tile = functools.partial(_tile_in, rows // tm)

    def rhs_index(n_i, step, k_i, offsets, group_ids, m_tile_ids):
        block = (n_i, k_i) if transpose_rhs else (k_i, n_i)
        return (group_ids[step], *block)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tiles=tiles, tiles_k=tiles_k,
                          transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, step, k_i, o, g, m:
                             (tile(m, step), k_i)),
                pl.BlockSpec((None, tn, tk) if transpose_rhs
                             else (None, tk, tn), rhs_index),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda n_i, step, k_i, o, g, m:
                                   (tile(m, step), n_i)),
            grid=(tiles_n, meta.visits, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)] * (tiles_k > 1),
        ),
        compiler_params=_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * k * tiles_n + groups * k * n + rows * n)
            * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta.offsets, meta.group_ids, meta.m_tile_ids, lhs, rhs)


def _tgmm_kernel(offsets, group_ids, m_tile_ids, lhs, rhs, out, acc, *, tiles):
    step, steps = pl.program_id(2), pl.num_programs(2)
    group = group_ids[step]
    tile_row = jax.lax.mul(m_tile_ids[step], _scalar(tiles.m))
    before = group_ids[jax.lax.max(jax.lax.sub(step, _scalar(1)), _scalar(0))]
    after = group_ids[jax.lax.min(jax.lax.add(step, _scalar(1)),
                                  jax.lax.sub(steps, _scalar(1)))]
    # the rows of other groups (and rows never written) leave the
    # contraction by a select, so a NaN there does not reach the sum; an
    # empty group's step adds noughts
    a = _keep(_group_rows(offsets, group, tile_row, lhs.shape),
              lhs[...], jnp.zeros(lhs.shape, jnp.float32), lhs.dtype)
    b = _keep(_group_rows(offsets, group, tile_row, rhs.shape),
              rhs[...], jnp.zeros(rhs.shape, jnp.float32), rhs.dtype)
    product = jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    opens = jax.lax.bitwise_or(jax.lax.eq(step, _scalar(0)),
                               jax.lax.ne(before, group))
    acc[...] = jax.lax.select(jax.lax.broadcast(opens, product.shape), product,
                              jax.lax.add(acc[...], product))
    closes = jax.lax.bitwise_or(
        jax.lax.eq(step, jax.lax.sub(steps, _scalar(1))),
        jax.lax.ne(after, group))

    @pl.when(closes)
    def _():
        out[...] = jax.lax.convert_element_type(acc[...], out.dtype)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret"))
def tgmm(lhs, rhs, meta: GroupMetadata, *, tiles: Tiles | None = None,
         interpret: bool = False):
    """``out[g] = lhs[rows of g]^T @ rhs[rows of g]``: ``lhs`` ``[rows,
    K]``, ``rhs`` ``[rows, N]``, the result ``[groups, K, N]`` in
    ``lhs``'s type, nought for an empty group."""
    rows, k = lhs.shape
    n, groups = rhs.shape[1], meta.offsets.shape[0] - 1
    itemsize = lhs.dtype.itemsize
    if tiles is None:
        tiles = tiles_for("tgmm", rows, k, n, groups, itemsize)
    _publish("tgmm", tiles, rows, k, n, groups)
    tm, tk, tn = tiles
    tiles_k, tiles_n = k // tk, n // tn
    tile = functools.partial(_tile_in, rows // tm)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tiles=tiles),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[
                pl.BlockSpec((tm, tk), lambda n_i, k_i, step, o, g, m:
                             (tile(m, step), k_i)),
                pl.BlockSpec((tm, tn), lambda n_i, k_i, step, o, g, m:
                             (tile(m, step), n_i)),
            ],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda n_i, k_i, step, o, g, m:
                (g[step], k_i, n_i)),
            grid=(tiles_n, tiles_k, meta.visits),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        compiler_params=_PARAMS,
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n, transcendentals=0,
            bytes_accessed=(rows * k * tiles_n + rows * n * tiles_k
                            + groups * k * n) * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(meta.offsets, meta.group_ids, meta.m_tile_ids, lhs, rhs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def grouped_dot(lhs, rhs, meta: GroupMetadata, interpret: bool = False):
    """``gmm`` with its two transposes: ``gmm_t`` gives the rows'
    gradient and ``tgmm`` the weights', over the same walk."""
    return gmm(lhs, rhs, meta, interpret=interpret)


def _grouped_dot_fwd(lhs, rhs, meta, interpret):
    return gmm(lhs, rhs, meta, interpret=interpret), (lhs, rhs, meta)


def _grouped_dot_bwd(interpret, res, g):
    lhs, rhs, meta = res
    g = g.astype(lhs.dtype)
    return (gmm(g, rhs, meta, transpose_rhs=True, interpret=interpret),
            tgmm(lhs, g, meta, interpret=interpret).astype(rhs.dtype), None)


grouped_dot.defvjp(_grouped_dot_fwd, _grouped_dot_bwd)
