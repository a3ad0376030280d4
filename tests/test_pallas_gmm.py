"""The grouped products of ``ops/pallas_gmm.py``: the three kernels under
the Pallas interpreter against ``jax.lax.ragged_dot`` and its ``jax.vjp``,
the walk over the row tiles, the tile rule by hand at the expert cells'
shapes, the two gauges, and the expert layer through the kernels.

Cost: about 30 s in one process on this sandbox's CPU (the interpreter
runs 512-row tiles at widths of 128-384; the rule's cases are arithmetic).
``tests/test_tpu_compile.py`` holds the same kernels to the v5e compiler
at the cells' real widths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluxdistributed_tpu.obs import get_registry
from fluxdistributed_tpu.ops import pallas_attention as pa
from fluxdistributed_tpu.ops import pallas_gmm as pg
from fluxdistributed_tpu.parallel import ep

BF = jnp.bfloat16
ROWS, K, N = 2048, 256, 384

#: group sizes over 2,048 rows (four tiles of 512)
SIZES = {
    # an empty group first and one in the middle, groups that end inside
    # a tile, 124 rows behind the last group
    "ragged": [0, 700, 324, 0, 900],
    "whole_tiles": [512, 1024, 0, 512],  # every boundary on a tile's edge
    "one_group": [2048],
    "few_rows": [3, 0, 0, 5],  # sum(sizes) far below the rows: one tile visited
    "all_empty": [0, 0, 0],
}


def _operands(sizes, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(keys[0], (ROWS, K), BF)
    w = jax.random.normal(keys[1], (len(sizes), K, N), BF) * K ** -0.5
    g = jax.random.normal(keys[2], (ROWS, N), BF)
    return a, w, g, jnp.asarray(sizes, jnp.int32)


def _close(got, want, rtol=2e-2):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    scale = max(float(np.abs(want).max(initial=0.0)), 1.0)
    np.testing.assert_allclose(got, want, atol=rtol * scale, rtol=0)


@pytest.mark.parametrize("tiles", [None, pg.Tiles(512, 128, 128)],
                         ids=["rule", "cut_contraction"])
@pytest.mark.parametrize("case", list(SIZES))
def test_products_match_ragged_dot(case, tiles):
    """``gmm``, ``gmm_t`` and ``tgmm`` against ``ragged_dot`` and its two
    transposes on the groups' rows; bf16 in, float32 sums.  ``tiles``
    given by hand takes the path with an accumulator (three steps along
    the contraction) that the rule leaves unused at these widths."""
    a, w, g, sizes = _operands(SIZES[case])
    live = sum(SIZES[case])
    meta = pg.group_metadata(sizes, ROWS)

    def ref(a, w):
        return jax.lax.ragged_dot(a, w, sizes, preferred_element_type=BF)

    want, pull = jax.vjp(ref, a, w)
    # ragged_dot's own transposes see g's rows behind the groups as well:
    # nought there, as the layer's `live` mask makes them
    g = jnp.where(jnp.arange(ROWS)[:, None] < live, g, 0)
    want_da, want_dw = pull(g)
    t = tiles and pg.Tiles(tiles.m, tiles.n, tiles.k)  # gmm_t contracts N
    got = pg.gmm(a, w, meta, tiles=tiles, interpret=True)
    got_da = pg.gmm(g, w, meta, transpose_rhs=True, tiles=t, interpret=True)
    got_dw = pg.tgmm(a, g, meta, tiles=tiles, interpret=True)
    assert got.dtype == got_da.dtype == got_dw.dtype == BF
    _close(got[:live], want[:live])
    _close(got_da[:live], want_da[:live])
    _close(got_dw, want_dw)
    empty = np.asarray(SIZES[case]) == 0
    assert not np.asarray(got_dw, np.float32)[empty].any()


@pytest.mark.parametrize("sizes", [[512, 512, 0], [300, 600, 100], [0, 0, 7]],
                         ids=["full_then_empty", "ragged", "nearly_empty"])
def test_a_longer_buffers_walk_serves_a_shorter_one(sizes):
    """The expert layer makes one walk a layer, over all its slots, for
    whichever rung the step takes: the products over the first 1,024
    rows with the walk of 2,048.  A buffer that is full with an empty
    group behind it names a tile behind its rows, which the calls hold
    inside (``_tile_in``)."""
    a, w, g, _ = _operands(sizes)
    a, g = a[:1024], g[:1024]
    sizes_ = jnp.asarray(sizes, jnp.int32)
    long_walk = pg.group_metadata(sizes_, ROWS)
    own_walk = pg.group_metadata(sizes_, 1024)
    live = sum(sizes)
    for fn in (lambda m: pg.gmm(a, w, m, interpret=True)[:live],
               lambda m: pg.gmm(g, w, m, transpose_rhs=True,
                                interpret=True)[:live],
               lambda m: pg.tgmm(a, g, m, interpret=True)):
        np.testing.assert_array_equal(
            np.asarray(fn(long_walk), np.float32),
            np.asarray(fn(own_walk), np.float32))
    want = jax.lax.ragged_dot(a, w, sizes_, preferred_element_type=BF)
    _close(pg.gmm(a, w, long_walk, interpret=True)[:live], want[:live])


def test_grouped_dot_vjp_is_the_two_transposes():
    a, w, g, sizes = _operands(SIZES["ragged"], seed=1)
    live = sum(SIZES["ragged"])
    meta = pg.group_metadata(sizes, ROWS)

    def loss(dot, a, w):
        y = jnp.where(jnp.arange(ROWS)[:, None] < live, dot(a, w), 0)
        return jnp.sum(y.astype(jnp.float32) * g.astype(jnp.float32))

    got = jax.grad(functools.partial(
        loss, lambda a, w: pg.grouped_dot(a, w, meta, True)), (0, 1))(a, w)
    want = jax.grad(functools.partial(
        loss, lambda a, w: jax.lax.ragged_dot(
            a, w, sizes, preferred_element_type=BF)), (0, 1))(a, w)
    _close(got[0][:live], want[0][:live])
    _close(got[1], want[1])


def test_tgmm_keeps_rows_it_never_reads_out_of_the_sum():
    """A row behind the last group may hold anything (``gmm`` never
    writes there): a NaN in it must not reach a group's sum."""
    a, _, g, sizes = _operands(SIZES["ragged"])
    live = sum(SIZES["ragged"])
    meta = pg.group_metadata(sizes, ROWS)
    want = pg.tgmm(a, g, meta, interpret=True)
    got = pg.tgmm(a.at[live:].set(jnp.nan), g.at[live:].set(jnp.nan), meta,
                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("case", list(SIZES))
def test_the_walk_by_hand(case):
    """Every tile that holds a row of a group is visited by it, in
    order; an empty group visits one tile; tiles never go backwards (a
    result's block may only be revisited at once)."""
    sizes = SIZES[case]
    offsets, group_ids, m_tile_ids, visits = (
        np.asarray(v) for v in pg.group_metadata(
            jnp.asarray(sizes, jnp.int32), ROWS))
    want, start = [], 0
    for g, size in enumerate(sizes):
        first = min(start // 512, ROWS // 512 - 1)
        last = (start + size - 1) // 512 if size else first
        want += [(g, t) for t in range(first, last + 1)]
        start += size
    assert list(offsets) == [0, *np.cumsum(sizes)]
    assert visits == len(want) <= len(group_ids) == ROWS // 512 + len(sizes) - 1
    assert list(zip(group_ids[:visits], m_tile_ids[:visits])) == want
    assert (np.diff(m_tile_ids) >= 0).all() and m_tile_ids.max() < ROWS // 512


CELLS = {"glm47_flash": (64, 1536), "lfm2_8b_a1b": (32, 1792)}
RUNGS = [(cell, rows) for cell, (experts, _) in CELLS.items()
         for rows in ep.compact_rows(65536, 8, experts)]


@pytest.mark.parametrize("product", pg.PRODUCTS)
@pytest.mark.parametrize("cell,rows", RUNGS,
                         ids=[f"{c}-{r}" for c, r in RUNGS])
def test_tile_rule_at_the_cells_shapes(cell, rows, product):
    """Both expert cells, every rung, each of a layer's widths: a tile
    divides its dimension and is a multiple of 128 or the whole of it,
    the blocks fit the budget, the contraction of ``gmm`` / ``gmm_t`` is
    whole (the weights read once) and no operand is read more than
    twice, where XLA's 512 x 512 x 256 reads the rows 7 or 4 times."""
    d, m = 2048, CELLS[cell][1]
    for k, n in ((d, m), (m, d)):
        tiles = pg.tiles_for(product, rows, k, n, 8)
        assert tiles.m == pg.ROW_TILE and rows % tiles.m == 0
        for tile, dim in ((tiles.k, k), (tiles.n, n)):
            assert dim % tile == 0 and tile % 128 == 0
        assert pg._vmem_bytes(product, tiles, 2) <= pg.VMEM_BUDGET
        reads = pg.operand_reads(product, tiles, rows, k, n, 8)
        assert reads == {"rows": 2.0, "weights": 1.0}
        if product == "tgmm":
            assert tiles[1:] == (k // 2, n // 2)
        else:
            assert tiles[1:] == (k, n // 2)
    xla = pg.Tiles(512, 512, 256)
    assert pg.operand_reads("gmm", xla, rows, d, m, 8) == {
        "rows": m // 256, "weights": (rows // 512 + 7) / 8}


@pytest.mark.parametrize("k,n,itemsize,want", [
    (256, 384, 2, (512, 256, 384)),      # all of it fits: one block a group
    # too long to keep whole, and once it is cut every step fetches its
    # weights whatever the cut: the widest result that fits, rows 4 times
    (8192, 8192, 2, (512, 512, 2048)),
    # whole it fits beside a result 256 wide, the rows read 16 times (805
    # MB at these few rows a group); cut, twice and the weights 1.9 (570)
    (4096, 4096, 2, (512, 512, 2048)),
    (128, 128, 2, (512, 128, 128)),
])
def test_tile_rule_elsewhere(k, n, itemsize, want):
    tiles = pg.tiles_for("gmm", 4096, k, n, 8, itemsize)
    assert tiles == want
    assert pg._vmem_bytes("gmm", tiles, itemsize) <= pg.VMEM_BUDGET


def test_tile_rule_refuses_what_it_cannot_divide():
    assert pg.tileable(1024, 256, 128)
    for shape in ((1000, 256, 128), (1024, 200, 128), (1024, 256, 64)):
        assert not pg.tileable(*shape)
        with pytest.raises(ValueError, match="multiple"):
            pg.tiles_for("gmm", *shape, 4)


def test_gauges_hold_the_call_traced_last():
    a, w, g, sizes = _operands(SIZES["ragged"])
    meta = pg.group_metadata(sizes, ROWS)
    pg.gmm.clear_cache()
    pg.tgmm.clear_cache()
    pg.gmm(a, w, meta, interpret=True)
    pg.gmm(g, w, meta, transpose_rhs=True, tiles=pg.Tiles(512, 128, 128),
           interpret=True)
    pg.tgmm(a, g, meta, tiles=pg.Tiles(512, 128, 128), interpret=True)
    reg = get_registry()

    def tiles(product):
        return tuple(reg.value("fdtpu_gmm_tiles", product, dim)
                     for dim in "mkn")

    def reads(product):
        return tuple(reg.value("fdtpu_gmm_operand_reads", product, operand)
                     for operand in ("rows", "weights"))

    assert tiles("gmm") == (512, K, N) and reads("gmm") == (1, 1)
    # cut in three along N, the contraction here: the weights once a step
    assert tiles("gmm_t") == (512, 128, 128)
    assert reads("gmm_t") == (K // 128, (ROWS // 512 + 4) / 5)
    assert tiles("tgmm") == (512, 128, 128)
    assert reads("tgmm") == ((K * 3 + N * 2) / (K + N), 1)


def test_expert_layer_through_the_kernels(monkeypatch):
    """``held_experts_apply`` as on a TPU (the kernels interpreted) against
    the plain path, forward and every gradient, over a ladder of three
    rungs: the layer's semantics are the plain path's."""
    n, k, d, m, experts, held = 1024, 4, 128, 256, 16, 2
    assert ep.compact_rows(n * k, held, experts) == (1024, 1536, 4096)
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (n, d), BF)
    chosen = jax.random.randint(keys[1], (n, k), 0, experts)
    weights = jax.random.uniform(keys[2], (n, k), jnp.float32)
    w_gate = jax.random.normal(keys[3], (held, d, m)) * d ** -0.5
    w_up = jax.random.normal(keys[4], (held, d, m)) * d ** -0.5
    w_down = jax.random.normal(keys[5], (held, m, d)) * m ** -0.5

    def run():
        ep._rung_forward.clear_cache()
        ep._rung_backward.clear_cache()
        return jax.value_and_grad(
            lambda *a: jnp.sum(ep.held_experts_apply(
                a[0], chosen, *a[1:], experts).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3, 4))(x, weights, w_gate, w_up, w_down)

    want = run()
    calls, grouped_dot = [], pg.grouped_dot

    def interpreted(a, w, walk):
        calls.append(a.shape)
        return grouped_dot(a, w, walk, True)

    monkeypatch.setattr(pa, "interpret_mode", lambda: False)
    monkeypatch.setattr(pg, "grouped_dot", interpreted)
    try:
        got = run()
    finally:
        ep._rung_forward.clear_cache()
        ep._rung_backward.clear_cache()
    assert len(calls) == 3 * (3 + 3)  # three rungs, forward and recomputed
    _close(got[0], want[0])
    for g, w in zip(got[1], want[1]):
        assert g.dtype == w.dtype
        _close(g, w, rtol=4e-2)
