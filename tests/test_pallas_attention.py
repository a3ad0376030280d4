"""Pallas flash-attention kernel vs the XLA reference.

Runs the REAL kernel under the Pallas interpreter on the CPU CI mesh
(same code path as TPU modulo Mosaic lowering), pinned to
``dot_product_attention`` the way the reference pins its DP machinery to
single-batch gradients (test/single_device.jl:42-62).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fluxdistributed_tpu.obs import get_registry
from fluxdistributed_tpu.ops import pallas_attention as pa
from fluxdistributed_tpu.ops.attention import NEG_INF, dot_product_attention
from fluxdistributed_tpu.ops.pallas_attention import (
    flash_attention,
    flash_attention_lse,
    tile_census,
)

# tier-2 (slow): the 27 older Pallas interpret-mode cases, two blocks or fewer a side — the tier-1
# iteration loop must fit its verify window (ROADMAP); CI's slow job still runs them.  The cases
# from `BAND_CASES` down are tier-1: they are the ones in which all three tile classes occur.
slow = pytest.mark.slow


def _qkv(b=2, t=64, h=2, d=32, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, 16, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@slow
def test_flash_non_divisible_seq():
    q, k, v = _qkv(t=40)
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, False, 16, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@slow
def test_flash_causal_decode_shape():
    """Tq != Tk causal must end-align (KV-cache decode), like the reference."""
    q, _, _ = _qkv(t=8)
    q = q[:, :1]  # single query step
    _, k, v = _qkv(t=8, seed=1)
    ref = dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 8, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@slow
def test_fully_masked_row_is_zero_everywhere():
    """All implementations agree: no attendable position → output 0."""
    q, k, v = _qkv(t=8)
    mask = jnp.ones((8, 8), bool).at[3].set(False)[None, None]
    ref = dot_product_attention(q, k, v, mask=mask)
    assert np.abs(np.asarray(ref[:, 3])).max() == 0.0

    # Flash kernel path: causal with Tq > Tk leaves the leading rows with
    # no attendable key (end-aligned) — they must be exactly 0, not NaN.
    q8, _, _ = _qkv(t=8, seed=2)
    _, k4, v4 = _qkv(t=4, seed=3)
    out = flash_attention(q8, k4, v4, True, 4, 4)
    ref2 = dot_product_attention(q8, k4, v4, causal=True)
    assert np.abs(np.asarray(out[:, :4])).max() == 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref2), rtol=2e-5, atol=2e-5)


@slow
def test_flash_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    ref = dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, False, 16, 16)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


@slow
def test_flash_grads_match_reference():
    q, k, v = _qkv(t=32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 8, 8) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_non_divisible(causal):
    """Pallas backward with padded Q and KV blocks (t % block != 0)."""
    q, k, v = _qkv(t=40)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal, 16, 16) ** 2).sum()

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
def test_flash_grads_decode_aligned():
    """Tq != Tk causal backward (end-aligned, the KV-cache convention)."""
    q, _, _ = _qkv(t=8)
    q = q[:, :4]
    _, k, v = _qkv(t=8, seed=1)

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, True, 4, 4) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
def test_flash_grads_fully_masked_rows_finite():
    """Causal Tq > Tk leaves rows with no attendable key: their output is
    0, so every grad must be exactly finite (0 for dq rows) — not NaN
    from exp(s - LSE) with a degenerate LSE."""
    q8, _, _ = _qkv(t=8, seed=2)
    _, k4, v4 = _qkv(t=4, seed=3)

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, True, 4, 4) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q8, k4, v4)
    gr = jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q8, k4, v4)
    for a, b in zip(gf, gr):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)
    # the first 4 query rows attend nothing → dq exactly 0 there
    assert np.abs(np.asarray(gf[0][:, :4])).max() == 0.0


@slow
def test_flash_grads_padded_k_extreme_scores_finite():
    """Non-causal with padded KV blocks and strongly-repelling q/k: a
    row whose every real score is << 0 has LSE < -88, where
    exp(0 - LSE) overflows f32 — the padded K column must be re-masked
    in the backward or dQ picks up inf·0 = NaN."""
    q, k, v = _qkv(t=24)  # 24 % 16 != 0 → one padded KV block
    q = q.at[:, 0].set(q[:, 0] * 0 + 5.0)
    k = k * 0 - 5.0  # row-0 scores ≈ -5·5·D/sqrt(D) ≈ -141 → LSE < -88

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, False, 16, 16) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    # |s| ~ 1e2 exaggerates f32 cancellation in exp(s - LSE); the point
    # here is finiteness plus agreement at a tolerance matching that
    for a, b in zip(gf, gr):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-3)


@slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_repeated_kv(causal):
    """Grouped-query attention: the kernel maps each group of query
    heads onto its shared KV head via BlockSpec index maps (KV never
    repeated in HBM) — fwd and bwd must equal dense attention over
    explicitly repeated KV."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    B, T, Hq, Hkv, D = 2, 32, 8, 2, 16
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    rep = lambda x: jnp.repeat(x, Hq // Hkv, axis=2)

    ref = dot_product_attention(q, rep(k), rep(v), causal=causal)
    out = flash_attention(q, k, v, causal, 8, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, causal, 8, 8) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (
            dot_product_attention(q, rep(k), rep(v), causal=causal) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)  # autodiff through the repeat sums each group for dk/dv
    for a, b in zip(gf, gr):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
@pytest.mark.parametrize("window", [1, 5, 16, 40])
def test_flash_sliding_window_matches_reference(window):
    """Sliding-window attention (causal): parity with the windowed dense
    core at window sizes below/at/above the block size and full-T,
    fwd AND bwd; non-divisible T exercises the padded band."""
    q, k, v = _qkv(t=40)
    ref = dot_product_attention(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, True, 16, 16, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, True, 16, 16, window) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (
            dot_product_attention(q, k, v, causal=True, window=window) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
def test_flash_window_with_gqa():
    """Window and grouped KV compose in one kernel invocation."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    B, T, Hq, Hkv, D = 2, 48, 4, 2, 16
    q = jax.random.normal(ks[0], (B, T, Hq, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    rep = lambda x: jnp.repeat(x, Hq // Hkv, axis=2)
    ref = dot_product_attention(q, rep(k), rep(v), causal=True, window=10)
    out = flash_attention(q, k, v, True, 16, 16, 10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@slow
def test_flash_window_requires_causal():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, False, 16, 16, 8)


@slow
def test_flash_gqa_rejects_indivisible_heads():
    q, k, v = _qkv(h=3)
    with pytest.raises(ValueError, match="multiple of num KV heads"):
        flash_attention(q, k[:, :, :2], v[:, :, :2], False, 16, 16)


@slow
def test_flash_grads_bf16():
    q, k, v = _qkv(dtype=jnp.bfloat16)

    gf = jax.grad(
        lambda q, k, v: (flash_attention(q, k, v, False, 16, 16).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (dot_product_attention(q, k, v).astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), rtol=5e-2, atol=5e-2
        )


@slow
def test_flash_in_vit():
    """ViT wired with the Pallas kernel == ViT with XLA attention."""
    from functools import partial

    from fluxdistributed_tpu.models import vit_tiny

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    m_ref = vit_tiny(num_classes=10, dtype=jnp.float32)
    variables = m_ref.init(jax.random.PRNGKey(0), x, train=False)
    m_flash = vit_tiny(
        num_classes=10, dtype=jnp.float32,
        attn_fn=partial(flash_attention, block_q=16, block_k=16),
    )
    a = m_ref.apply(variables, x, train=False)
    b = m_flash.apply(variables, x, train=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


@slow
@pytest.mark.parametrize("window,sinks", [(8, 2), (12, 8), (16, 1)])
def test_flash_attention_sinks_match_reference(window, sinks):
    """StreamingLLM sinks: first `sinks` keys stay attendable outside
    the window; parity with the windowed+sinked dense core fwd AND bwd
    (T=48 ensures band, sink, and dead regions all exist)."""
    q, k, v = _qkv(t=48)
    ref = dot_product_attention(q, k, v, causal=True, window=window, sinks=sinks)
    out = flash_attention(q, k, v, True, 16, 16, window, sinks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)

    gf = jax.grad(
        lambda q, k, v: (
            flash_attention(q, k, v, True, 16, 16, window, sinks) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    gr = jax.grad(
        lambda q, k, v: (
            dot_product_attention(
                q, k, v, causal=True, window=window, sinks=sinks) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@slow
def test_sinks_require_window():
    q, k, v = _qkv()
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, True, 16, 16, None, 2)
    with pytest.raises(ValueError, match="window"):
        dot_product_attention(q, k, v, causal=True, sinks=2)


# ---------------------------------------------------------------------------
# The three tile classes (tier-1): where a tile lies against the band decides
# what its grid step fetches, masks and computes.

def _brute_mask(tq, tk, bq, bk, causal, window, sinks):
    """The attend mask over the padded grid, from the definition."""
    bq, bk = min(bq, tq), min(bk, tk)
    tq_p, tk_p = tq + -tq % bq, tk + -tk % bk
    q = np.arange(tq_p)[:, None]
    k = np.arange(tk_p)[None, :]
    m = np.broadcast_to(k < tk, (tq_p, tk_p)).copy()
    if causal:
        hi = q + (tk - tq)
        m &= k <= hi
        if window is not None:
            m &= (k >= hi - (window - 1)) | (k < sinks)
    return m, bq, bk


def _brute_classes(tq, tk, bq, bk, causal, window, sinks):
    m, bq, bk = _brute_mask(tq, tk, bq, bk, causal, window, sinks)
    nq, nk = m.shape[0] // bq, m.shape[1] // bk
    tiles = m.reshape(nq, bq, nk, bk).transpose(0, 2, 1, 3).reshape(nq, nk, -1)
    return np.where(tiles.all(-1), "inside",
                    np.where(tiles.any(-1), "across", "outside")), bq, bk


# (tq, tk, block_q, block_k, causal, window, sinks)
BAND_CASES = [
    (4096, 4096, 1024, 1024, True, None, 0),   # the glm47_flash cell: 6 / 6 / 4
    (32, 32, 8, 8, True, None, 0),
    (32, 32, 8, 8, False, None, 0),            # every tile inside
    (30, 30, 8, 8, False, None, 0),            # padded tk: the last column of tiles across
    (30, 30, 8, 8, True, None, 0),
    (16, 40, 8, 8, True, None, 0),             # causal_offset 24, on the blocks
    (16, 37, 8, 8, True, None, 0),             # causal_offset 21, off the blocks, padded tk
    (40, 16, 8, 8, True, None, 0),             # causal_offset -24: rows that see nothing
    (32, 32, 8, 16, True, None, 0),            # block_q != block_k
    (48, 48, 16, 8, True, None, 0),
    (64, 64, 8, 8, True, 20, 0),               # a window's lower edge
    (64, 64, 8, 8, True, 8, 0),
    (64, 64, 8, 8, True, 1, 0),
    (60, 60, 8, 8, True, 12, 0),               # window and padded tk
    (64, 64, 8, 8, True, 12, 4),               # sinks inside the first block
    (64, 64, 8, 8, True, 12, 13),              # sinks across a block boundary
    (64, 64, 8, 8, True, 16, 16),
    (24, 61, 8, 8, True, 10, 3),               # offset, window, sinks and padding at once
    (8, 8, 16, 16, True, None, 0),             # one tile
]


@pytest.mark.parametrize("case", BAND_CASES, ids=lambda c: "-".join(map(str, c)))
def test_tile_census_matches_brute_force(case):
    """`_tile_class` is exact: a tile is inside iff its brute-force mask
    is all true, outside iff all false; `tile_census` counts them."""
    tq, tk, bq, bk, causal, window, sinks = case
    classes, bq, bk = _brute_classes(*case)
    band = pa._band(tq, tk, bk, causal, window, sinks)
    for i in range(classes.shape[0]):
        for j in range(classes.shape[1]):
            live, full = pa._tile_class(i * bq, j * bk, bq, bk, band)
            got = "outside" if not live else "inside" if full else "across"
            assert got == classes[i, j], (i, j)
    assert tile_census(*case) == {
        kind: int((classes == kind).sum())
        for kind in ("outside", "inside", "across")}


def test_tile_census_of_the_cell():
    assert tile_census(4096, 4096, 1024, 1024, True) == {
        "outside": 6, "inside": 6, "across": 4}


@pytest.mark.parametrize("case", BAND_CASES, ids=lambda c: "-".join(map(str, c)))
def test_index_maps_name_live_blocks(case):
    """A live step names its own block; a dead step names a block that a
    live step of the same row of the grid uses (so nothing new is
    fetched), always within range."""
    tq, tk, bq, bk, causal, window, sinks = case
    classes, bq, bk = _brute_classes(*case)
    live = classes != "outside"
    nq, nk = live.shape
    band = pa._band(tq, tk, bk, causal, window, sinks)
    for i in range(nq):
        for j in range(nk):
            # forward / dQ: row i of the grid walks the key blocks
            jj = int(pa._kv_block_index(i, j, bq, bk, nk, band))
            assert 0 <= jj < nk
            if live[i, j]:
                assert jj == j
            elif live[i].any():
                assert live[i, jj], ("kv", i, j, jj)
            # dK/dV: row j of the grid walks the query blocks
            ii = int(pa._q_block_index(j, i, bq, bk, nq, band))
            assert 0 <= ii < nq
            if live[i, j]:
                assert ii == i
            elif live[:, j].any():
                assert live[ii, j], ("q", j, i, ii)


def test_dead_steps_fetch_nothing_in_the_cell():
    """At the cell's 4 x 4 grid the K/V block index changes only on a
    live step (or at a row's first step), for all three kernels' walks."""
    band = pa._band(4096, 4096, 1024, True, None, 0)
    for i in range(4):
        walk = [int(pa._kv_block_index(i, j, 1024, 1024, 4, band)) for j in range(4)]
        assert walk == [min(j, i) for j in range(4)]
    for j in range(4):
        walk = [int(pa._q_block_index(j, i, 1024, 1024, 4, band)) for i in range(4)]
        assert walk == [max(i, j) for i in range(4)]


def test_diagonal_quarter_only_for_the_plain_square_diagonal():
    """The quarter above the diagonal is left out for square blocks of
    256 up on the blocks' own diagonal; everything else is one masked whole."""
    sl = slice
    band = pa._band(1024, 1024, 256, True, None, 0)
    assert pa._across_parts(256, 256, band, by_cols=False) == (
        (sl(0, 128), sl(0, 128)), (sl(128, 256), sl(0, 256)))
    assert pa._across_parts(256, 256, band, by_cols=True) == (
        (sl(0, 256), sl(0, 128)), (sl(128, 256), sl(128, 256)))
    whole = ((sl(0, 256), sl(0, 512)),)
    assert pa._across_parts(256, 512, band, False) == whole  # block_q != block_k
    for other in (pa._band(1024, 1024, 256, True, 300, 0),    # a window
                  pa._band(1024, 1024 + 40, 256, True, None, 0),  # off the blocks
                  pa._band(1024, 1024, 256, False, None, 0)):
        assert pa._across_parts(256, 256, other, False) == ((sl(0, 256), sl(0, 256)),)
    small = pa._band(64, 64, 8, True, None, 0)
    assert pa._across_parts(8, 8, small, False) == ((sl(0, 8), sl(0, 8)),)


def _dense(q, k, v, causal, window=None, sinks=0):
    """(out, lse) of plain attention, [B, T, H, D] -> ([B, Tq, H, D], [B, H, Tq])."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / q.shape[-1] ** 0.5
    tq, tk = q.shape[1], k.shape[1]
    m, _, _ = _brute_mask(tq, tk, tq, tk, causal, window, sinks)
    s = jnp.where(m[None, None], s, NEG_INF)
    lse = jax.nn.logsumexp(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(s - lse[..., None]), v)
    return out, lse


def _inputs(tq, tk, h=2, hkv=2, d=16, b=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (b, tq, h, d)),
            jax.random.normal(ks[1], (b, tk, hkv, d)),
            jax.random.normal(ks[2], (b, tk, hkv, d)))


# name: (tq, tk, block_q, block_k, window, sinks, heads, kv heads); all
# causal, four blocks or more a side, so one call holds all three classes
ALL_CLASSES = {
    "causal": (32, 32, 8, 8, None, 0, 2, 2),
    "causal_offset": (32, 48, 8, 8, None, 0, 2, 2),
    "causal_offset_off_blocks": (32, 45, 8, 8, None, 0, 2, 2),
    "window": (64, 64, 8, 8, 20, 0, 2, 2),
    "window_sinks": (64, 64, 8, 8, 12, 13, 2, 2),
    "gqa": (32, 32, 8, 8, None, 0, 4, 2),
    "padded_last_block": (37, 37, 8, 8, None, 0, 2, 2),
    "block_q_ne_block_k": (64, 64, 8, 16, None, 0, 2, 2),
    "diagonal_quarter": (1024, 1024, 256, 256, None, 0, 1, 1),
    "diagonal_quarter_offset_gqa": (512, 1024, 256, 256, None, 0, 2, 1),
    "diagonal_quarter_padded": (1000, 1000, 256, 256, None, 0, 1, 1),
}


@pytest.mark.parametrize("name", ALL_CLASSES)
def test_all_classes_in_one_call_match_reference(name):
    """Forward, dQ, dK and dV against plain attention where tiles outside,
    inside and across the band all occur in the one call."""
    tq, tk, bq, bk, window, sinks, h, hkv = ALL_CLASSES[name]
    census = tile_census(tq, tk, bq, bk, True, window, sinks)
    assert min(census.values()) > 0, census
    q, k, v = _inputs(tq, tk, h, hkv)
    w = jax.random.normal(jax.random.PRNGKey(7), (1, tq, h, 16))

    def loss(attn):
        return lambda q, k, v: (attn(q, k, v) * w).sum()

    flash = lambda q, k, v: flash_attention(q, k, v, True, bq, bk, window, sinks)
    dense = lambda q, k, v: _dense(q, k, v, True, window, sinks)[0]
    np.testing.assert_allclose(
        np.asarray(flash(q, k, v)), np.asarray(dense(q, k, v)), rtol=2e-5, atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(32, 32, 8), (32, 48, 8), (1024, 1024, 256)],
                         ids=["causal", "causal_offset", "diagonal_quarter"])
def test_all_classes_lse_with_upstream_gradient(shape):
    """`flash_attention_lse` with a cotangent into the LSE output (what
    ring attention's combine sends back), all three classes in the call."""
    tq, tk, blk = shape
    q, k, v = _inputs(tq, tk)
    wo = jax.random.normal(jax.random.PRNGKey(7), (1, tq, 2, 16))
    wl = jax.random.normal(jax.random.PRNGKey(8), (1, 2, tq))

    def loss(attn):
        def f(q, k, v):
            out, lse = attn(q, k, v)
            return (out * wo).sum() + (lse * wl).sum()
        return f

    flash = lambda q, k, v: flash_attention_lse(q, k, v, True, blk, blk)
    dense = lambda q, k, v: _dense(q, k, v, True)
    for a, b in zip(flash(q, k, v), dense(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
    gf = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


def test_flash_tiles_gauge_holds_the_census_of_the_last_trace():
    reg = get_registry()

    def read(kernel):
        return {kind: reg.value("fdtpu_flash_tiles", kernel, kind)
                for kind in ("outside", "inside", "across")}

    # shapes no other case of this file traces, so both calls trace
    q, k, v = _inputs(40, 40, d=8)
    jax.grad(lambda q, k, v: flash_attention(q, k, v, True, 8, 8).sum())(q, k, v)
    first = tile_census(40, 40, 8, 8, True)
    assert first == {"outside": 10, "inside": 10, "across": 5}
    for kernel in pa.KERNEL_NAMES:
        assert read(kernel) == first, kernel

    q, k, v = _inputs(24, 48, d=8)
    flash_attention(q, k, v, True, 8, 8, 10)  # forward only, a window
    second = tile_census(24, 48, 8, 8, True, 10)
    assert second != first
    assert read(pa.KERNEL_NAMES[0]) == second
    for kernel in pa.KERNEL_NAMES[1:]:  # the backward kernels were not traced again
        assert read(kernel) == first, kernel
