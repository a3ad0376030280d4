"""Phi-4-mini-flash on the CPU at tiny sizes in float32: the selective
scan's Pallas kernels (interpreted) against the plain ``lax.scan``, the
flash kernels with a value twice the key's width against the plain
attention, and the model against the plain reference
(``chipbench/configs/phi4_mini_flash.py``) on its seeded weights, loss
and every leaf's gradient, for an uncut stack and for the cell's cut;
which operator and ``lambda_init`` each published layer gets.

Tolerances: both sides compute in float32 (products at ``highest`` in
the reference), so they differ by the order of their sums alone.  The
scan's two paths read under 1e-6 relative, the model's under 1e-5 by
leaf: ``SCAN_TOL`` 1e-5 and ``TOL`` 5e-5 are ten and five times that,
and a hundredth or less of what the scan in bfloat16 or a dropped
``lambda`` gives (both held below to fail it)."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import fluxdistributed_tpu as fd
from fluxdistributed_tpu.obs import get_registry
from fluxdistributed_tpu.ops import pallas_scan as ps
from fluxdistributed_tpu.ops.attention import dot_product_attention
from fluxdistributed_tpu.ops.pallas_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from chipbench import harness, refcommon  # noqa: E402

pf = importlib.import_module("fluxdistributed_tpu.models.phi4_flash")
REF = harness.load_module(os.path.join(ROOT, "chipbench", "configs",
                                       "phi4_mini_flash.py"))
BENCH = harness.load_module(os.path.join(ROOT, "benchmarks", "scan_bench.py"))
SCAN_TOL, TOL = 1e-5, 5e-5


def rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.fixture
def tiles(monkeypatch):
    """``tiles(chunk)``: chunks of ``chunk`` positions and channel blocks
    of 128 (a ``d_inner`` of 256 is two of them), where the cell's are 256
    and 512 / 256; the jitted wrappers keep the traces made here to
    themselves."""
    monkeypatch.setattr(ps, "BLOCK_FWD", 128)
    monkeypatch.setattr(ps, "BLOCK_BWD", 128)
    yield lambda chunk: monkeypatch.setattr(ps, "CHUNK", chunk)
    ps._scan_fwd.clear_cache()
    ps._scan_bwd.clear_cache()


@pytest.mark.parametrize("t,chunk,c,n", [
    (64, 256, 256, 16), (40, 16, 256, 16), (64, 8, 256, 16),
    (45, 16, 256, 16), (600, 256, 256, 16), (40, 16, 96, 16), (40, 16, 256, 4)],
    ids=["one_chunk", "padded_chunks", "eight_chunks", "partial_loop_step",
         "three_chunks_in_reverse", "narrow_channels", "four_states"])
def test_selective_scan_kernels_match_the_plain_scan(tiles, t, chunk, c, n):
    """Forward and all six gradients, over chunks (the kept states, the
    state's gradient carried back across them), padded positions, two
    channel blocks or one narrower than a block; a loop step whose last
    positions are padding (45 = 5 x 8 + 5); 4 states, fewer than the
    8 sublanes of a tile; the gradients of ``B`` and ``C``, which
    leave a loop step as one ``[N, UNROLL]`` tile, also element by
    element."""
    tiles(chunk)
    args, w = BENCH.inputs(2, t, c, n)
    y = ps.selective_scan(*args)
    assert rel(y, ps.selective_scan_xla(*args)) < SCAN_TOL
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * w)  # noqa: E731
    got = jax.grad(loss(ps.selective_scan), argnums=range(6))(*args)
    want = jax.grad(loss(ps.selective_scan_xla), argnums=range(6))(*args)
    for name, g, h in zip(("u", "delta", "A", "B", "C", "D"), got, want):
        assert g.shape == h.shape and rel(g, h) < SCAN_TOL, name
    for g, h in zip(got[3:5], want[3:5]):
        np.testing.assert_allclose(g, h, rtol=SCAN_TOL,
                                   atol=SCAN_TOL * float(jnp.max(jnp.abs(h))))
    reg = get_registry()
    kept = reg.value("fdtpu_scan_state_bytes", "kept")
    assert kept == 2 * -(-t // min(chunk, -(-t // 8) * 8)) * n * c * 4
    assert reg.value("fdtpu_scan_state_bytes", "all") == 2 * t * n * c * 4
    assert reg.value("fdtpu_scan_tiles", "channels_fwd") == min(c, 128)
    assert reg.value("fdtpu_scan_tiles", "columns_per_load") == ps.UNROLL == 8


def test_the_scan_micro_benchmark(monkeypatch, capsys):
    """``benchmarks/scan_bench.py`` times a copy of the kernels' module
    loaded by path (as it times another commit's), holds ``y`` and each
    gradient to the plain scan by name and reads its gauges; on a backend
    that is no TPU it refuses."""
    bench = BENCH
    mod = bench.load(os.path.join(ROOT, "fluxdistributed_tpu", "ops", "pallas_scan.py"))
    assert mod is not ps and mod.KERNEL_NAMES == ps.KERNEL_NAMES
    args, w = bench.inputs(1, 40, 256, 16)
    row = bench.bench(mod, args, w, reps=1, check=(1, 45, 256))
    assert row["fwd_ms"] > 0 and row["fwdbwd_ms"] > row["bwd_ms"]
    assert list(row["rel_err"]) == ["y", "u", "delta", "A", "B", "C", "D"]
    assert max(row["rel_err"].values()) == row["max_rel_err"] < SCAN_TOL
    assert row["tiles"] == {"chunk": 40, "channels_fwd": 256,
                            "channels_bwd": 256, "columns_per_load": 8}
    monkeypatch.setattr("sys.argv", ["scan_bench.py"])
    assert bench.main() == 1
    assert json.loads(capsys.readouterr().out) == {"error": "no TPU",
                                                   "platform": "cpu"}


def test_scan_tiles_at_the_cells_widths():
    tiles = ps.scan_tiles(4096, 5120)
    assert tiles == ps.ScanTiles(256, 512, 256)
    # one state a chunk kept: 1 / 256 of every position's
    assert 100 * (4096 // tiles.chunk) / 4096 == 0.390625
    assert ps.scan_tiles(40, 5120).chunk == 40 and ps.scan_tiles(3, 96).chunk == 8
    assert ps.scan_tiles(64, 96) == ps.ScanTiles(64, 96, 96)


@pytest.mark.parametrize("causal,window,h,hkv", [
    (True, None, 4, 2), (True, 24, 4, 2), (False, None, 4, 4)],
    ids=["causal_gqa", "window_gqa", "full"])
def test_flash_kernels_with_a_value_twice_the_key(causal, window, h, hkv):
    """``v`` at twice ``q``'s and ``k``'s width (differential attention's
    shared value): the output and ``dv`` take it, the scale stays
    ``q``'s; forward and the three gradients."""
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (2, 64, h, 8))
    k = jax.random.normal(ks[1], (2, 64, hkv, 8))
    v = jax.random.normal(ks[2], (2, 64, hkv, 16))
    w = jax.random.normal(ks[3], (2, 64, h, 16))
    flash = lambda q, k, v: flash_attention(q, k, v, causal, 16, 16, window)  # noqa: E731
    plain = lambda q, k, v: dot_product_attention(  # noqa: E731
        q, k, v, causal=causal, window=window)
    out = flash(q, k, v)
    assert out.shape == (2, 64, h, 16) and rel(out, plain(q, k, v)) < 2e-5
    grads = [jax.grad(lambda *a: jnp.sum(fn(*a) * w), argnums=(0, 1, 2))(q, k, v)
             for fn in (flash, plain)]
    for g, want in zip(*grads):
        assert g.shape == want.shape and rel(g, want) < 1e-4


def cell_config():
    with open(os.path.join(ROOT, "chipbench", "configs", "phi4_mini_flash.json")) as f:
        return json.load(f)


def tiny(layers, offset, published, impl):
    """(reference's cfg, the program's factory keywords) at a tiny size:
    hidden 32, 4 heads of 8 over 2 key-value heads (one pair, a shared
    value of 16), d_inner 64, a window of 16 over rows of 64."""
    cfg = dict(cell_config(), hidden_size=32, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=64, sliding_window=16,
               num_hidden_layers=layers, layer_offset=offset,
               published=dict(cell_config()["published"], num_hidden_layers=published),
               mamba=dict(cell_config()["mamba"], dt_rank=2),
               input={"kind": "tokens", "seq_len": 64, "vocab": 64})
    model = dict(cell_config()["model"]["kwargs"], vocab=64, dim=32,
                 num_layers=layers, layer_offset=offset, published_layers=published,
                 num_heads=4, num_kv_heads=2, intermediate_size=64, sliding_window=16,
                 dt_rank=2, dtype="float32", attention_impl=impl, attn_block_q=16,
                 attn_block_k=16, remat=impl == "pallas")
    return cfg, model


def gaps(cfg, model_kw, seed=3):
    """The loss's gap and the worst leaf's gradient gap, program against
    reference, on the reference's seeded weights and two rows."""
    model = fd.models.phi4_flash(**model_kw)
    params, _ = REF.make_params(cfg, jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 64), 0, 64)
    loss = fd.models.lm_loss_fn(model)
    got, g_got = jax.jit(jax.value_and_grad(
        lambda p: loss(p, {}, {"tokens": toks}, True)[0]))(params)
    prec = refcommon.Precision("f32")
    want, g_want = jax.jit(jax.value_and_grad(
        lambda p: REF.row_loss_sum(cfg, prec, p, {}, toks)[0] / 2))(params)
    leaves = jax.tree.leaves(jax.tree.map(rel, g_got, g_want))
    assert len(leaves) == len(REF.param_shapes(cfg))
    return abs(float(got) - float(want)) / abs(float(want)), max(leaves)


STACKS = {"uncut_8": (8, 0, 8), "cut_14_19": (6, 14, 32)}


@pytest.mark.parametrize("stack,impl", [
    ("uncut_8", "pallas"), ("cut_14_19", "pallas"), ("cut_14_19", "xla")])
def test_the_model_is_the_reference(stack, impl):
    """An uncut stack of 8 (the boundary at 4-5: Mamba and window, the
    memory at 4, the keys and values at 5, two GMU and cross layers
    after) and the cell's cut of published layers 14-19, on the kernels
    (interpreted, the layers rematerialised) and the cut on the plain
    path too."""
    loss_gap, grad_gap = gaps(*tiny(*STACKS[stack], impl))
    assert loss_gap < TOL and grad_gap < TOL, (loss_gap, grad_gap)


def _bf16_scan(*args):
    return ps.selective_scan_xla(*(x.astype(jnp.bfloat16).astype(jnp.float32)
                                   for x in args))


@pytest.mark.parametrize("fault", ["scan_in_bf16", "lambda_dropped"])
def test_the_tolerance_catches(monkeypatch, fault):
    if fault == "scan_in_bf16":
        monkeypatch.setattr(pf, "selective_scan_xla", _bf16_scan)
    else:
        monkeypatch.setattr(pf, "diff_lambda", lambda *a: 0.0)
    loss_gap, grad_gap = gaps(*tiny(*STACKS["cut_14_19"], "xla"))
    assert grad_gap > 100 * TOL, (loss_gap, grad_gap)


def test_the_cut_follows_published_layers_14_to_19():
    kw = cell_config()["model"]["kwargs"]
    cfg = pf.Phi4FlashConfig(**{k: v for k, v in kw.items() if k != "dtype"})
    kinds = [pf.layer_kind(cfg, 14 + i) for i in range(6)]
    assert kinds == ["mamba", "window", "mamba", "full", "gmu", "cross"]
    # of the six consecutive layers that hold every kind, the cut is the
    # one with two Mamba layers (15-20 holds one, and two gated memory units)
    every = [s for s in range(27) if {pf.layer_kind(cfg, s + i)
                                      for i in range(6)} == set(pf.LAYER_KINDS)]
    assert every == [14, 15]
    assert [pf.layer_kind(cfg, 15 + i) for i in range(6)].count("mamba") == 1
    assert [pf.layer_kind(cfg, i) for i in range(32)].count("mamba") == 9
    ref = cell_config()
    assert [REF.kind_of(ref, 14 + i) for i in range(6)] == kinds
    assert [round(pf.lambda_init(14 + i), 6) for i in range(6)] == [
        round(0.8 - 0.6 * np.exp(-0.3 * (14 + i)), 6) for i in range(6)]
    assert pf.lambda_init(15) == REF.lambda_init(15)
    # the gauge counts the program's kinds: 2 / 1 / 1 / 1 / 1
    _, model_kw = tiny(6, 14, 32, "xla")
    model = fd.models.phi4_flash(**model_kw)
    jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                      jnp.zeros((1, 64), jnp.int32)))
    assert {k: get_registry().value("fdtpu_layer_kinds", k)
            for k in pf.LAYER_KINDS} == {"mamba": 2, "window": 1, "full": 1,
                                         "gmu": 1, "cross": 1}


def test_a_cut_without_its_sources_and_decode_are_refused():
    with pytest.raises(ValueError, match="reads layer 16"):
        pf.Phi4FlashConfig(num_layers=3, layer_offset=17)
    with pytest.raises(NotImplementedError, match="scan state"):
        pf.Phi4Flash(pf.Phi4FlashConfig(), decode=True)
    assert "memory" in pf.NO_DECODE and "cross-attention" in pf.NO_DECODE
