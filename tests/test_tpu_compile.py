"""The Pallas kernels of the chip path, put through the v5e compiler.

The only file of the suite that describes a chip.  Interpret mode (what
every other kernel test runs) cannot see what Mosaic refuses — a block
that is not (8, 128)-tileable, too much VMEM — so each Pallas entry
point is compiled here for a DESCRIBED ``v5e:2x2`` device at the widths
``chip_smoke.py`` runs on the real one: GPT-2-small attention (12 heads
x 64) at T = 1024 and 2048, decode batch 8 over a 1024-row cache, the
paged pool ``[512, 16, 12, 64]``, Adam over a ResNet-50-sized flat
buffer.  Nothing runs: a compile that passes is not a chip run.

The topology is described inside a module-scoped, non-autouse fixture
(never at import, in a ``skipif`` or in ``parametrize``): only the
worker that is handed this file loads the TPU compiler, and it compiles
in its own process with the persistent cache off.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from fluxdistributed_tpu.ops import pallas_attention as pa
from fluxdistributed_tpu.ops import pallas_decode as pd
from fluxdistributed_tpu.parallel import zero1_fused as zf

H, HKV, D = 12, 4, 64
B_ATTN, B_DEC, ROWS = 4, 8, 1024
POOL, PAGE = 512, 16
ADAM_N = 25_557_032 + (-25_557_032) % 1024
BF = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """``chip(shape, dtype)`` → an argument placed on one described v5e
    chip, with the persistent cache off around the module's compiles (a
    described device's entries can be written but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()
    # the flash wrappers bake "interpreted or compiled" in at trace
    # time: drop the compiled-mode traces made here
    pa._flash_fwd_impl.clear_cache()
    pa._flash_bwd_impl.clear_cache()


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The process's backend is the CPU, so the flash wrappers would
    pick the interpreter; steer that here, in the test."""
    monkeypatch.setattr(pa, "interpret_mode", lambda: False)


def _compile(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _grads(attn):
    return jax.grad(
        lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))


@pytest.mark.parametrize("t", [1024, 2048])
def test_flash_forward(chip, as_on_tpu, t):
    q = chip((B_ATTN, t, H, D), BF)
    _compile(lambda q, k, v: pa.flash_attention(q, k, v, True), q, q, q)


@pytest.mark.parametrize("t", [1024, 2048])
def test_flash_backward(chip, as_on_tpu, t):
    """Forward + the dQ and dK/dV kernels (three custom calls)."""
    q = chip((B_ATTN, t, H, D), BF)
    text = _compile(
        _grads(lambda q, k, v: pa.flash_attention(q, k, v, True)), q, q, q)
    assert text.count("tpu_custom_call") >= 3


def test_flash_backward_gqa(chip, as_on_tpu):
    q = chip((B_ATTN, 1024, H, D), BF)
    kv = chip((B_ATTN, 1024, HKV, D), BF)
    _compile(_grads(lambda q, k, v: pa.flash_attention(q, k, v, True)),
             q, kv, kv)


def test_flash_backward_window_sinks(chip, as_on_tpu):
    q = chip((B_ATTN, 2048, H, D), BF)
    _compile(_grads(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 128, 128, 256, 4)), q, q, q)


def test_flash_lse_backward(chip, as_on_tpu):
    """The ring-attention building block: LSE out, and its cotangent
    folded into the same backward kernels."""
    q = chip((B_ATTN, 1024, H, D), BF)
    _compile(jax.grad(
        lambda q, k, v: sum(x.astype(jnp.float32).sum() for x in
                            pa.flash_attention_lse(q, k, v, True)),
        argnums=(0, 1, 2)), q, q, q)


def test_flash_backward_latent_attention_widths(chip, as_on_tpu):
    """The `glm47_flash` cell's attention: 20 heads of 256 over 4,096
    positions in 1,024 x 1,024 blocks, under the names the trace reducer
    reads (`chipbench/metrics/attn_roofline_pct.py`)."""
    q = chip((1, 4096, 20, 256), BF)
    text = _compile(_grads(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 1024, 1024)), q, q, q)
    from fluxdistributed_tpu.obs import get_registry

    for name in pa.KERNEL_NAMES:
        assert f"%{name}" in text, name
        # a (row, head)'s 4 x 4 tiles by class, as traced for this call
        assert [get_registry().value("fdtpu_flash_tiles", name, kind)
                for kind in ("outside", "inside", "across")] == [6, 6, 4]


def _kept_out(q, k, v):
    return pa.flash_attention(q, k, v, True, 1024, 1024)


def _kept_lse(q, k, v):
    """`flash_attention_lse` with both results used, as ring attention
    uses them: the rows' statistics weigh the output."""
    out, lse = pa.flash_attention_lse(q, k, v, True, 1024, 1024)
    return out * jax.nn.sigmoid(lse).transpose(0, 2, 1)[..., None].astype(BF)


@pytest.mark.parametrize("attn,policy,forwards", [
    (_kept_out, True, 1), (_kept_lse, True, 1), (_kept_out, False, 2),
], ids=["flash_attention", "flash_attention_lse", "plain_remat"])
def test_a_rematerialised_stack_runs_the_forward_kernel_once(
        chip, as_on_tpu, monkeypatch, attn, policy, forwards):
    """Two rematerialised layers (`models.common.maybe_remat`) of the
    `glm47_flash` cell's attention widths, forward and backward: a layer
    holds ONE forward call, one dQ, one dK/dV.  What the forward made
    (`out`, `lse`) is kept by name across the block, so the backward
    pass's second run of the block drops its forward call as dead code;
    under plain `nn.remat`, the third case, it runs twice."""
    import re

    from flax import linen as nn

    from fluxdistributed_tpu.models.common import maybe_remat
    from fluxdistributed_tpu.obs import get_registry

    if not policy:
        monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                            lambda *names: None)

    class Block(nn.Module):
        @nn.compact
        def __call__(self, x):
            qkv = nn.DenseGeneral((3, 20, 256), dtype=BF, use_bias=False)(x)
            out = attn(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2])
            return x + nn.DenseGeneral(
                x.shape[-1], axis=(-2, -1), dtype=BF, use_bias=False)(out)

    class Stack(nn.Module):
        @nn.compact
        def __call__(self, x):
            block = maybe_remat(Block, True)
            for i in range(2):
                x = block(name=f"layer{i}")(x)
            return x

    x = chip((2, 4096, 2048), BF)
    params = jax.tree.map(
        lambda p: chip(p.shape, p.dtype),
        jax.eval_shape(Stack().init, jax.random.PRNGKey(0), x))
    text = _compile(jax.value_and_grad(lambda p, x: Stack().apply(
        p, x).astype(jnp.float32).sum()), params, x)
    calls = [len(re.findall(rf"%{name}[.\d]* = ", text))
             for name in pa.KERNEL_NAMES]
    assert calls == [2 * forwards, 2, 2]
    # out in bf16 and the rows' statistics in float32, of one call
    assert get_registry().value("fdtpu_flash_kept_bytes", pa.KERNEL_NAMES[0]) \
        == 2 * 4096 * 20 * (256 * 2 + 4)


def test_flash_backward_grouped_query_widths(chip, as_on_tpu):
    """The `lfm2_8b_a1b` cell's attention call as the step makes it: 4
    rows of 4,096 positions, 32 query heads over 8 key-value heads of 64
    (half the MXU's lanes), 1,024 x 1,024 blocks; `k`, `v` and their
    gradients stay at 8 heads, and the kernels keep the names the trace
    reducer reads (`chipbench/metrics/gqa_attn_roofline_pct.py`)."""
    q, kv = chip((4, 4096, 32, 64), BF), chip((4, 4096, 8, 64), BF)
    fn = _grads(lambda q, k, v: pa.flash_attention(q, k, v, True, 1024, 1024))
    text = _compile(fn, q, kv, kv)
    out = jax.eval_shape(fn, q, kv, kv)
    assert [x.shape for x in out] == [q.shape, kv.shape, kv.shape]
    from fluxdistributed_tpu.obs import get_registry

    for name in pa.KERNEL_NAMES:
        assert f"%{name}" in text, name
        assert [get_registry().value("fdtpu_flash_tiles", name, kind)
                for kind in ("outside", "inside", "across")] == [6, 6, 4]


def test_eva_attention_calls_at_the_cells_widths(chip, as_on_tpu):
    """The `evabyte` cell's EVA layer as the step makes it: 2 rows of
    8,192 positions, 8 held heads of 128, windows of 2,048 in chunks of
    16.  The exact part is ONE causal call on the 8 folded windows in
    1,024 x 1,024 blocks (a 2 x 2 grid a window and head: 1 tile outside
    the band, 1 inside, 2 across); the summarised part one non-causal
    call a window after the first over 128, 256 and 384 summaries, every
    tile inside.  Forward and gradients: 4 calls of each kernel."""
    from fluxdistributed_tpu.ops.eva_attention import eva_attention

    q, mu = chip((2, 8192, 8, 128), BF), chip((8, 128), jnp.float32)
    fn = jax.grad(lambda q, k, v, mu, phi: eva_attention(
        q, k, v, mu, phi, window=2048, chunk=16, impl="pallas",
        block_q=1024, block_k=1024).astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4))
    text = _compile(fn, q, q, q, mu, mu)
    assert [x.shape for x in jax.eval_shape(fn, q, q, q, mu, mu)] == [
        q.shape] * 3 + [mu.shape] * 2
    for name in pa.KERNEL_NAMES:
        assert text.count(f"%{name}") >= 4, name
    assert pa.tile_census(2048, 2048, 1024, 1024, True) == {
        "outside": 1, "inside": 1, "across": 2}
    for tk in (128, 256, 384):
        assert pa.tile_census(2048, tk, 1024, tk, False) == {
            "outside": 0, "inside": 2, "across": 0}
    # the two expert cells' calls, whose census this PR leaves alone
    assert pa.tile_census(4096, 4096, 1024, 1024, True) == {
        "outside": 6, "inside": 6, "across": 4}
    from fluxdistributed_tpu.obs import get_registry

    assert get_registry().value("fdtpu_eva_pairs", "summary") == 1572864
    assert get_registry().value("fdtpu_eva_pairs", "local") == 8392704


def _window_bounds(call: str) -> list:
    """The blocks of a Pallas call's operands and results, in order,
    from its line of HLO text: the Mosaic body is MLIR bytecode, base64
    under ``backend_config``."""
    import base64
    import re

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    body = call.split('"body":"', 1)[1].split('"', 1)[0]
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        text = str(ir.Module.parse(base64.b64decode(body)))
    return [tuple(int(n) for n in bounds.split(","))
            for bounds in re.findall(r"window_bounds = array<i64: ([\d, ]+)>",
                                     text)]


@pytest.fixture
def fresh_rungs():
    """The rungs are jitted and keep their traces: drop the ones made
    here as on a TPU, so that no later test of this worker finds them."""
    from fluxdistributed_tpu.parallel import ep

    yield
    ep._rung_forward.clear_cache()
    ep._rung_backward.clear_cache()


@pytest.mark.parametrize("experts,moe_dim,scale", [
    (64, 1536, 1.8), (32, 1792, 1.0)], ids=["glm47_flash", "lfm2_8b_a1b"])
def test_held_experts_grouped_products(chip, as_on_tpu, fresh_rungs, experts,
                                       moe_dim, scale):
    """The expert cells' layer: 16,384 tokens, 4 of ``experts`` experts a
    token, 8 held; every grouped product, forward and both gradients, is
    the repo's own kernel under the name the reducer reads, and none is
    left under XLA's ``ragged-dot-none``.  Each direction is one
    conditional with a branch a rung of the ladder: three products
    forward, nine backward (the forward's again and six gradients), and
    only the last rung makes an array as long as the slots.  The block
    of a product's weights spans the whole contraction at both widths:
    a group's weights are fetched once, not once a row tile."""
    import re

    from fluxdistributed_tpu.obs import get_registry
    from fluxdistributed_tpu.ops import pallas_gmm
    from fluxdistributed_tpu.parallel import ep

    x = chip((16384, 2048), BF)
    router = chip((2048, experts), jnp.float32)
    w_in = chip((8, 2048, moe_dim), jnp.float32)
    w_out = chip((8, moe_dim, 2048), jnp.float32)

    def layer(x, router, w_gate, w_up, w_down):
        chosen, weights, _ = ep.sigmoid_route(
            x, router, jnp.zeros((experts,)), top_k=4, scale=scale)
        return ep.held_experts_apply(x, chosen, weights, w_gate, w_up, w_down,
                                     experts)

    text = _compile(jax.value_and_grad(
        lambda *a: layer(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)),
        x, router, w_in, w_in, w_out)
    assert "%ragged-dot-none" not in text
    ladder = ep.compact_rows(65536, 8, experts)
    assert len(ladder) == 5 and ladder[-1] == 65536
    conditionals = re.findall(
        r" conditional\(.*branch_computations=\{([^}]*)\}", text)
    products = []
    kernel = rf"%{pallas_gmm.KERNEL_NAME}[.\d]* = (\S+)\[([\d,]+)\].*"
    for names in conditionals:
        branches = [text[text.index(f"\n{name.strip()} ("):].split("\n}\n", 1)[0]
                    for name in names.split(",")]
        assert len(branches) == len(ladder)
        for rows, branch in zip(ladder, branches):
            assert f"[{rows},{moe_dim}]" in branch
            assert ("[65536," in branch) == (rows == 65536)
            for call in re.finditer(kernel, branch):
                shape = tuple(int(n) for n in call.group(2).split(","))
                lhs, rhs, out = _window_bounds(call.group(0))
                if len(shape) == 2:  # gmm, gmm_t: [1, K, tn] or [1, tn, K]
                    assert shape[0] == rows and lhs[1] in rhs[1:]
                    assert sorted(rhs[1:]) == sorted((lhs[1], out[1]))
                    assert lhs[1] in (2048, moe_dim)  # the contraction whole
                else:  # tgmm: both row operands a tile of 512 rows
                    assert lhs[0] == rhs[0] == pallas_gmm.ROW_TILE
                    assert out == (1, lhs[1], rhs[1])
        (count,) = {len(re.findall(kernel, branch)) for branch in branches}
        products.append(count)
    assert sorted(products) == [3, 9]  # forward; backward with its forward
    reg = get_registry()
    for product in pallas_gmm.PRODUCTS:
        assert reg.value("fdtpu_gmm_tiles", product, "m") == 512
        assert reg.value("fdtpu_gmm_operand_reads", product, "rows") == 2
        assert reg.value("fdtpu_gmm_operand_reads", product, "weights") == 1


@pytest.mark.parametrize("hkv", [H, HKV], ids=["dense", "gqa"])
def test_decode(chip, hkv):
    q, idx = chip((B_DEC, 1, H, D), BF), chip((B_DEC,), jnp.int32)
    c = chip((B_DEC, ROWS, hkv, D), BF)
    _compile(lambda q, k, v, i: pd.flash_decode(q, k, v, i, impl="pallas"),
             q, c, c, idx)


def test_decode_ring_slot_pos(chip):
    q, idx = chip((B_DEC, 1, H, D), BF), chip((B_DEC,), jnp.int32)
    c, sp = chip((B_DEC, ROWS, H, D), BF), chip((B_DEC, ROWS), jnp.int32)
    _compile(lambda q, k, v, i, sp: pd.flash_decode(
        q, k, v, i, slot_pos=sp, window=ROWS - 4, sinks=4, impl="pallas"),
        q, c, c, idx, sp)


def test_decode_int8_scales(chip):
    q, idx = chip((B_DEC, 1, H, D), BF), chip((B_DEC,), jnp.int32)
    c = chip((B_DEC, ROWS, H, D), jnp.int8)
    s = chip((B_DEC, ROWS, H), jnp.float32)
    _compile(lambda q, k, v, i, ks, vs: pd.flash_decode(
        q, k, v, i, k_scale=ks, v_scale=vs, impl="pallas"),
        q, c, c, idx, s, s)


@pytest.mark.parametrize("variant", ["plain", "int8", "ring"])
def test_decode_paged(chip, variant):
    q, idx = chip((B_DEC, 1, H, D), BF), chip((B_DEC,), jnp.int32)
    pt = chip((B_DEC, ROWS // PAGE), jnp.int32)
    pool = chip((POOL, PAGE, H, D), jnp.int8 if variant == "int8" else BF)
    arrays, static = {}, {}
    if variant == "int8":
        scale = chip((POOL, PAGE, H), jnp.float32)
        arrays = {"k_scale": scale, "v_scale": scale}
    elif variant == "ring":
        arrays = {"slot_pos": chip((B_DEC, ROWS), jnp.int32)}
        static = dict(window=ROWS - 4, sinks=4)
    _compile(lambda q, k, v, pt, i, arrays: pd.flash_decode_paged(
        q, k, v, pt, i, impl="pallas", **arrays, **static),
        q, pool, pool, pt, idx, arrays)


def test_fused_adam(chip):
    f = chip((ADAM_N,), jnp.float32)
    _compile(lambda p, g, m, v: zf.fused_adam_update(
        p, g, m, v, jnp.int32(3), impl="pallas"), f, f, f, f)


@pytest.fixture
def scan_on_tpu(monkeypatch):
    """The selective scan's wrappers, steered to the compiled kernels;
    the traces made here are dropped after the test."""
    from fluxdistributed_tpu.ops import pallas_scan as ps

    monkeypatch.setattr(ps, "interpret_mode", lambda: False)
    yield ps
    ps._scan_fwd.clear_cache()
    ps._scan_bwd.clear_cache()


def test_selective_scan_at_the_cells_widths(chip, scan_on_tpu):
    """The `phi4_mini_flash` cell's Mamba scan as the step makes it: 2
    rows of 4,096 positions, d_inner 5,120, 16 states, float32; forward
    and the six gradients, under the names the trace reducer reads
    (`chipbench/metrics/scan_roofline_pct.py`).  The forward keeps one
    state of [16, 5,120] a chunk of 256: 1 / 256 of every position's; one
    load brings a loop step's 8 columns of `B` and of `C`."""
    ps = scan_on_tpu
    f32 = jnp.float32
    wide, narrow = chip((2, 4096, 5120), f32), chip((2, 4096, 16), f32)
    a, d = chip((5120, 16), f32), chip((5120,), f32)
    fn = jax.grad(lambda u, dt, a, b, c, d: ps.selective_scan(
        u, dt, a, b, c, d).sum(), argnums=range(6))
    text = _compile(fn, wide, wide, a, narrow, narrow, d)
    assert [x.shape for x in jax.eval_shape(fn, wide, wide, a, narrow, narrow, d)] \
        == [wide.shape, wide.shape, a.shape, narrow.shape, narrow.shape, d.shape]
    for name in ps.KERNEL_NAMES:
        assert f"%{name}" in text, name
    from fluxdistributed_tpu.obs import get_registry

    reg = get_registry()
    assert reg.value("fdtpu_scan_state_bytes", "kept") == 2 * 16 * 16 * 5120 * 4
    assert reg.value("fdtpu_scan_state_bytes", "all") == 2 * 4096 * 16 * 5120 * 4
    assert [reg.value("fdtpu_scan_tiles", dim) for dim in (
        "chunk", "channels_fwd", "channels_bwd", "columns_per_load")] \
        == [256, 512, 256, 8]


@pytest.mark.parametrize("window", [512, None], ids=["window", "causal"])
def test_differential_attention_calls_at_the_cells_widths(chip, as_on_tpu, window):
    """One of a `phi4_mini_flash` layer's two flash calls: 2 rows of
    4,096 positions, 20 query heads over 10 key heads of 64 and a value
    of 128 (a pair's two heads side by side), 512 x 512 blocks, a window
    of 512 or fully causal; the output and the value's gradient at 128."""
    q, k = chip((2, 4096, 20, 64), BF), chip((2, 4096, 10, 64), BF)
    v = chip((2, 4096, 10, 128), BF)
    fn = _grads(lambda q, k, v: pa.flash_attention(q, k, v, True, 512, 512, window))
    text = _compile(fn, q, k, v)
    assert [x.shape for x in jax.eval_shape(fn, q, k, v)] == [q.shape, k.shape, v.shape]
    assert jax.eval_shape(lambda q, k, v: pa.flash_attention(
        q, k, v, True, 512, 512, window), q, k, v).shape == (2, 4096, 20, 128)
    for name in pa.KERNEL_NAMES:
        assert f"%{name}" in text, name


@pytest.mark.parametrize("heads,window", [(72, 512), (48, None)],
                         ids=["sliding", "full"])
def test_laguna_attention_calls_at_the_cells_widths(chip, as_on_tpu, heads, window):
    """A `laguna_s21` layer's flash call: 2 rows of 4,096 positions, 72
    (sliding, a band of 512) or 48 (full, causal) query heads over 8
    key-value heads of 128, groups of 9 and 6, 512 x 512 blocks; `k`,
    `v` and their gradients stay at 8 heads."""
    q, kv = chip((2, 4096, heads, 128), BF), chip((2, 4096, 8, 128), BF)
    fn = _grads(lambda q, k, v: pa.flash_attention(q, k, v, True, 512, 512, window))
    text = _compile(fn, q, kv, kv)
    assert [x.shape for x in jax.eval_shape(fn, q, kv, kv)] == [q.shape, kv.shape, kv.shape]
    for name in pa.KERNEL_NAMES:
        assert f"%{name}" in text, name
