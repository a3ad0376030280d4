"""Declarative sharding-rules engine (parallel/rules.py).

The headline contracts:

* PINNED PLACEMENT — each committed rule table gives exactly the spec
  tree written out below for its probe model (dp/zero1 = replicated,
  fsdp = the one-rule shape walk, lm/vit tp = the Megatron tables,
  fsdp x tp = table + overlay), so an edit to a table that moves a
  single leaf's placement is seen — the AOT keys and the memory
  baseline depend on it.
* FALLBACK HONESTY — unmatched leaves replicate, but dead rules and
  large silently-replicating leaves are reported (and raise under
  strict=True).
* VALIDATION — unknown axes and indivisible shards are rejected
  eagerly, before any memory commits, with the offending rule/leaf
  named.
* END-TO-END — a ~10-line rule list shards a model through
  prepare_training with NO hand-written spec code, at loss parity
  with the unsharded step.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from fluxdistributed_tpu import mesh as mesh_lib, optim
from fluxdistributed_tpu.parallel import dp, rules


def _spec_leaves(tree):
    return jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None or isinstance(x, P))[0]


def assert_specs(tree, expected, ctx=""):
    """``tree`` holds exactly the leaves of ``expected`` ('/'-joined
    path -> PartitionSpec), each with exactly that spec."""
    got = {rules._leaf_path(kp): s for kp, s in _spec_leaves(tree)}
    assert sorted(got) == sorted(expected), (ctx, sorted(got))
    for path, spec in expected.items():
        assert got[path] == spec, (ctx, path, got[path], spec)


def _lm_params(**kw):
    from fluxdistributed_tpu.models.transformer_lm import TransformerLM

    model = TransformerLM(vocab=32, dim=16, depth=2, num_heads=4,
                          mlp_dim=32, **kw)
    return jax.eval_shape(
        lambda s: model.init(jax.random.PRNGKey(0), s, train=False),
        jax.ShapeDtypeStruct((1, 8), "int32"))["params"]


def _cnn_state():
    from fluxdistributed_tpu.models.simple import SimpleCNN

    model = SimpleCNN(num_classes=4, features=8)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8, 8, 3), np.float32),
                        train=True)["params"]
    return dp.TrainState.create(params, optim.adam(1e-3))


@pytest.fixture(scope="module")
def mesh24():
    return mesh_lib.make_mesh(
        {mesh_lib.DATA_AXIS: 2, mesh_lib.MODEL_AXIS: 4})


# ---------------------------------------------------------------- parity

def test_dp_table_is_replicated_everywhere():
    """The dp/zero1 placement as the EMPTY table: every leaf P()."""
    params = _lm_params()
    specs = rules.match_partition_rules(rules.dp_rules(), params)
    for pth, s in _spec_leaves(specs):
        assert s == P(), jax.tree_util.keystr(pth)


# What each table must give, as data: '/'-joined leaf path -> spec.
# M/D are the model and data axes of mesh24 (data=2 x model=4).
M, D = mesh_lib.MODEL_AXIS, mesh_lib.DATA_AXIS


def _blocks(per_block, rest, depth=2):
    out = {f"block{i}/{k}": v for i in range(depth)
           for k, v in per_block.items()}
    out.update(rest)
    return out


_LN = {f"LayerNorm_{i}/{k}": P() for i in (0, 1) for k in ("bias", "scale")}
_LM_ATTN = {
    "CausalSelfAttention_0/qkv/kernel": P(None, None, M, None),
    "CausalSelfAttention_0/qkv/bias": P(None, M, None),
    "CausalSelfAttention_0/out/kernel": P(M, None, None),
    "CausalSelfAttention_0/out/bias": P(),
}
_LM_GQA_ATTN = {
    "CausalSelfAttention_0/q/kernel": P(None, M, None),
    "CausalSelfAttention_0/q/bias": P(M, None),
    "CausalSelfAttention_0/kv/kernel": P(None, None, M, None),
    "CausalSelfAttention_0/kv/bias": P(None, M, None),
    "CausalSelfAttention_0/out/kernel": P(M, None, None),
    "CausalSelfAttention_0/out/bias": P(),
}
_GELU = {
    "Dense_0/kernel": P(None, M), "Dense_0/bias": P(M),
    "Dense_1/kernel": P(M, None), "Dense_1/bias": P(),
}
_SWIGLU = {
    "gate/kernel": P(None, M), "up/kernel": P(None, M),
    "down/kernel": P(M, None),
}
_LM_OUTER = {"embed/embedding": P(M, None),
             "final_ln/bias": P(), "final_ln/scale": P()}

LM_TP_EXPECTED = {
    "plain": ({}, _blocks({**_LM_ATTN, **_GELU, **_LN}, _LM_OUTER)),
    "gqa": ({"num_kv_heads": 2},
            _blocks({**_LM_GQA_ATTN, **_GELU, **_LN}, _LM_OUTER)),
    "swiglu": ({"mlp": "swiglu"},
               _blocks({**_LM_ATTN, **_SWIGLU, **_LN}, _LM_OUTER)),
    "untied": ({"tie_embeddings": False},
               _blocks({**_LM_ATTN, **_GELU, **_LN},
                       {**_LM_OUTER, "head/kernel": P(None, M),
                        "head/bias": P(M)})),
}

VIT_TP_EXPECTED = _blocks(
    {"MultiHeadAttention_0/qkv/kernel": P(None, None, M, None),
     "MultiHeadAttention_0/qkv/bias": P(None, M, None),
     "MultiHeadAttention_0/out/kernel": P(M, None, None),
     "MultiHeadAttention_0/out/bias": P(),
     "MlpBlock_0/Dense_0/kernel": P(None, M),
     "MlpBlock_0/Dense_0/bias": P(M),
     "MlpBlock_0/Dense_1/kernel": P(M, None),
     "MlpBlock_0/Dense_1/bias": P(),
     **_LN},
    {k: P() for k in (
        "final_norm/bias", "final_norm/scale", "head/bias", "head/kernel",
        "patch_embed/bias", "patch_embed/kernel", "pos_embed")})


def _cnn_state_expected(conv, dense):
    """SimpleCNN + adam: each param's spec, its two moments following
    it, the step replicated."""
    params = {"Conv_0/kernel": conv, "Conv_0/bias": P(),
              "Conv_1/kernel": conv, "Conv_1/bias": P(),
              "Dense_0/kernel": dense, "Dense_0/bias": P()}
    out = {f"params/{k}": v for k, v in params.items()}
    out.update({f"opt_state/{k}/{i}": v
                for k, v in params.items() for i in (0, 1)})
    out["step"] = P()
    return out


# the largest kernel here has 3*3*8*16 = 1,152 elements: under the
# default threshold every leaf stays whole, at 64 the kernels split
FSDP_STATE_EXPECTED = {
    rules.FALLBACK_MIN_SIZE: _cnn_state_expected(P(), P()),
    64: _cnn_state_expected(P(None, None, None, D), P(D, None)),
}

# the overlay keeps the table's entries and, at 64, gives the data axis
# the largest dim the table left whole; a leaf the overlay leaves alone
# keeps its table spec padded to its rank (P(None), not P())
_R1 = P(None)
_LN_R1 = {k: _R1 for k in _LN}
FSDP_TP_EXPECTED = {
    rules.FALLBACK_MIN_SIZE: _blocks(
        {**_LM_ATTN, **_GELU, **_LN_R1,
         "CausalSelfAttention_0/out/bias": _R1, "Dense_1/bias": _R1},
        {"embed/embedding": P(M, None),
         "final_ln/bias": _R1, "final_ln/scale": _R1}),
    64: _blocks(
        {"CausalSelfAttention_0/qkv/kernel": P(D, None, M, None),
         "CausalSelfAttention_0/qkv/bias": P(None, M, None),
         "CausalSelfAttention_0/out/kernel": P(M, None, D),
         "CausalSelfAttention_0/out/bias": _R1,
         "Dense_0/kernel": P(D, M), "Dense_0/bias": P(M),
         "Dense_1/kernel": P(M, D), "Dense_1/bias": _R1,
         **_LN_R1},
        {"embed/embedding": P(M, D),
         "final_ln/bias": _R1, "final_ln/scale": _R1}),
}


@pytest.mark.parametrize("variant", ["plain", "gqa", "swiglu", "untied"])
def test_lm_tp_table_gives_pinned_specs(variant, mesh24):
    kw, expected = LM_TP_EXPECTED[variant]
    table = rules.match_partition_rules(
        rules.lm_tp_rules_table(), _lm_params(**kw), mesh=mesh24)
    assert_specs(table, expected, variant)


def test_vit_tp_table_gives_pinned_specs(mesh24):
    from fluxdistributed_tpu.models.vit import ViT

    model = ViT(patch=4, depth=2, dim=16, num_heads=4, mlp_dim=32,
                num_classes=4)
    params = jax.eval_shape(
        lambda s: model.init(jax.random.PRNGKey(0), s, train=False),
        jax.ShapeDtypeStruct((1, 8, 8, 3), "float32"))["params"]
    table = rules.match_partition_rules(
        rules.vit_tp_rules_table(), params, mesh=mesh24)
    assert_specs(table, VIT_TP_EXPECTED, "vit")


@pytest.mark.parametrize("min_size", sorted(FSDP_STATE_EXPECTED))
def test_fsdp_table_gives_pinned_state_tree(min_size):
    """ONE ShardLargest rule places the FULL TrainState (params + Adam
    moments broadcast from their param; model_state/step replicated)."""
    state = _cnn_state()
    mesh = mesh_lib.data_mesh(8)
    p_specs = rules.match_partition_rules(
        rules.fsdp_rules(axis=D, min_size=min_size),
        state.params, mesh=mesh)
    derived = rules.train_state_specs(state, p_specs)
    assert_specs(derived, FSDP_STATE_EXPECTED[min_size], "fsdp")


@pytest.mark.parametrize("min_size", sorted(FSDP_TP_EXPECTED))
def test_fsdp_overlay_on_tp_table_gives_pinned_specs(min_size, mesh24):
    """rules table + with_fsdp: the 2-D composition, leaf for leaf."""
    params = _lm_params()
    base = rules.match_partition_rules(
        rules.lm_tp_rules_table(), params, mesh=mesh24)
    derived = rules.with_fsdp(base, params, mesh24, axis=D,
                              min_size=min_size)
    assert_specs(derived, FSDP_TP_EXPECTED[min_size], "fsdp_tp")


# ------------------------------------------------------- matcher semantics

def test_first_match_wins_and_scalars_replicate():
    params = {"block": {"qkv": {"kernel": np.zeros((8, 8))}},
              "scale": np.zeros(())}
    specs = rules.match_partition_rules(
        [(r"qkv/kernel$", P(None, mesh_lib.MODEL_AXIS)),
         (r"kernel$", P(mesh_lib.DATA_AXIS, None)),
         # scalars replicate before any rule is consulted
         (r"scale$", P(mesh_lib.DATA_AXIS))],
        params)
    assert specs["block"]["qkv"]["kernel"] == P(None, mesh_lib.MODEL_AXIS)
    assert specs["scale"] == P()


def test_fallback_report_and_strict():
    params = {"big": np.zeros((4096, 4)), "small": np.zeros((8,)),
              "hit": np.zeros((16, 16))}
    rep = rules.RuleReport({}, [], [], [])
    rules.match_partition_rules(
        [(r"hit$", P()), (r"matches_nothing$", P())], params,
        report=rep)
    assert rep.dead == ["matches_nothing$"]
    assert {p for p, _ in rep.unmatched} == {"big", "small"}
    assert [p for p, _ in rep.large_unmatched] == ["big"]
    with pytest.raises(ValueError, match="fell to replication"):
        rules.match_partition_rules(
            [(r"hit$", P())], params, strict=True)


def test_rule_report_never_needs_a_mesh():
    rep = rules.rule_report(rules.fsdp_rules(), {"w": np.zeros((64, 64))})
    assert rep.matched[r".*"] == ["w"] and rep.dead == []


# ------------------------------------------------------------- validation

def test_unknown_axis_rejected_eagerly(mesh24):
    with pytest.raises(ValueError, match="bogus.*not on the mesh"):
        rules.match_partition_rules(
            [(r".*", P("bogus"))], {"w": np.zeros((8, 8))}, mesh=mesh24)
    with pytest.raises(ValueError, match="not on the mesh"):
        rules.match_partition_rules(
            [(r".*", rules.ShardLargest("bogus"))],
            {"w": np.zeros((8, 8))}, mesh=mesh24)


def test_validate_specs_divisibility(mesh24):
    shapes = {"w": np.zeros((6, 8))}  # 6 % model(4) != 0
    specs = {"w": P(mesh_lib.MODEL_AXIS, None)}
    with pytest.raises(ValueError, match="not divisible"):
        rules.validate_specs(specs, shapes, mesh24, where="toy")
    # adam-style tuple state must not be mistaken for a shape literal
    shapes = {"w": (np.zeros((8, 8)), np.zeros((8, 8)))}
    specs = {"w": (P(mesh_lib.MODEL_AXIS, None),) * 2}
    rules.validate_specs(specs, shapes, mesh24, where="toy")


def test_bad_rule_value_type():
    with pytest.raises(TypeError, match="neither a PartitionSpec"):
        rules.match_partition_rules(
            [(r".*", "data")], {"w": np.zeros((8, 8))})


# ----------------------------------------------------------- end-to-end

def test_ten_line_table_trains_at_loss_parity():
    """The acceptance bar: a ~10-line rule list shards a model through
    prepare_training with NO hand-written spec code, at loss parity
    with the unsharded step (same math, different placement —
    allclose, not bitwise: GSPMD may order reductions differently)."""
    from fluxdistributed_tpu.data.synthetic import SyntheticDataset
    from fluxdistributed_tpu.models.simple import SimpleCNN
    from fluxdistributed_tpu.train.trainer import prepare_training

    model = SimpleCNN(num_classes=4, features=8)
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(8, 8, 3))

    def losses(**kw):
        task = prepare_training(model, ds, optim.adam(1e-3),
                                batch_size=16, cycles=3, seed=0, **kw)
        out = []
        state = task.state
        for batch in task.loader:
            state, metrics = task.step_fn(state, batch)
            out.append(float(metrics["loss"]))
        return out

    whole = losses(spmd="jit")
    derived = losses(layout="fsdp")  # the ONE-rule fsdp table
    assert np.allclose(whole, derived, rtol=2e-4, atol=2e-5), (
        whole, derived)


def test_layout_conflicts_rejected():
    from fluxdistributed_tpu.data.synthetic import SyntheticDataset
    from fluxdistributed_tpu.models.simple import SimpleCNN
    from fluxdistributed_tpu.train.trainer import prepare_training

    model = SimpleCNN(num_classes=4, features=8)
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(8, 8, 3))
    with pytest.raises(ValueError, match="cannot combine with spmd"):
        prepare_training(model, ds, optim.adam(1e-3), layout="fsdp",
                         spmd="shard_map", batch_size=16, cycles=1)
    with pytest.raises(ValueError, match="ZeRO-3 placement subsumes"):
        prepare_training(model, ds, optim.adam(1e-3), layout="fsdp",
                         zero1=True, batch_size=16, cycles=1)
    with pytest.raises(ValueError, match="divisible by the"):
        prepare_training(model, ds, optim.adam(1e-3), layout="dp_fsdp",
                         batch_size=12, cycles=1)


@pytest.mark.parametrize("name, spelling", [
    ("fsdp", 'layout="fsdp"'),
    ("tp", 'layout=Layout("tp", dp=D, tp=K)'),
    ("fsdp_tp", 'layout="fsdp_tp"'),
])
def test_retired_spmd_names_point_to_layout(name, spelling):
    """The three spmd= names that were a second spelling of a layout
    are refused, and the refusal says the layout= that replaces them."""
    from fluxdistributed_tpu.models.simple import SimpleCNN
    from fluxdistributed_tpu.train.trainer import prepare_training

    with pytest.raises(ValueError, match=re.escape(spelling)):
        prepare_training(SimpleCNN(num_classes=4), None, optim.adam(1e-3),
                         spmd=name)


def test_layout_over_device_subset_mesh():
    """A layout + mesh built over a device SUBSET resolves against the
    mesh's own device count, not the process's (review regression)."""
    import jax

    from fluxdistributed_tpu.data.synthetic import SyntheticDataset
    from fluxdistributed_tpu.models.simple import SimpleCNN
    from fluxdistributed_tpu.parallel.layout import Layout
    from fluxdistributed_tpu.train.trainer import prepare_training

    lay = Layout("dp_fsdp_4", dp=2, fsdp=2)
    mesh = lay.build_mesh(devs=jax.devices()[:4])
    model = SimpleCNN(num_classes=4, features=8)
    ds = SyntheticDataset(nsamples=64, nclasses=4, shape=(8, 8, 3))
    task = prepare_training(model, ds, optim.adam(1e-3), layout=lay,
                            mesh=mesh, batch_size=16, cycles=1)
    _, m = task.step_fn(task.state, next(iter(task.loader)))
    assert np.isfinite(float(m["loss"]))
