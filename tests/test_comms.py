"""Collective-traffic ledger (obs/comms.py): exact per-variant counts
on the 8-virtual-device CPU mesh, both ledger layers, and the rollup
helpers.

The headline assertion is the arXiv:2004.13336 signature on the REAL
registered paths: the explicit ZeRO-1 step moves its parameter traffic
as reduce-scatter + all-gather where the DP step moves all-reduce ONLY
— and the fused ZeRO-1 step does it in exactly ONE collective of each
kind (the PR-8 claim, now measured instead of asserted in prose).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from fluxdistributed_tpu.analysis.variants import build_variants
from fluxdistributed_tpu.obs.comms import (
    collective_signature,
    hlo_collectives,
    jaxpr_collectives,
    merge_entries,
    total_bytes,
)


def _by_key(entries):
    return {(e["kind"], tuple(e["axes"]) if e["axes"] else None):
            e["count"] for e in entries}


@pytest.fixture(scope="module")
def variants():
    """One build per variant name the module pins (builds trace
    nothing; the hlo tests compile their own few)."""
    names = ["dp", "dp_shardmap", "zero1_shardmap", "zero1_fused",
             "pp_1f1b", "context", "tp", "fsdp"]
    return {v.name: v for n in names for v in build_variants([n])}


# ---- jaxpr layer: explicit-collective schedules ---------------------------

def test_dp_shardmap_all_reduce_only(variants):
    """DP's semantic signature: gradient + loss traffic is all-reduce
    and NOTHING else — one pmean per param leaf (6) plus the loss."""
    v = variants["dp_shardmap"]
    entries = jaxpr_collectives(v.fn, v.args)
    assert _by_key(entries) == {("all_reduce", ("data",)): 7}


def test_zero1_shardmap_reduce_scatter_all_gather(variants):
    """THE ZeRO-1 signature (arXiv:2004.13336): parameter traffic is
    reduce-scatter (summed 1/N slice in) + all-gather (updated params
    out), one per param leaf; the only all-reduce left is the scalar
    loss.  Exact counts, exact axes, on the real prepare_training
    path."""
    v = variants["zero1_shardmap"]
    entries = jaxpr_collectives(v.fn, v.args)
    assert _by_key(entries) == {
        ("reduce_scatter", ("data",)): 6,
        ("all_gather", ("data",)): 6,
        ("all_reduce", ("data",)): 1,
    }
    # the parameter bytes ride the scatter/gather pair, not all-reduce:
    per_kind = {e["kind"]: e["bytes"] for e in entries}
    assert per_kind["reduce_scatter"] == per_kind["all_gather"]
    assert per_kind["all_reduce"] < per_kind["reduce_scatter"]


def test_zero1_fused_one_collective_each(variants):
    """The fused packed update's whole point, pinned: ONE
    reduce-scatter, ONE all-gather (the packed buffer), ONE all-reduce
    (the loss scalar) — not one per leaf."""
    v = variants["zero1_fused"]
    assert _by_key(jaxpr_collectives(v.fn, v.args)) == {
        ("reduce_scatter", ("data",)): 1,
        ("all_gather", ("data",)): 1,
        ("all_reduce", ("data",)): 1,
    }


def test_pp_1f1b_ppermute_signature(variants):
    """The pipeline's signature: activation/cotangent hops are
    ppermute on the pipe axis (scan-multiplied to the per-step count),
    plus the loss/grad psums on pipe and the DP mean on data."""
    v = variants["pp_1f1b"]
    assert _by_key(jaxpr_collectives(v.fn, v.args)) == {
        ("ppermute", ("pipe",)): 20,
        ("all_reduce", ("pipe",)): 4,
        ("all_reduce", ("data",)): 16,
    }


def test_context_ring_signature(variants):
    """Ring attention rotates KV shards with ppermute on the seq axis
    — the context-parallel signature, and the ONLY explicit collective:
    q/k/v enter the shard_map sharded on every mesh axis, so its
    transpose has nothing to reduce (the gradient mean over the batch
    is GSPMD's, outside the jaxpr)."""
    v = variants["context"]
    sig = _by_key(jaxpr_collectives(v.fn, v.args))
    assert sig == {("ppermute", ("seq",)): 16}


# ---- HLO layer: GSPMD-inserted collectives --------------------------------

def test_dp_gspmd_hlo_all_reduce_only(variants):
    """The GSPMD dp step's jaxpr carries NO collectives (XLA inserts
    them) — the compiled-HLO layer sees them, attributed to the data
    axis via replica_groups.  XLA's all-reduce combiner folds the 7
    reductions the shard_map twin writes (6 leaves + the loss) into ONE
    tuple all-reduce carrying the same bytes."""
    v = variants["dp"]
    assert jaxpr_collectives(v.fn, v.args) == []
    compiled = v.fn.lower(*v.args).compile()
    (entry,) = hlo_collectives(compiled, mesh=v.mesh)
    assert (entry["kind"], entry["axes"], entry["count"]) == (
        "all_reduce", ["data"], 1)
    # the combined op still moves every gradient leaf + the loss
    twin = variants["dp_shardmap"]
    assert entry["bytes"] == total_bytes(
        jaxpr_collectives(twin.fn, twin.args))


def test_tp_hlo_axes_attribution(variants):
    """Tensor parallelism's signature: activation reductions on the
    model axis next to the gradient mean on data — the replica_groups
    → mesh-axis matcher must untangle BOTH axis communicators of the
    2x4 mesh (including XLA's iota/transposed group spellings)."""
    v = variants["tp"]
    compiled = v.fn.lower(*v.args).compile()
    sig = _by_key(hlo_collectives(compiled, mesh=v.mesh))
    assert sig == {("all_reduce", ("model",)): 8,
                   ("all_reduce", ("data",)): 1}


def test_layout_hlo_signatures():
    """The rule-derived 3-D layouts' compiled signatures, pinned like
    dp/zero1: the 2-D dp x fsdp image layout moves its gradient mean
    over the JOINT (data, fsdp) communicator (the batch shards over
    both, so the mean is one all-reduce spanning both axes); the
    tp-composed LM layouts split activation reductions onto the model
    axis next to the batch-communicator gradient mean (combined into
    one tuple all-reduce, as in dp) — the same structure as the
    hand-built tp variant's (1 data + 8 model) with the batch
    communicator renamed to the layout's axes.  The
    replica_groups matcher must untangle the multi-axis groups of the
    3-D mesh, including the joint (data, fsdp) combination."""
    cases = {
        "layout_dp_fsdp": {("all_reduce", ("data", "fsdp")): 1},
        "layout_fsdp_tp": {("all_reduce", ("fsdp",)): 1,
                           ("all_reduce", ("model",)): 8},
        "layout_dp_fsdp_tp": {("all_reduce", ("data", "fsdp")): 1,
                              ("all_reduce", ("model",)): 8},
    }
    for name, want in cases.items():
        (v,) = build_variants([name])
        # GSPMD variant: the jaxpr carries no collectives, the
        # compiled HLO carries the derived schedule
        assert jaxpr_collectives(v.fn, v.args) == []
        compiled = v.fn.lower(*v.args).compile()
        assert _by_key(hlo_collectives(compiled, mesh=v.mesh)) == want, name


def test_fsdp_hlo_signature(variants):
    """fsdp's compiled signature pinned as XLA emits it HERE: on this
    CPU build the tiny model's gather/scatter pairs fold into plain
    all-reduces (sharding propagation re-replicates small params),
    combined into one — the pinned count is the regression tripwire; a
    future XLA emitting all-gather+reduce-scatter instead is a
    deliberate baseline update."""
    v = variants["fsdp"]
    compiled = v.fn.lower(*v.args).compile()
    assert _by_key(hlo_collectives(compiled, mesh=v.mesh)) == {
        ("all_reduce", ("fsdp",)): 1}


# ---- counting semantics ---------------------------------------------------

def test_scan_multiplies_and_cond_takes_max():
    def body_fn(x):
        def one(c, _):
            return jax.lax.ppermute(c, "data", [(0, 1), (1, 0)]), None

        out, _ = jax.lax.scan(one, x, None, length=5)
        return out

    from fluxdistributed_tpu import mesh as mesh_lib

    m = mesh_lib.data_mesh(2)
    f = jax.jit(jax.shard_map(
        body_fn, mesh=m,
        in_specs=jax.sharding.PartitionSpec("data"),
        out_specs=jax.sharding.PartitionSpec("data")))
    entries = jaxpr_collectives(f, (jnp.zeros((2, 4)),))
    # renamed axis inside shard_map is 'data'; scan body runs 5x
    assert _by_key(entries) == {("ppermute", ("data",)): 5}

    def cond_fn(x, flag):
        return jax.lax.cond(
            flag > 0,
            lambda c: jax.lax.psum(c, "data"),
            lambda c: jax.lax.psum(c * 2, "data"),
            x)

    g = jax.jit(jax.shard_map(
        cond_fn, mesh=m,
        in_specs=(jax.sharding.PartitionSpec("data"),
                  jax.sharding.PartitionSpec()),
        out_specs=jax.sharding.PartitionSpec("data")))
    entries = jaxpr_collectives(g, (jnp.zeros((2, 4)),
                                    jnp.zeros((), jnp.int32)))
    # ONE branch runs per invocation: merged at max, not summed to 2
    assert _by_key(entries) == {("all_reduce", ("data",)): 1}


def test_bytes_accounting():
    from fluxdistributed_tpu import mesh as mesh_lib

    m = mesh_lib.data_mesh(8)

    def fn(x):
        return jax.lax.psum(x, "data")

    f = jax.jit(jax.shard_map(
        fn, mesh=m, in_specs=jax.sharding.PartitionSpec(),
        out_specs=jax.sharding.PartitionSpec()))
    x = jnp.zeros((4, 8), jnp.float32)
    (entry,) = jaxpr_collectives(f, (x,))
    assert entry["bytes"] == entry["bytes_per_call"] == 4 * 8 * 4
    assert total_bytes([entry]) == 128


# ---- rollups --------------------------------------------------------------

def test_signature_and_merge():
    a = [{"kind": "all_reduce", "axes": ["data"], "count": 2,
          "bytes": 100, "bytes_per_call": 60}]
    b = [{"kind": "all_reduce", "axes": ["data"], "count": 3,
          "bytes": 50, "bytes_per_call": 50},
         {"kind": "ppermute", "axes": None, "count": 1,
          "bytes": 10, "bytes_per_call": 10}]
    merged = merge_entries(a, b)
    assert _by_key(merged) == {("all_reduce", ("data",)): 5,
                               ("ppermute", None): 1}
    assert collective_signature(merged) == {"all_reduce": 5,
                                            "ppermute": 1}
    ar = next(e for e in merged if e["kind"] == "all_reduce")
    assert ar["bytes"] == 150 and ar["bytes_per_call"] == 60
