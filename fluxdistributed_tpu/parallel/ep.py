"""Expert layers (MoE).  Two paths live here; they share nothing but
the name, and a new model takes the second.

**1. ``moe_apply``: capacity and one-hots, experts sharded over an
``expert`` mesh axis** (the Mesh-TF/Switch lineage; net-new scope beyond
the reference, SURVEY §2: "EP: NO").  Top-1 (Switch) or top-k
(GShard-style) softmax routing; tokens are sharded over the same
``expert`` axis, ``router_dispatch`` builds ``(tokens, experts,
capacity)`` one-hots locally, and two ``all_to_all`` collectives move
token activations to their expert's device and back: dense einsums and
static shapes throughout.  ``TransformerLM``'s ``MoEDecoderBlock`` and
``spmd="ep"`` use it.  It fits few experts and short batches: the one-hot
grows with tokens x experts x capacity, and what overflows is dropped.

* ``top_k=1`` (Switch): one expert per token, output scaled by the
  router probability; ``top_k>1`` (GShard lineage): k experts per
  token, later choices queue after earlier ones in each expert's
  capacity, gates normalized to sum to 1;
* per-shard expert capacity ``C = ceil(tokens_per_shard / E *
  capacity_factor * top_k)``; tokens over capacity are DROPPED (output
  zero for that choice), the documented switch behavior;
* auxiliary load-balance loss ``E * Σ_e f_e · p_e`` (first-choice
  fraction routed × mean router prob), returned for the caller to add
  to the task loss.

**2. ``sigmoid_route`` and ``held_experts_apply``: no capacity, no
drops, a grouped matrix product** (``models/glm4_moe_lite.py`` uses it;
the path for a model with many experts).  The router scores every token
over all experts in float32 and balances by a selection bias, not by a
loss term.  The layer is told which experts it holds (``first``, and the
leading size of its weights): the token-slots are sorted by expert, the
held experts' slots come first, one grouped product a projection runs
over the sorted rows, and the weighted results are gathered back to
their tokens.  On a TPU the product and its two gradients are the
Pallas kernels of ``ops/pallas_gmm.py`` (``fdtpu_gmm`` in a trace),
which visit the live row tiles only, on tiles derived from the call's
own shapes: 512 rows, and the contraction whole in VMEM wherever it
fits, so that a group's weights are read once and not once a row tile.
XLA's own kernel under ``jax.lax.ragged_dot`` (``ragged-dot-none``)
takes 512 x 512 x 256 by divisibility alone and is bound by HBM at the
expert models' widths (PERF.md §6, PR 36); ``ragged_dot`` stays as the
plain path off the TPU, which the CPU tests compare the kernels and the
layer against.  The buffer is as long as the step's
held rows need: ``compact_rows`` is a short ladder of lengths, multiples
of the held experts' even share of the token-slots and the last a row
for every slot, and a ``lax.switch`` on the step's own load takes the
first rung that holds the held slots (everything around the products
runs over the buffer's length, live or not, so a shorter buffer is a
cheaper step; the products visit live tiles only).  Nothing is dropped:
every rung computes the same rows in the same order, and a step too
large for every rung takes the whole buffer; where the first rung is
already every slot (most experts held) that is the only path and there
is no branch.  What the absent experts would add is left out.  The
trainer counts the path a layer took in
``fdtpu_moe_compact_total{path}`` and the rows it took beside the rows
that were live in ``fdtpu_moe_buffer_rows_total{kind}``.  On one chip
the layer runs without an exchange; the exchange between chips that hold
different experts is not built yet.
"""

from __future__ import annotations

import math
from fractions import Fraction
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Pytree = Any

__all__ = [
    "compact_rows",
    "moe_apply",
    "router_dispatch",
    "router_dispatch_expert_choice",
    "stack_expert_params",
    "sigmoid_route",
    "held_experts_apply",
]

# sourced from the device layer's single declaration (lint rule FDT105:
# a re-declared literal drifts silently on rename); re-exported here for
# the callers that import it from the ep module
from ..mesh import EXPERT_AXIS
from ..ops import pallas_attention, pallas_gmm


def stack_expert_params(per_expert: list, mesh: Mesh, axis: str = EXPERT_AXIS) -> Pytree:
    """Stack E per-expert param trees on a leading dim sharded over
    ``axis`` — expert ``g`` lives on device ``g // (E // axis_size)``
    (contiguous blocks of local experts per device)."""
    from ..sharding import stack_on_axis

    return stack_on_axis(per_expert, mesh, axis)


def router_dispatch(
    logits: jnp.ndarray, capacity: int, k: int = 1, normalize: Optional[bool] = None
):
    """Top-``k`` dispatch/combine tensors from router logits.

    ``logits``: (T, E).  Returns ``dispatch`` (T, E, C) {0,1},
    ``combine`` (T, E, C) = dispatch · gate, and the load-balance
    auxiliary loss.  Pure jnp — used identically inside the sharded
    program and by the single-device golden model in tests.

    ``k=1`` is Switch routing (gate = router prob); ``k>1`` is
    GShard-style top-k, where later choices queue after earlier ones in
    each expert's capacity and gates are normalized to sum to 1 across
    the chosen experts (``normalize`` overrides; default ``k > 1``).
    The aux loss always uses first-choice assignment (Switch def.).
    """
    t, e = logits.shape
    dtype = logits.dtype
    if not 1 <= k <= e:
        # past round E the masked probs are all-zero and argmax would
        # silently re-route every token to expert 0
        raise ValueError(f"top-k ({k}) must be in [1, experts ({e})]")
    if normalize is None:
        normalize = k > 1
    # routing math in f32 regardless of compute dtype: a bf16 cumsum
    # saturates at 256, collapsing every later queue position onto slot
    # 255 (silent dispatch corruption for large expert queues)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    masked = probs
    counts = jnp.zeros((e,), jnp.float32)  # queue fill from earlier rounds
    ds, gates = [], []
    first_oh = None
    for _ in range(k):
        expert_idx = jnp.argmax(masked, axis=-1)  # (T,)
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (T, E)
        if first_oh is None:
            first_oh = onehot
        # position of each token in its expert's queue (0-based), offset
        # by tokens already queued there in earlier rounds
        pos = (jnp.cumsum(onehot, axis=0) + counts[None, :]) * onehot - 1.0
        kept = (pos >= 0) & (pos < capacity)
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), capacity, dtype=jnp.float32)
        ds.append(pos_oh * kept.astype(jnp.float32)[..., None])
        gates.append(jnp.max(probs * onehot, axis=-1))  # (T,) routed prob, f32
        counts = counts + onehot.sum(axis=0)
        masked = masked * (1.0 - onehot)
    if normalize:
        gsum = sum(gates) + 1e-9
        gates = [g / gsum for g in gates]
    dispatch = sum(ds).astype(dtype)
    combine = sum(
        d * g[:, None, None] for d, g in zip(ds, gates)
    ).astype(dtype)
    # load-balance aux: E * Σ_e (fraction of tokens to e) · (mean prob of e)
    frac = first_oh.mean(axis=0)
    mean_p = probs.mean(axis=0)
    aux = e * jnp.sum(frac * mean_p)
    return dispatch, combine, aux


def router_dispatch_expert_choice(logits: jnp.ndarray, capacity: int):
    """Expert-choice dispatch/combine (Zhou et al. 2022): each EXPERT
    picks its top-``capacity`` tokens by router probability, instead of
    tokens picking experts.

    Load balance is perfect by construction (every expert processes
    exactly ``capacity`` token slots), so the aux loss is 0; tokens may
    be processed by several experts or none.  Returns the same
    ``(dispatch (T,E,C), combine, aux)`` contract as ``router_dispatch``.
    """
    t, e = logits.shape
    dtype = logits.dtype
    if capacity > t:
        raise ValueError(
            f"expert-choice capacity ({capacity}) cannot exceed tokens per shard ({t})"
        )
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    _, idx = jax.lax.top_k(probs.T, capacity)  # (E, C) token ids per expert
    dispatch_f32 = jax.nn.one_hot(idx, t, dtype=jnp.float32).transpose(2, 0, 1)
    combine = (dispatch_f32 * probs[:, :, None]).astype(dtype)
    return dispatch_f32.astype(dtype), combine, jnp.zeros((), jnp.float32)


def moe_apply(
    expert_fn: Callable,
    mesh: Mesh,
    axis: str = EXPERT_AXIS,
    capacity_factor: float = 1.25,
    capacity: Optional[int] = None,
    top_k: int = 1,
    routing: str = "token",
    batch_axis: Optional[str] = None,
    pad_tokens: bool = False,
):
    """Build ``fn(stacked_params, router_w, x) -> (y, aux)``.

    ``x``: (T, D) tokens sharded on ``axis``; ``router_w``: (D, E)
    replicated; ``stacked_params`` leaves (E, ...) sharded on ``axis``.
    E must be a multiple of the ``axis`` size: each device hosts
    ``E // axis_size`` experts (expert ``g`` lives on device ``g // L``,
    matching ``stack_expert_params``'s contiguous sharding).  Output is
    token-sharded like ``x``; ``aux`` is the replicated (pmean-ed)
    load-balance loss.  ``routing`` selects token-choice (``"token"``,
    with ``top_k`` = 1 Switch / >1 GShard-style) or expert-choice
    (``"expert_choice"``: each expert takes its top-C tokens; perfectly
    balanced, aux = 0).

    ``batch_axis`` composes data parallelism on a ``(data, expert)``
    mesh: the token dim is sharded over BOTH axes, each data row routes
    its own tokens among that row's expert shards (expert weights are
    replicated across rows; their gradient all-reduce over ``data`` is
    AD's transpose of that replication), and the dispatch ``all_to_all``
    stays within the row.
    """
    if routing not in ("token", "expert_choice"):
        raise ValueError(f"unknown routing {routing!r}")
    if routing == "expert_choice" and top_k != 1:
        raise ValueError("top_k applies to token-choice routing only")
    if pad_tokens and routing == "expert_choice":
        # pad tokens get uniform router prob 1/E and would displace real
        # tokens from each expert's top-capacity pick
        raise ValueError("pad_tokens is incompatible with expert_choice routing")
    if pad_tokens and capacity is None:
        raise ValueError(
            "pad_tokens=True needs an explicit capacity: the auto capacity "
            "ceil(T/E * factor) is ~1 for tiny decode steps and pad tokens "
            "consume slots — size it for the real token count plus headroom"
        )
    e_devices = mesh.shape[axis]
    tok_spec = P((batch_axis, axis)) if batch_axis else P(axis)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), tok_spec),
        out_specs=(tok_spec, P()),
    )
    def run(stacked_params, router_w, x):
        t, d = x.shape
        e = router_w.shape[-1]
        s = e_devices  # shards on the expert axis
        assert e % s == 0, (
            f"experts ({e}) must be a multiple of '{axis}' size ({s})"
        )
        loc = e // s  # experts hosted per device
        if capacity is not None:
            if capacity < 1:
                raise ValueError(f"capacity must be >= 1, got {capacity}")
            cap = capacity
        else:
            cap = max(1, math.ceil(t / e * capacity_factor * top_k))
        logits = x @ router_w
        if routing == "expert_choice":
            dispatch, combine, aux = router_dispatch_expert_choice(logits, cap)
        else:
            dispatch, combine, aux = router_dispatch(logits, cap, k=top_k)
        # (T,D),(T,E,C) → (E,C,D): each expert's queue from this shard
        expert_in = jnp.einsum("td,tec->ecd", x, dispatch)
        # exchange: device q receives every shard's queues for its LOC
        # local experts (global expert g = q·LOC + l)
        expert_in = jax.lax.all_to_all(
            expert_in.reshape(s, loc, cap, d), axis,
            split_axis=0, concat_axis=0, tiled=False,
        )  # (S_src, LOC, C, D)
        # per local expert: tokens from all shards, one batched apply
        xin = expert_in.transpose(1, 0, 2, 3).reshape(loc, s * cap, d)
        y = jax.vmap(expert_fn)(stacked_params, xin)  # leaves (LOC, ...)
        y = y.reshape(loc, s, cap, d).transpose(1, 0, 2, 3)  # (S, LOC, C, D)
        # route results back to the token-owning shards
        y = jax.lax.all_to_all(y, axis, split_axis=0, concat_axis=0, tiled=False)
        out = jnp.einsum("ecd,tec->td", y.reshape(e, cap, d), combine)
        aux = jax.lax.pmean(aux, axis)
        if batch_axis:
            aux = jax.lax.pmean(aux, batch_axis)
        return out, aux

    n_shards = e_devices * (mesh.shape[batch_axis] if batch_axis else 1)

    def fn(stacked_params, router_w, x):
        t = x.shape[0]
        pad = (-t) % n_shards
        if pad and not pad_tokens:
            raise ValueError(
                f"token count {t} is not divisible by the mesh's {n_shards} "
                "shards. For training this usually means a batch/mesh "
                "misconfiguration; for small decode steps build the moe_fn "
                "with pad_tokens=True and an explicit capacity"
            )
        if pad:
            # zero tokens route like any other (uniform router prob) and
            # occupy capacity slots + appear in the aux statistics — the
            # explicit-capacity requirement above keeps real tokens safe
            x = jnp.concatenate(
                [x, jnp.zeros((pad, x.shape[1]), x.dtype)], axis=0
            )
        y, aux = run(stacked_params, router_w, x)
        return (y[:t] if pad else y), aux

    return fn


# -- path 2: routing without drops, a grouped product over sorted rows ------

def sigmoid_route(x, router_w, bias, *, top_k: int, scale: float = 1.0,
                  normalize: bool = True):
    """Score ``x`` (N, D) over all ``E`` experts and choose ``top_k``.

    ``scores = sigmoid(x @ router_w)`` in float32 at full precision (on
    a TPU a float32 product otherwise runs in bfloat16 passes, and a
    score's rounding moves the choice).  The chosen experts are the
    largest of ``scores + bias``; ``bias`` (E,) only steers the choice
    and carries no gradient.  The weights are the chosen experts'
    ``scores`` (without the bias), over their sum if ``normalize``,
    times ``scale``.  Returns ``(chosen (N, k) int32, weights (N, k)
    f32, load (E,) f32)``, ``load`` the count of token-slots an expert.
    """
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, chosen = jax.lax.top_k(
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalize:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    e = router_w.shape[-1]
    # a compare-and-count over (slots, E): fused, never stored, and no
    # scatter (which a TPU serialises)
    load = jnp.sum(chosen.reshape(-1, 1) == jnp.arange(e, dtype=chosen.dtype),
                   axis=0, dtype=jnp.int32)
    return chosen, w * scale, load.astype(jnp.float32)


#: the sorted buffer's lengths over the held slots expected of an even
#: router, ascending: the ladder's rungs below a row for every slot.
#: Each rung is one more compiled copy of the layer, forward and
#: backward, so there are four.  Fixed by chip runs (PERF.md §6, PR 28
#: and 34): a router that stays even holds 1.0-1.1 times the even share
#: in every step (the first rung); training one chip's share of the
#: experts against a drifting router sends them from the even share to
#: 1.5-2 times it within 60 steps (the middle rungs), single steps to
#: 2.5 (the last, where PR 28's two lengths took every slot).
COMPACT_OVER_EXPECTED = (Fraction(9, 8), Fraction(3, 2), Fraction(2),
                         Fraction(3))
_COMPACT_TILE = pallas_gmm.ROW_TILE  # a rung is a whole number of row tiles


def compact_rows(slots, held: int, experts: int) -> tuple:
    """The lengths the sorted buffer may take for ``slots`` token-slots
    routed over ``experts`` of which ``held`` live here, ascending: each
    of ``COMPACT_OVER_EXPECTED`` times the expected share, up to the
    next tile, and last a row for every slot; a rung at or over the
    slots is left out, so where most experts are held the ladder is
    ``(slots,)``.  Of shapes alone, so the trace decides; ``slots`` may
    also be an array of whole numbers (a step's loads, for the counters
    of the rung taken): the rungs are then arrays, none left out and
    none over the slots."""
    rungs = [-(-over.numerator * slots * held
               // (over.denominator * experts * _COMPACT_TILE)) * _COMPACT_TILE
             for over in COMPACT_OVER_EXPECTED]
    if isinstance(slots, int):
        return (*sorted({r for r in rungs if r < slots}), slots)
    return (*(jnp.minimum(r, slots) for r in rungs), slots)


def _sum_rows(rows, at, scale=None):
    """``out[n] = sum_j scale[n, j] * rows[at[n, j]]`` in float32, the
    choices ``j`` in order; an ``at`` behind the last row adds nought.
    One gather of N rows a choice: no (N*k, D) array is made."""
    total = 0.0
    for j in range(at.shape[-1]):
        row = jnp.take(rows, at[:, j], axis=0, mode="fill",
                       fill_value=0).astype(jnp.float32)
        total = total + (row if scale is None else row * scale[:, j:j + 1])
    return total


@jax.custom_vjp
def _to_sorted(x, order, inverse):
    """Row ``r`` of the result is the row of ``x`` (N, D) of the token
    that slot ``order[r]`` belongs to, for the R rows of the buffer
    (``order`` holds the first R slots of the sorted order, ``inverse``
    (N, k) every slot's row).  The transpose is a gather too: a token's
    cotangent is the sum over its slots' rows, nought for a slot whose
    row is behind the buffer."""
    return jnp.take(x, order // inverse.shape[-1], axis=0)


def _to_sorted_fwd(x, order, inverse):
    return _to_sorted(x, order, inverse), inverse


def _to_sorted_bwd(inverse, g):
    return (_sum_rows(g, inverse).astype(g.dtype), None, None)


_to_sorted.defvjp(_to_sorted_fwd, _to_sorted_bwd)


@jax.custom_vjp
def _from_sorted(y, weights, order, inverse):
    """Each token's weighted sum over its slots' rows of ``y`` (R, D):
    ``sum_j weights[n, j] * y[inverse[n, j]]`` in float32, a slot behind
    the buffer adding nought.  The transpose stays among the R rows."""
    return _sum_rows(y, inverse, weights.astype(jnp.float32)).astype(y.dtype)


def _from_sorted_fwd(y, weights, order, inverse):
    return _from_sorted(y, weights, order, inverse), (y, weights, order, inverse)


def _from_sorted_bwd(res, g):
    y, weights, order, inverse = res
    g_rows = jnp.take(g, order // inverse.shape[-1], axis=0).astype(jnp.float32)
    w_rows = jnp.take(weights.reshape(-1), order).astype(jnp.float32)
    d_rows = jnp.sum(y.astype(jnp.float32) * g_rows, axis=-1)
    d_weights = jnp.take(d_rows, inverse, mode="fill", fill_value=0)
    return ((g_rows * w_rows[:, None]).astype(y.dtype),
            d_weights.astype(weights.dtype), None, None)


_from_sorted.defvjp(_from_sorted_fwd, _from_sorted_bwd)


def _sorted_experts(rows, x, weights, w_gate, w_up, w_down, order, inverse,
                    sizes, walk):
    """The layer over the first ``rows`` rows of the sorted order, which
    must hold every held slot (``sum(sizes) <= rows``).  ``walk`` is
    :func:`_walk`'s: the kernels' walk over the groups, or None for the
    plain path."""
    order = order[:rows]
    live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
    xs = jnp.where(live, _to_sorted(x, order, inverse), 0)

    def grouped(a, w):
        """``a``'s rows times their group's ``w``.  ``ragged_dot`` is
        kept as the plain path: what runs off the TPU (and where the
        kernels' tiles do not divide the shapes), and what the CPU
        tests hold the kernels and this layer to."""
        if walk is not None:
            return pallas_gmm.grouped_dot(a, w.astype(a.dtype), walk)
        return jax.lax.ragged_dot(
            a, w.astype(a.dtype), sizes,
            preferred_element_type=a.dtype)

    # a row behind the last group is whatever the kernel left there
    h = jnp.where(live, jax.nn.silu(grouped(xs, w_gate)) * grouped(xs, w_up), 0)
    y = jnp.where(live, grouped(h, w_down), 0)
    return _from_sorted(y, weights, order, inverse)


def _walk(sizes, slots, w_gate):
    """The grouped-product kernels' walk over the groups (one for every
    rung: ``pallas_gmm.group_metadata`` over all ``slots``), where the
    backend is a TPU and the kernels' tiles divide the layer's shapes;
    else None, and the products are ``ragged_dot``'s."""
    if (pallas_attention.interpret_mode()
            or not pallas_gmm.tileable(slots, *w_gate.shape[1:])):
        return None
    return pallas_gmm.group_metadata(sizes, slots)


def _fits(ladder, sizes, path):
    """``path(rows)`` for the first rung of ``ladder`` that holds the
    step's held slots; the last is a row for every slot, so nothing is
    dropped."""
    rung = jnp.sum(jnp.sum(sizes) > jnp.asarray(ladder[:-1]), dtype=jnp.int32)
    return jax.lax.switch(rung, [partial(path, rows) for rows in ladder])


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bounded_experts(ladder, x, weights, w_gate, w_up, w_down, order, inverse,
                     sizes, walk):
    """:func:`_sorted_experts` over the first rung of ``ladder`` that
    the step fits.  A ``switch``'s own transpose keeps the union of all
    branches' residuals and writes noughts for the branches not taken,
    the whole buffer's in every step; so what crosses from forward to
    backward is the arguments alone, the same in every branch, and the
    backward chooses again and recomputes its branch's products (under
    ``jax.checkpoint`` the forward's then fall away)."""
    return _rung_forward(ladder, x, weights, w_gate, w_up, w_down, order,
                         inverse, sizes, walk)


# jitted, so that a model's expert layers, whose shapes are the same, are
# traced and lowered once a direction and not once a layer with every rung
# again: in the step, and in an un-jitted ``model.init``, where each layer's
# ``switch`` would else be lowered and loaded as a program of its own
@partial(jax.jit, static_argnums=(0,))
def _rung_forward(ladder, x, weights, w_gate, w_up, w_down, order, inverse,
                  sizes, walk):
    return _fits(ladder, sizes, lambda rows: _sorted_experts(
        rows, x, weights, w_gate, w_up, w_down, order, inverse, sizes, walk))


@partial(jax.jit, static_argnums=(0,))
def _rung_backward(ladder, args, g):
    *diff, order, inverse, sizes, walk = args
    return _fits(ladder, sizes, lambda rows: jax.vjp(
        lambda *a: _sorted_experts(rows, *a, order, inverse, sizes, walk),
        *diff)[1](g))


def _bounded_fwd(ladder, *args):
    return _bounded_experts(ladder, *args), args


def _bounded_bwd(ladder, args, g):
    return (*_rung_backward(ladder, args, g), None, None, None, None)


_bounded_experts.defvjp(_bounded_fwd, _bounded_bwd)


def held_experts_apply(x, chosen, weights, w_gate, w_up, w_down, experts, *,
                       first: int = 0):
    """What the experts held here add to each token: ``sum_i g_i E_i(x)``
    over the chosen experts ``i`` in ``[first, first + held)``, ``E(x) =
    (silu(x W_gate) * x W_up) W_down``.

    ``x`` (N, D); ``chosen``, ``weights`` (N, k) from
    :func:`sigmoid_route` over ``experts`` experts; ``w_gate``, ``w_up``
    (held, D, M) and ``w_down`` (held, M, D).  The N*k token-slots are
    sorted by expert, the held experts' first and the absent experts'
    behind them; the three grouped products run over the first rows of
    that order with the held experts' group sizes, as many as the first
    rung of :func:`compact_rows` that holds the step's held slots, and a
    slot behind them adds nought to its token without a row being
    touched for it.  Nothing is dropped: a step whose held slots
    overflow every rung takes a buffer with a row for every slot, and
    where that is the only rung (most experts held) there is no branch.
    """
    n, k, held = x.shape[0], chosen.shape[-1], w_gate.shape[0]
    local = chosen.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)
    inverse = jnp.argsort(order).reshape(n, k)
    sizes = jnp.sum(key[:, None] == jnp.arange(held, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    ladder = compact_rows(n * k, held, experts)
    args = (x, weights, w_gate, w_up, w_down, order, inverse, sizes,
            _walk(sizes, n * k, w_gate))
    if len(ladder) == 1:
        return _sorted_experts(n * k, *args)
    return _bounded_experts(ladder, *args)
