"""Hand-scheduled 1F1B pipeline parallelism.

The GPipe schedule in ``parallel.pp`` derives its backward pass from AD:
differentiate through the forward ``lax.scan`` and the reverse pipeline
falls out.  Elegant — but the scan transpose stores residuals for every
tick, so activation memory grows with the microbatch count M.  That is
GPipe's textbook pathology, and it is measurable: on the benchmark mesh,
per-tick cost inflates >2x from M=S to M=8S as the stashed residuals
grow (benchmarks/pp_bubble.py, docs/parallelism.md).

This module hand-writes the 1F1B (one-forward-one-backward) schedule
instead, the way Megatron-LM runs its pipelines — but TPU-idiomatic:
the whole schedule (all forwards, all backwards, gradient accumulation)
is ONE ``lax.scan`` over lockstep ticks inside ONE ``shard_map``, with
neighbor transfers as ``ppermute`` collectives.  Per tick each pipe
device performs one stage-forward, one stage-backward, or idles,
according to a STATIC schedule table computed in Python at trace time
(S and M are static, so the whole timetable is).  Nothing here is
data-dependent control flow: per-device divergence is a ``lax.cond`` on
a device-varying flag read from the table.

Memory property (the point of 1F1B): a device stashes at most
``min(S, M)`` in-flight microbatch INPUTS — a fixed-size ring buffer —
instead of the O(M·ticks) residuals of AD-through-scan.  Backward ticks
recompute the stage forward under ``jax.vjp`` from the stored input
(same recompute trade as ``pipeline_apply(remat=True)``, which is how
Megatron runs production pipelines too: activation recompute +
schedule).  Net: activation memory O(S), not O(M), so M — and with it
the (S-1)/(M+S-1) bubble — can grow freely.

Because forward and backward interleave *within* the schedule, the loss
must be computable per-microbatch inside the pipeline: the caller
provides ``embed_fn`` (applied at stage 0, e.g. token embedding) and
``head_fn`` (applied at stage S-1: final norm + logits + scalar loss).
Stage-parameter gradients stay local to their pipe device (no gradient
collective at all); ``embed_fn``/``head_fn`` ("outer") parameter
gradients accumulate on devices 0 and S-1 and are summed across the
pipe axis once at the end — which also makes weight tying (embedding
matrix used by both ends) come out right for free.

Reference anchor: net-new scope beyond FluxDistributed.jl (SURVEY §2
"PP: NO"); the reference never pipelines.  Schedule follows the
published 1F1B form (PipeDream-flush / Megatron-LM); implementation is
original and TPU-first.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..optim import Optimizer
from .dp import TrainState
from .pp import PIPE_AXIS, _accepts_stage

Pytree = Any

__all__ = ["Schedule1F1B", "build_schedule", "pipeline_grads_1f1b",
           "make_train_step_1f1b", "split_state_shardings", "SCHEDULES"]

#: the hand-written schedules this module compiles: classic 1F1B, and
#: the ZB-H1-style zero-bubble variant that splits each microbatch
#: backward into an input-grad (B) tick and a deferred weight-grad (W)
#: tick so W work fills the drain bubble (arXiv:2401.10241's
#: handcrafted form, adapted to the recompute-from-ring regime)
SCHEDULES = ("1f1b", "zb")


def split_state_shardings(mesh: Mesh, axis: str = PIPE_AXIS) -> Callable:
    """``state_shardings(state)`` builder for the split param tree
    ``{"outer": ..., "stages": ...}``: outer replicated, stages sharded
    on ``axis``, optimizer state following its param.  The single source
    of truth for both pipeline schedules (``lm_pp``/``lm_pp_1f1b`` reuse
    it, and ``make_train_step_1f1b`` compiles with it)."""
    from ..sharding import make_shardings
    from .rules import train_state_specs

    def state_shardings(state: TrainState) -> TrainState:
        p_specs = {
            "outer": jax.tree.map(lambda _: P(), state.params["outer"]),
            "stages": jax.tree.map(lambda _: P(axis), state.params["stages"]),
        }
        return make_shardings(train_state_specs(state, p_specs), mesh)

    return state_shardings


class Schedule1F1B(NamedTuple):
    """Static lockstep timetable: ``[T, S]`` arrays, one row per tick.

    ``is_fwd[t, i]``/``is_bwd[t, i]`` — does device i run a stage
    forward / backward at tick t (at most one of the two is set);
    ``fwd_mb``/``bwd_mb`` — which microbatch (0 when inactive);
    ``fwd_chunk``/``bwd_chunk`` — which of the device's V interleaved
    chunks (always 0 when V = 1): selects the chunk's params and its
    input-ring slab;
    ``fwd_slot``/``bwd_slot`` — the ring-buffer slot inside that chunk;
    ``fwd_latch``/``bwd_latch`` — FLAT index (``chunk·D + mb mod D``)
    into the depth-D latch buffers a consuming tick reads from;
    ``recv_act[t, i]`` — the neighbor to the left (ring order) produced
    an activation this tick, so latch the incoming ppermute value at
    flat index ``recv_act_ix[t, i]``; ``recv_cot``/``recv_cot_ix`` —
    same for cotangents from the right.

    ``ring`` — input-ring slots per chunk; ``n_chunks`` — V;
    ``latch_depth`` — D latch slots per chunk per direction.

    Zero-bubble timetables (``schedule="zb"``) additionally carry
    ``is_w``/``w_mb``/``w_chunk``/``w_slot`` — the deferred weight-grad
    (W) pass of each microbatch, reading the stashed input AND the
    cotangent the B tick banked at the same ``m % ring`` slot; the
    input-ring slot retires at W, not B.  For ``schedule="1f1b"`` the W
    columns are all-zero and the runtime never reads them.
    """

    is_fwd: np.ndarray
    is_bwd: np.ndarray
    fwd_mb: np.ndarray
    bwd_mb: np.ndarray
    fwd_chunk: np.ndarray
    bwd_chunk: np.ndarray
    fwd_slot: np.ndarray
    bwd_slot: np.ndarray
    fwd_latch: np.ndarray
    bwd_latch: np.ndarray
    recv_act: np.ndarray
    recv_act_ix: np.ndarray
    recv_cot: np.ndarray
    recv_cot_ix: np.ndarray
    ring: int
    n_chunks: int
    latch_depth: int
    max_in_flight: int
    is_w: np.ndarray = None
    w_mb: np.ndarray = None
    w_chunk: np.ndarray = None
    w_slot: np.ndarray = None
    schedule: str = "1f1b"

    @property
    def ticks(self) -> int:
        return self.is_fwd.shape[0]

    def busy_per_device(self) -> np.ndarray:
        """Scheduled actions per device over the T ticks (F + B, plus W
        for zero-bubble timetables) — ``[S]`` ints."""
        busy = self.is_fwd.sum(axis=0) + self.is_bwd.sum(axis=0)
        if self.is_w is not None:
            busy = busy + self.is_w.sum(axis=0)
        return busy.astype(np.int64)

    @property
    def idle_ticks(self) -> np.ndarray:
        """Idle ticks per device — the bubble, counted where it sits."""
        return self.ticks - self.busy_per_device()

    @property
    def utilization(self) -> float:
        """Busy fraction over all devices and ticks (every device
        performs the same action count, so this equals any single
        device's busy share)."""
        S = self.is_fwd.shape[1]
        return float(self.busy_per_device().sum()) / (self.ticks * S)

    def render(self, max_ticks: Optional[int] = None) -> str:
        """ASCII timetable, one row per device, one column per tick:
        ``F3``/``B3``/``W3`` = forward / input-grad backward /
        weight-grad of microbatch 3 (lowercase letter + chunk digit
        when V > 1, e.g. ``f1:3`` → chunk 1, microbatch 3), ``.`` =
        idle.  Each device row ends with its idle-tick count — the
        per-device bubble at a glance.  Interleaved (V > 1) layouts
        render in full by default; pass ``max_ticks`` to truncate wide
        timetables instead.  Eyeball the warmup ramp, the steady state,
        and the (W-filled, for zb) drain directly:

        >>> print(build_schedule(4, 8).render())
        """
        T, S = self.is_fwd.shape
        V = self.n_chunks
        shown = T if max_ticks is None else min(T, max_ticks)
        cells = []
        width = 0
        for i in range(S):
            row = []
            for t in range(shown):
                if self.is_fwd[t, i]:
                    c = (f"F{self.fwd_mb[t, i]}" if V == 1 else
                         f"f{self.fwd_chunk[t, i]}:{self.fwd_mb[t, i]}")
                elif self.is_bwd[t, i]:
                    c = (f"B{self.bwd_mb[t, i]}" if V == 1 else
                         f"b{self.bwd_chunk[t, i]}:{self.bwd_mb[t, i]}")
                elif self.is_w is not None and self.is_w[t, i]:
                    c = (f"W{self.w_mb[t, i]}" if V == 1 else
                         f"w{self.w_chunk[t, i]}:{self.w_mb[t, i]}")
                else:
                    c = "."
                width = max(width, len(c))
                row.append(c)
            cells.append(row)
        idle = self.idle_ticks
        lines = [
            f"dev{i} " + " ".join(c.rjust(width) for c in row)
            + f"  idle={int(idle[i])}"
            for i, row in enumerate(cells)
        ]
        tail = "" if T <= shown else f"\n... ({T - shown} more ticks)"
        name = "ZB" if self.schedule == "zb" else "1F1B"
        head = (f"{name} schedule: S={S} M={int(self.is_fwd[:, 0].sum()) // V} "
                f"V={V} T={T} util={self.utilization:.3f} "
                f"in-flight<={self.max_in_flight}")
        return head + "\n" + "\n".join(lines) + tail


def _verify_placement(S: int, M: int, V: int, ring: int, D: int,
                      fdone, bdone, wdone=None) -> None:
    """The dependency oracle: PROVE a placement safe for the runtime's
    fixed-size buffers, raising ``RuntimeError`` on the first violated
    invariant.  ``fdone``/``bdone``/``wdone`` are tick-of-action arrays
    ``[device][chunk][mb]`` (``wdone=None`` = classic 1F1B, where the
    backward is one joint tick).

    Checked, for every edge/chunk/slot:

    * **act/cot order + latch safety** — a produced activation (left
      neighbor's F, or the S-1 → 0 chunk wrap) / cotangent (right
      neighbor's B, or the 0 → S-1 wrap) lands strictly before its
      consumer fires, and is consumed before the producer's D-th next
      value for that chunk overwrites the latch;
    * **action order** (zb) — F(m) < B(m) < W(m) on each (device,
      chunk);
    * **ring safety** — an input's ``m % ring`` slot is not reused by
      F(m+ring) until its occupant retires: at B for 1F1B, at W for zb
      (W re-reads the stashed input for the weight-grad recompute);
    * **cot-stash safety** (zb) — the cotangent B(m) banks at
      ``m % ring`` survives until W(m) consumes it, i.e. B(m+ring)
      lands after W(m).

    Exposed at module level so tests can feed deliberately corrupted
    placements and property-test the oracle itself — a proof that never
    fires proves nothing.  Real exceptions, not asserts: a placement
    bug here means silently corrupted gradients at runtime, and asserts
    vanish under ``-O``.
    """
    def _prove(ok: bool, i: int, c: int, m: int, what: str):
        if not ok:
            raise RuntimeError(
                f"pipeline schedule unsafe for S={S}, M={M}, V={V}: "
                f"{what} (device {i}, chunk {c}, microbatch {m})"
            )

    retire = wdone if wdone is not None else bdone
    for c in range(V):
        for i in range(S):
            # activation latch into device i's chunk c: produced by the
            # left neighbor (or the S-1 -> 0 wrap from chunk c-1)
            if i > 0:
                prod = [fdone[i - 1][c][m] for m in range(M)]
            elif c > 0:
                prod = [fdone[S - 1][c - 1][m] for m in range(M)]
            else:
                prod = None  # embeds, no latch
            if prod is not None:
                cons = [fdone[i][c][m] for m in range(M)]
                for m in range(M):
                    _prove(prod[m] < cons[m], i, c, m, "act order")
                    if m + D < M:
                        _prove(prod[m + D] >= cons[m], i, c, m,
                               "act latch overwritten before consumption")
            # cotangent latch into device i's chunk c: produced by the
            # right neighbor (or the 0 -> S-1 wrap from chunk c+1)
            if i < S - 1:
                prod = [bdone[i + 1][c][m] for m in range(M)]
            elif c < V - 1:
                prod = [bdone[0][c + 1][m] for m in range(M)]
            else:
                prod = None  # local loss, no latch
            if prod is not None:
                cons = [bdone[i][c][m] for m in range(M)]
                for m in range(M):
                    _prove(prod[m] < cons[m], i, c, m, "cot order")
                    if m + D < M:
                        _prove(prod[m + D] >= cons[m], i, c, m,
                               "cot latch overwritten before consumption")
            for m in range(M):
                _prove(fdone[i][c][m] < bdone[i][c][m], i, c, m,
                       "backward before its own forward")
                if wdone is not None:
                    _prove(bdone[i][c][m] < wdone[i][c][m], i, c, m,
                           "weight-grad before its input-grad")
    for i in range(S):  # ring-slot + cot-stash reuse, per chunk
        for c in range(V):
            for m in range(M - ring):
                _prove(fdone[i][c][m + ring] > retire[i][c][m], i, c, m,
                       "ring slot reused while occupant still in flight")
                if wdone is not None:
                    _prove(bdone[i][c][m + ring] > wdone[i][c][m], i, c, m,
                           "cot stash overwritten before its W consumed it")


def build_schedule(S: int, M: int, V: int = 1,
                   schedule: str = "1f1b") -> Schedule1F1B:
    """Build and VERIFY the lockstep timetable for S pipe devices, M
    microbatches, and V interleaved chunks per device (virtual stages;
    logical stage ``c·S + i`` lives on device i as chunk c).
    ``schedule`` picks the discipline: ``"1f1b"`` (one joint backward
    tick per microbatch) or ``"zb"`` (zero-bubble: the backward splits
    into an input-grad B tick and a deferred weight-grad W tick, and
    the dependency-free W work fills idle ticks — above all the drain,
    ZB-H1-style).

    Placement is dependency-driven lockstep greedy list-scheduling.
    Because no single greedy discipline wins across (S, M, V) — the
    1F1B backward-first rule is best for V ≤ 2, forward-first (memory
    gates throttling) often wins at deeper interleave — the builder
    tries a small PORTFOLIO (backward-first / forward-first × latch
    depth D ∈ {1, 2}; for zb, B>F>W vs B>W>F) and keeps the timetable
    with the fewest ticks.  Readiness = upstream forward / downstream
    cotangent placed at a strictly earlier tick, plus the resource
    gates that bound the runtime's buffers: the per-chunk input-ring
    slot gate (in-flight ≤ min(S, M) per chunk; for zb a slot retires
    at W, not B), the depth-D latch gate (a producer may not send value
    m until its consumer consumed m−D), and for zb the cot-stash gate
    (B(m) may not overwrite the stash slot of m−ring before W(m−ring)
    read it).

    For V = 1 the 1F1B backward-first/D=1 member reproduces the classic
    warmup/steady/cooldown sequence and the canonical 2(M+S-1) ticks;
    for V > 1 the fill/drain bubble shrinks toward (S-1)/V chunk-ticks
    per side — the Megatron interleaving effect.  The zb timetable runs
    3·V·M cheaper actions instead of 2·V·M, trading tick count for
    near-zero idle: its drain is W work, not waiting (the returned
    ``utilization``/``idle_ticks`` report the achieved occupancy).

    The builder then PROVES the chosen placement safe for the runtime's
    fixed-size buffers via :func:`_verify_placement` — the dependency
    oracle tests can (and do) feed corrupted placements.
    """
    if S < 2:
        raise ValueError(f"1F1B needs >= 2 pipeline stages, got {S}")
    if M < 1:
        raise ValueError(f"need >= 1 microbatch, got {M}")
    if V < 1:
        raise ValueError(f"need >= 1 chunk per device, got {V}")
    if schedule not in SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}; pick one of "
            f"{SCHEDULES}")
    zb = schedule == "zb"

    ring = min(S, M)
    # portfolio: D > 1 only helps interleaved placements; keep V = 1 on
    # the canonical single-latch schedule.  Ties on tick count break
    # toward the placement with fewer in-flight microbatches (less
    # stash memory) — e.g. a forward-greedy member that merely matches
    # backward-first on ticks must not win on memory-hungrier shape.
    if zb:
        prios = ["bfw", "bwf"]
    else:
        prios = ["bfirst", "ffirst"]
    variants = [(p, 1) for p in prios] if V == 1 else \
        [(p, d) for d in (1, 2) for p in prios]
    best = best_key = None
    for prio, depth in variants:
        placed = _place(S, M, V, ring, depth, prio, zb=zb)
        if placed is None:
            continue
        fdone_v, bdone_v, wdone_v, ticks_v, max_if_v = placed
        key = (ticks_v, max_if_v)
        if best_key is None or key < best_key:
            best_key = key
            best = (fdone_v, bdone_v, wdone_v, ticks_v, max_if_v, depth)
    if best is None:
        raise RuntimeError(
            f"{schedule} schedule failed to converge (S={S}, M={M}, V={V})")
    fdone, bdone, wdone, T, max_in_flight, D = best

    _verify_placement(S, M, V, ring, D, fdone, bdone, wdone)

    # ---- timetable arrays from the placement
    shape = (T, S)
    is_fwd = np.zeros(shape, bool)
    is_bwd = np.zeros(shape, bool)
    is_w = np.zeros(shape, bool)
    fwd_mb = np.zeros(shape, np.int32)
    bwd_mb = np.zeros(shape, np.int32)
    w_mb = np.zeros(shape, np.int32)
    fwd_chunk = np.zeros(shape, np.int32)
    bwd_chunk = np.zeros(shape, np.int32)
    w_chunk = np.zeros(shape, np.int32)
    for i in range(S):
        for c in range(V):
            for m in range(M):
                tf, tb = fdone[i][c][m], bdone[i][c][m]
                is_fwd[tf, i], fwd_mb[tf, i], fwd_chunk[tf, i] = True, m, c
                is_bwd[tb, i], bwd_mb[tb, i], bwd_chunk[tb, i] = True, m, c
                if wdone is not None:
                    tw = wdone[i][c][m]
                    is_w[tw, i], w_mb[tw, i], w_chunk[tw, i] = True, m, c

    # receiver-side latch tables: device i latches the incoming
    # activation when its ring-left neighbor fired a forward — into the
    # same chunk, or chunk c+1 across the S-1 -> 0 wrap (the final
    # logical stage's output latches nowhere: it is consumed by the
    # head on device S-1 itself).  Cotangents mirror this to the left.
    # Latch indices are FLAT: chunk·D + (mb mod D).
    recv_act = np.zeros(shape, bool)
    recv_act_ix = np.zeros(shape, np.int32)
    recv_cot = np.zeros(shape, bool)
    recv_cot_ix = np.zeros(shape, np.int32)
    recv_act[:, 1:] = is_fwd[:, :-1]
    recv_act_ix[:, 1:] = fwd_chunk[:, :-1] * D + fwd_mb[:, :-1] % D
    wrap = is_fwd[:, S - 1] & (fwd_chunk[:, S - 1] < V - 1)
    recv_act[:, 0] = wrap
    recv_act_ix[:, 0] = np.where(
        wrap, (fwd_chunk[:, S - 1] + 1) * D + fwd_mb[:, S - 1] % D, 0)
    recv_cot[:, :-1] = is_bwd[:, 1:]
    recv_cot_ix[:, :-1] = bwd_chunk[:, 1:] * D + bwd_mb[:, 1:] % D
    wrap_b = is_bwd[:, 0] & (bwd_chunk[:, 0] > 0)
    recv_cot[:, S - 1] = wrap_b
    recv_cot_ix[:, S - 1] = np.where(
        wrap_b, (bwd_chunk[:, 0] - 1) * D + bwd_mb[:, 0] % D, 0)

    return Schedule1F1B(
        is_fwd, is_bwd, fwd_mb, bwd_mb, fwd_chunk, bwd_chunk,
        (fwd_mb % ring).astype(np.int32), (bwd_mb % ring).astype(np.int32),
        (fwd_chunk * D + fwd_mb % D).astype(np.int32),
        (bwd_chunk * D + bwd_mb % D).astype(np.int32),
        recv_act, recv_act_ix, recv_cot, recv_cot_ix,
        ring, V, D, max_in_flight,
        is_w, w_mb, w_chunk, (w_mb % ring).astype(np.int32),
        schedule,
    )


def _place(S, M, V, ring, D, prio, zb: bool = False):
    """One greedy lockstep placement: returns ``(fdone, bdone, wdone,
    ticks, max_in_flight)`` (tick of each action, [device][chunk][mb];
    peak stashed microbatches on any device; ``wdone`` is None unless
    ``zb``) or None on non-convergence.  ``prio`` picks which ready
    action a device fires: ``bfirst`` retires the oldest ready backward
    (1F1B discipline), ``ffirst`` advances the oldest ready forward and
    lets the memory gates force backwards (depth-first, better at deep
    interleave); the zb disciplines are ``bfw`` (B > F > W: keep the
    pipe fed, W genuinely fills idle ticks) and ``bwf`` (B > W > F:
    retire stash slots eagerly)."""
    fdone = [[[-1] * M for _ in range(V)] for _ in range(S)]
    bdone = [[[-1] * M for _ in range(V)] for _ in range(S)]
    wdone = [[[-1] * M for _ in range(V)] for _ in range(S)] if zb else None
    retire = wdone if zb else bdone  # what frees an input-ring slot

    def f_ready(i, c, m, t):
        if fdone[i][c][m] >= 0:
            return False
        # upstream activation: left neighbor same chunk, or the S-1 -> 0
        # chunk wrap; chunk 0 on device 0 embeds (always ready)
        if i > 0:
            if not 0 <= fdone[i - 1][c][m] < t:
                return False
        elif c > 0:
            if not 0 <= fdone[S - 1][c - 1][m] < t:
                return False
        # ring-slot gate: the slot's previous occupant must be retired
        # (backward for 1F1B; the deferred weight-grad for zb, which
        # re-reads the stashed input)
        prev = m - ring
        if prev >= 0 and retire[i][c][prev] < 0:
            return False
        # forwards of a chunk run in m order (keeps the in-flight window
        # contiguous, which is what makes m % ring collision-free)
        if m > 0 and fdone[i][c][m - 1] < 0:
            return False
        # depth-D latch gate: my activation m-D for this chunk must be
        # consumed before value m lands — the dynamic counterpart of
        # the classic warmup cap S-1-i
        if m >= D:
            if i < S - 1:
                if not 0 <= fdone[i + 1][c][m - D] < t:
                    return False
            elif c < V - 1:
                if not 0 <= fdone[0][c + 1][m - D] < t:
                    return False
        return True

    def b_ready(i, c, m, t):
        if bdone[i][c][m] >= 0 or fdone[i][c][m] < 0:
            return False
        if not fdone[i][c][m] < t:
            return False
        # zb cot-stash gate: B(m) banks its cotangent at slot m % ring,
        # whose previous occupant must have been consumed by its W
        if zb:
            prev = m - ring
            if prev >= 0 and wdone[i][c][prev] < 0:
                return False
        # depth-D latch gate for the cotangent channel (mirror of f_ready)
        if m >= D:
            if i > 0:
                if not 0 <= bdone[i - 1][c][m - D] < t:
                    return False
            elif c > 0:
                if not 0 <= bdone[S - 1][c - 1][m - D] < t:
                    return False
        if i == S - 1 and c == V - 1:
            return True  # loss cotangent is local (own fwd checked above)
        if i < S - 1:
            return 0 <= bdone[i + 1][c][m] < t
        return 0 <= bdone[0][c + 1][m] < t  # 0 -> S-1 chunk wrap

    def w_ready(i, c, m, t):
        # weight-grad: needs only its own B (stashed input + cotangent
        # both local), run in m order per chunk so the stash ring stays
        # a contiguous window
        if wdone[i][c][m] >= 0:
            return False
        if not 0 <= bdone[i][c][m] < t:
            return False
        return m == 0 or wdone[i][c][m - 1] >= 0

    total = S * V * M
    placed_f = placed_b = placed_w = 0
    w_target = total if zb else 0
    t = 0
    # the interleaved critical path alone is 2·S·V ticks (one full
    # logical-pipeline traversal each way), so the non-convergence cap
    # must scale with V·(M+S), not M+S — at S=8, M=1, V=4 the feasible
    # schedule needs exactly 64 ticks.  zb places 3·V·M actions, so its
    # cap scales with the larger action count too.
    cap = (6 if zb else 4) * V * (M + S) + 8
    while placed_f < total or placed_b < total or placed_w < w_target:
        if t > cap:
            return None
        # decide every device against PRE-tick state, commit after
        chosen = []
        for i in range(S):
            pick_b = pick_f = pick_w = None
            for m in range(M):
                for c in reversed(range(V)):
                    if b_ready(i, c, m, t):
                        pick_b = ("B", c, m)
                        break
                if pick_b:
                    break
            for m in range(M):
                for c in range(V):
                    if f_ready(i, c, m, t):
                        pick_f = ("F", c, m)
                        break
                if pick_f:
                    break
            if zb:
                for m in range(M):
                    for c in range(V):
                        if w_ready(i, c, m, t):
                            pick_w = ("W", c, m)
                            break
                    if pick_w:
                        break
            if prio == "bfirst":
                pick = pick_b or pick_f
            elif prio == "ffirst":
                pick = pick_f or pick_b
            elif prio == "bfw":
                pick = pick_b or pick_f or pick_w
            else:  # bwf
                pick = pick_b or pick_w or pick_f
            chosen.append(pick)
        for i, pick in enumerate(chosen):
            if pick is None:
                continue
            act, c, m = pick
            if act == "F":
                fdone[i][c][m] = t
                placed_f += 1
            elif act == "B":
                bdone[i][c][m] = t
                placed_b += 1
            else:
                wdone[i][c][m] = t
                placed_w += 1
        t += 1

    # peak stashed microbatches on any device (fwd done, not yet
    # retired — at B for 1F1B, at W for zb)
    max_if = 0
    for i in range(S):
        events = []
        for c in range(V):
            for m in range(M):
                events.append((fdone[i][c][m], 1))
                events.append((retire[i][c][m], -1))
        events.sort()
        cur = 0
        for _, d in events:
            cur += d
            max_if = max(max_if, cur)
    return fdone, bdone, wdone, t, max_if


def pipeline_grads_1f1b(
    stage_fn: Callable,
    embed_fn: Callable,
    head_fn: Callable,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
    num_microbatches: Optional[int] = None,
    batch_axis: Optional[str] = None,
    interleave: int = 1,
    schedule: str = "1f1b",
):
    """Build ``run(stacked_params, outer, inputs, labels) -> (loss,
    stage_grads, outer_grads)`` executing the full fwd+bwd 1F1B schedule.

    * ``stage_fn(stage_params, x) -> y`` — shape-preserving pipe stage
      (``switch_stage``'s three-argument heterogeneous form — which
      receives the LOGICAL stage index ``chunk·S + device`` — and
      ``chunk_stages``-blocked virtual stages both compose);
    * ``embed_fn(outer, inputs_mb) -> x0`` — entry at logical stage 0,
      re-run under ``vjp`` at backward ticks;
    * ``head_fn(outer, y, labels_mb) -> scalar`` — exit at the final
      logical stage: per-microbatch mean loss.  The pipeline's loss is
      the mean over microbatches; gradients match ``jax.grad`` of that
      composition (tests/test_pp_1f1b.py proves it against the
      unpipelined model).

    ``interleave=V`` runs the Megatron interleaved-virtual-stage
    placement: ``stacked_params`` leaves carry a ``(S, V, ...)`` leading
    layout where ``[i, c]`` is LOGICAL stage ``c·S + i`` (round-robin,
    NOT the blocked ``chunk_stages`` layout), activations wrap
    S-1 → 0 between chunks, and the fill/drain bubble shrinks ~V-fold
    at the cost of V× the per-device latch/ring buffers.

    ``stage_grads`` come back stage-stacked (leading dim sharded on
    ``axis``) exactly like the input params — the optimizer update stays
    local to each pipe device.  ``outer_grads`` are psum'd across the
    pipe axis (embedding contributions from device 0, head contributions
    from device S-1; tied weights sum correctly).  ``batch_axis``
    composes data parallelism on a ``(data, pipe)`` mesh: grads are
    additionally averaged over ``batch_axis`` so each data row sees the
    global mean, matching the framework's DP semantics.

    ``schedule="zb"`` compiles the zero-bubble timetable instead: each
    microbatch's backward splits into an input-grad tick B (recompute
    the stage forward under ``vjp``, pull ONLY the activation cotangent
    through, bank the incoming cotangent in a per-chunk stash ring) and
    a weight-grad tick W (re-run the same ``vjp`` from the stashed
    input + banked cotangent, pull ONLY the parameter grads — plus the
    embed/head outer grads at the end stages).  W depends on nothing
    downstream, so the builder parks W ticks in the bubbles — above all
    the drain (ZB-H1).  Every pulled quantity is the SAME vjp applied
    to the SAME operands as the joint 1F1B backward, so loss and all
    gradients are bit-for-bit identical between the two schedules
    (tests/test_pp_zb.py pins this), and either schedule compiles
    exactly ONCE — the timetable is trace-time static either way.
    """
    S = mesh.shape[axis]
    M = num_microbatches or S
    V = interleave
    zb = schedule == "zb"
    sched = build_schedule(S, M, V, schedule=schedule)
    ring = sched.ring
    with_stage = _accepts_stage(stage_fn)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    cols = (
        sched.is_fwd, sched.is_bwd, sched.fwd_mb, sched.bwd_mb,
        sched.fwd_chunk, sched.bwd_chunk,
        sched.fwd_slot, sched.bwd_slot,
        sched.fwd_latch, sched.bwd_latch,
        sched.recv_act, sched.recv_act_ix,
        sched.recv_cot, sched.recv_cot_ix,
    )
    if zb:
        cols = cols + (sched.is_w, sched.w_mb, sched.w_chunk, sched.w_slot)
    rows = tuple(jnp.asarray(a) for a in cols)

    def apply_stage(sp, x, logical_stage):
        return stage_fn(sp, x, logical_stage) if with_stage else stage_fn(sp, x)

    def chunk_tree(sp, c):
        """Device-local params of chunk ``c``; identity when V = 1 (the
        stacked layout then has no chunk dim, preserving the original
        contract)."""
        if V == 1:
            return sp
        return jax.tree.map(
            lambda l: jax.lax.dynamic_index_in_dim(l, c, 0, keepdims=False), sp)

    def chunk_scatter_add(g_sp, gs_c, c):
        """Accumulate a chunk-c gradient into the (V, ...) tree."""
        if V == 1:
            return jax.tree.map(jnp.add, g_sp, gs_c)
        return jax.tree.map(
            lambda gl, gc: jax.lax.dynamic_update_index_in_dim(
                gl, jax.lax.dynamic_index_in_dim(gl, c, 0, keepdims=False) + gc,
                c, 0),
            g_sp, gs_c)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), P(batch_axis), P(batch_axis)),
        out_specs=(P(), P(axis), P()),
    )
    def run(stacked_params, outer, inputs, labels):
        sp = jax.tree.map(lambda p: p[0], stacked_params)
        idx = jax.lax.axis_index(axis)
        b = inputs.shape[0]
        assert b % M == 0, f"batch {b} not divisible by {M} microbatches"
        mb_in = inputs.reshape(M, b // M, *inputs.shape[1:])
        mb_lab = labels.reshape(M, b // M, *labels.shape[1:])

        want_axes = (axis,) if batch_axis is None else (axis, batch_axis)

        def _leaf_varying(x):
            # pcast rejects an already-varying operand; consult the
            # aval's varying-manual-axes set and convert only fresh
            # constants (zeros_like of a varying leaf is varying itself).
            # Under a (data, pipe) mesh the buffers must be varying over
            # BOTH axes, or cond branches mixing batch-derived values
            # with carries fail VMA typing.
            for ax in want_axes:
                if ax not in getattr(jax.typeof(x), "vma", frozenset()):
                    x = jax.lax.pcast(x, ax, to="varying")
            return x

        varying = lambda tr: jax.tree.map(_leaf_varying, tr)
        act = jax.eval_shape(embed_fn, outer, mb_in[0])
        # Use fully-VARYING views of the param trees inside the ticks:
        # differentiating w.r.t. a tree that is invariant over any mesh
        # axis makes the vjp transpose insert a psum_invariant INSIDE
        # the cond branch — a collective only some devices execute,
        # which deadlocks the mesh.  With varying params the pullback
        # stays device-local and the psums after the scan combine the
        # contributions (pipe for outer, batch_axis for both).
        outer = varying(outer)
        sp = varying(sp)
        zero_act = varying(jnp.zeros(act.shape, act.dtype))
        zeros_sp = varying(jax.tree.map(jnp.zeros_like, sp))
        zeros_chunk = varying(jax.tree.map(jnp.zeros_like, chunk_tree(sp, 0)))
        zeros_outer = varying(jax.tree.map(jnp.zeros_like, outer))
        f32_0 = varying(jnp.float32(0.0))
        # d(mean over microbatches)/d(l_m); varying like the vjp output
        seed = varying(jnp.float32(1.0 / M))

        def tick(carry, row):
            if zb:
                (h_act, h_cot, ringbuf, cotstash, g_sp, g_out,
                 loss_acc) = carry
                (isf, isb, mfs, mbs, cfs, cbs, sfs, sbs, lfs, lbs,
                 ras, rais, rcs, rcis, isw, mws, cws, sws) = row
            else:
                h_act, h_cot, ringbuf, g_sp, g_out, loss_acc = carry
                (isf, isb, mfs, mbs, cfs, cbs, sfs, sbs, lfs, lbs,
                 ras, rais, rcs, rcis) = row
            f = jnp.take(isf, idx)
            bk = jnp.take(isb, idx)
            mf, mb_ = jnp.take(mfs, idx), jnp.take(mbs, idx)
            cf, cb = jnp.take(cfs, idx), jnp.take(cbs, idx)
            sf, sb = jnp.take(sfs, idx), jnp.take(sbs, idx)
            lf, lb = jnp.take(lfs, idx), jnp.take(lbs, idx)

            # ---- forward tick: (maybe embed) -> stage -> stash input.
            # Buffers are (V, ring, ...) / latches (V, ...): chunk-
            # indexed so interleaved placements keep V streams apart.
            def do_f(_):
                x_in = jax.lax.cond(
                    (idx == 0) & (cf == 0),
                    lambda _: _leaf_varying(
                        embed_fn(outer, jax.lax.dynamic_index_in_dim(
                            mb_in, mf, 0, keepdims=False))),
                    lambda _: jax.lax.dynamic_index_in_dim(
                        h_act, lf, 0, keepdims=False),
                    None,
                )
                y = apply_stage(chunk_tree(sp, cf), x_in, cf * S + idx)
                slab = jax.lax.dynamic_index_in_dim(ringbuf, cf, 0, keepdims=False)
                slab = jax.lax.dynamic_update_index_in_dim(slab, x_in, sf, 0)
                return y, jax.lax.dynamic_update_index_in_dim(ringbuf, slab, cf, 0)

            y_send, ringbuf = jax.lax.cond(
                f, do_f, lambda _: (zero_act, ringbuf), None)

            # one ring-stash read for every backward flavor (joint 1F1B
            # B, zb B, zb W): the zb bit-parity guarantee rests on these
            # reads staying identical across the three consumers
            def stash_ctx(c, s, m):
                slab = jax.lax.dynamic_index_in_dim(
                    ringbuf, c, 0, keepdims=False)
                x_saved = jax.lax.dynamic_index_in_dim(
                    slab, s, 0, keepdims=False)
                lab = jax.lax.dynamic_index_in_dim(
                    mb_lab, m, 0, keepdims=False)
                return x_saved, lab, chunk_tree(sp, c), c * S + idx

            # ---- backward tick(s).  1F1B: ONE joint tick — recompute
            # fwd under vjp from the stashed input, pull param + input
            # grads together.  zb: the B tick pulls ONLY the input
            # cotangent (banking the incoming cotangent at the same
            # m % ring slot for W); the W tick re-runs the SAME vjp on
            # the SAME operands and pulls ONLY the param (+ outer)
            # grads — identical primitives on identical inputs, so
            # every gradient is bit-for-bit the 1F1B value.
            if not zb:
                def do_b(_):
                    x_saved, lab, pc, stage_ix = stash_ctx(cb, sb, mb_)

                    def last(_):
                        def fn(pc_, out_, x_):
                            return head_fn(out_, apply_stage(pc_, x_, stage_ix), lab)

                        l, pull = jax.vjp(fn, pc, outer, x_saved)
                        gs, go, gx = pull(seed)
                        return gs, varying(go), gx, l

                    def inner(_):
                        y, pull = jax.vjp(
                            lambda pc_, x_: apply_stage(pc_, x_, stage_ix),
                            pc, x_saved)
                        gs, gx = pull(jax.lax.dynamic_index_in_dim(
                            h_cot, lb, 0, keepdims=False))
                        return gs, zeros_outer, gx, f32_0

                    gs, go, gx, l = jax.lax.cond(
                        (idx == S - 1) & (cb == V - 1), last, inner, None)

                    def embed_bwd(_):
                        tok = jax.lax.dynamic_index_in_dim(
                            mb_in, mb_, 0, keepdims=False)
                        _, pull = jax.vjp(lambda o: embed_fn(o, tok), outer)
                        (go0,) = pull(gx)
                        return jax.tree.map(jnp.add, go, go0)

                    go = jax.lax.cond(
                        (idx == 0) & (cb == 0), embed_bwd, lambda _: go, None)
                    return gs, go, gx, l

                gs_d, go_d, gx_send, l = jax.lax.cond(
                    bk, do_b,
                    lambda _: (zeros_chunk, zeros_outer, zero_act, f32_0), None)
                g_sp = chunk_scatter_add(g_sp, gs_d, cb)
                g_out = jax.tree.map(jnp.add, g_out, go_d)
                loss_acc = loss_acc + l
            else:
                wk = jnp.take(isw, idx)
                mw = jnp.take(mws, idx)
                cw = jnp.take(cws, idx)
                sw = jnp.take(sws, idx)

                def do_b(_):
                    x_saved, lab, pc, stage_ix = stash_ctx(cb, sb, mb_)

                    def last(_):
                        def fn(pc_, out_, x_):
                            return head_fn(out_, apply_stage(pc_, x_, stage_ix), lab)

                        l, pull = jax.vjp(fn, pc, outer, x_saved)
                        _gs, _go, gx = pull(seed)
                        # W re-derives from the static seed; the stash
                        # write below still happens (dead value)
                        return gx, l, zero_act

                    def inner(_):
                        cot = jax.lax.dynamic_index_in_dim(
                            h_cot, lb, 0, keepdims=False)
                        y, pull = jax.vjp(
                            lambda pc_, x_: apply_stage(pc_, x_, stage_ix),
                            pc, x_saved)
                        _gs, gx = pull(cot)
                        return gx, f32_0, cot

                    gx, l, banked = jax.lax.cond(
                        (idx == S - 1) & (cb == V - 1), last, inner, None)
                    cslab = jax.lax.dynamic_index_in_dim(
                        cotstash, cb, 0, keepdims=False)
                    cslab = jax.lax.dynamic_update_index_in_dim(
                        cslab, banked, sb, 0)
                    return gx, l, jax.lax.dynamic_update_index_in_dim(
                        cotstash, cslab, cb, 0)

                gx_send, l, cotstash = jax.lax.cond(
                    bk, do_b, lambda _: (zero_act, f32_0, cotstash), None)
                loss_acc = loss_acc + l

                def do_w(_):
                    x_saved, lab, pc, stage_ix = stash_ctx(cw, sw, mw)

                    def last(_):
                        def fn(pc_, out_, x_):
                            return head_fn(out_, apply_stage(pc_, x_, stage_ix), lab)

                        _l, pull = jax.vjp(fn, pc, outer, x_saved)
                        gs, go, _gx = pull(seed)
                        return gs, varying(go)

                    def inner(_):
                        cot = jax.lax.dynamic_index_in_dim(
                            jax.lax.dynamic_index_in_dim(
                                cotstash, cw, 0, keepdims=False),
                            sw, 0, keepdims=False)
                        y, pull = jax.vjp(
                            lambda pc_, x_: apply_stage(pc_, x_, stage_ix),
                            pc, x_saved)
                        gs, gx = pull(cot)

                        def embed_bwd(_):
                            tok = jax.lax.dynamic_index_in_dim(
                                mb_in, mw, 0, keepdims=False)
                            _, pull2 = jax.vjp(
                                lambda o: embed_fn(o, tok), outer)
                            (go0,) = pull2(gx)
                            return go0

                        go = jax.lax.cond(
                            (idx == 0) & (cw == 0), embed_bwd,
                            lambda _: zeros_outer, None)
                        return gs, go

                    return jax.lax.cond(
                        (idx == S - 1) & (cw == V - 1), last, inner, None)

                gs_w, go_w = jax.lax.cond(
                    wk, do_w, lambda _: (zeros_chunk, zeros_outer), None)
                g_sp = chunk_scatter_add(g_sp, gs_w, cw)
                g_out = jax.tree.map(jnp.add, g_out, go_w)

            # ---- neighbor transfers + latches (collectives stay
            # OUTSIDE every cond: all devices participate every tick).
            # The barrier serializes the two transfers: XLA gives every
            # manual-mode collective the same channel id, and the CPU
            # thunk executor runs independent collectives concurrently,
            # so without a data dependency the two permutes join each
            # other's rendezvous and deadlock.  Sequential same-channel
            # collectives are safe (each epoch is a full barrier — the
            # same property every scan-over-ppermute pipeline relies on).
            recv_a = jax.lax.ppermute(y_send, axis, fwd_perm)
            gx_send = jax.lax.optimization_barrier((gx_send, recv_a))[0]
            recv_c = jax.lax.ppermute(gx_send, axis, bwd_perm)
            h_act = jnp.where(
                jnp.take(ras, idx),
                jax.lax.dynamic_update_index_in_dim(
                    h_act, recv_a, jnp.take(rais, idx), 0),
                h_act)
            h_cot = jnp.where(
                jnp.take(rcs, idx),
                jax.lax.dynamic_update_index_in_dim(
                    h_cot, recv_c, jnp.take(rcis, idx), 0),
                h_cot)
            if zb:
                return (h_act, h_cot, ringbuf, cotstash, g_sp, g_out,
                        loss_acc), None
            return (h_act, h_cot, ringbuf, g_sp, g_out, loss_acc), None

        latch0 = varying(
            jnp.zeros((V * sched.latch_depth,) + act.shape, act.dtype))
        ringbuf0 = varying(
            jnp.zeros((V, ring) + act.shape, act.dtype))
        if zb:
            # the zb cot stash: one banked cotangent per in-flight
            # microbatch, per chunk — same window the input ring bounds
            carry0 = (latch0, latch0, ringbuf0, ringbuf0, zeros_sp,
                      zeros_outer, f32_0)
            (_, _, _, _, g_sp, g_out, loss_acc), _ = jax.lax.scan(
                tick, carry0, rows)
        else:
            carry0 = (latch0, latch0, ringbuf0, zeros_sp, zeros_outer, f32_0)
            (_, _, _, g_sp, g_out, loss_acc), _ = jax.lax.scan(
                tick, carry0, rows)

        loss = jax.lax.psum(loss_acc, axis) / M
        g_out = jax.lax.psum(g_out, axis)
        if batch_axis is not None:  # DP composition: mean over data rows
            n = mesh.shape[batch_axis]
            loss = jax.lax.psum(loss, batch_axis) / n
            g_out = jax.tree.map(
                lambda g: jax.lax.psum(g, batch_axis) / n, g_out)
            g_sp = jax.tree.map(
                lambda g: jax.lax.psum(g, batch_axis) / n, g_sp)
        return loss, jax.tree.map(lambda g: g[None], g_sp), g_out

    run.schedule = sched
    run.utilization = sched.utilization
    return run


def make_train_step_1f1b(
    stage_fn: Callable,
    embed_fn: Callable,
    head_fn: Callable,
    optimizer: Optimizer,
    mesh: Mesh,
    axis: str = PIPE_AXIS,
    num_microbatches: Optional[int] = None,
    batch_axis: Optional[str] = None,
    interleave: int = 1,
    donate: bool = True,
    input_key: str = "tokens",
    label_key: Optional[str] = None,
    schedule: str = "1f1b",
):
    """Compile a full 1F1B (or zero-bubble, ``schedule="zb"``) training
    step.

    ``TrainState.params`` is the split tree ``{"outer": ..., "stages":
    ...}`` (``lm_pp_1f1b``'s ``split_params`` builds it for the LM).
    Gradients never leave their pipe device except the psum'd outer
    tree, so the optimizer update is stage-local like the GPipe step
    (``pp.make_train_step_pp``).  ``label_key`` defaults to
    ``input_key`` (next-token LM losses read the shifted inputs).
    """
    run = pipeline_grads_1f1b(
        stage_fn, embed_fn, head_fn, mesh, axis=axis,
        num_microbatches=num_microbatches, batch_axis=batch_axis,
        interleave=interleave, schedule=schedule,
    )
    repl = NamedSharding(mesh, P())
    # under DP composition the batch arrives data-sharded (the
    # shard_batch layout), not replicated
    batch_sh = NamedSharding(mesh, P(batch_axis)) if batch_axis else repl
    state_shardings = split_state_shardings(mesh, axis)

    def step(state: TrainState, batch):
        loss, g_stages, g_outer = run(
            state.params["stages"], state.params["outer"],
            batch[input_key], batch[label_key or input_key],
        )
        grads = {"outer": g_outer, "stages": g_stages}
        new_params, new_opt = optimizer.apply(
            state.params, grads, state.opt_state, state.step
        )
        return TrainState(
            params=new_params, opt_state=new_opt,
            model_state=state.model_state, step=state.step + 1,
        ), {"loss": loss}

    def compile_for(state: TrainState):
        sh = state_shardings(state)
        return jax.jit(
            step,
            in_shardings=(sh, batch_sh),
            out_shardings=(sh, repl),
            donate_argnums=(0,) if donate else (),
        )

    return compile_for
