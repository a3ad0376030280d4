"""Slot-based continuous-batching engine for ``TransformerLM``.

The ROADMAP's inference half ("serve heavy traffic") needs many
concurrent requests per chip, but per-request Python loops throw away
exactly what makes TPUs fast: a small set of fixed-shape compiled XLA
programs (arXiv:1810.09868's core lesson).  This engine serves ANY
number of requests through a handful of fixed-shape programs:

* **Bucketed prefill** (dense layout) — a batch-1 scalar-index decode
  forward over the prompt padded up to a shape bucket ({128, 512, 2048}
  by default), so the jit cache holds one compiled prefill per bucket
  and stays warm no matter what prompt lengths arrive.  Right-padding
  is safe by construction: a position's cache slot is a function of the
  position alone, the causal mask admits only positions ≤ the query's,
  and every pad entry is overwritten by the real token for its position
  before it could ever become attendable.
* **Fixed-slot decode** — ONE single-token step over all ``max_slots``
  cache rows of a ``slot_decode=True`` model (per-slot cursors, see
  models/transformer_lm.py), compiled once.  Finished requests free
  their slot; admissions splice a prefilled batch-1 cache into a free
  row mid-flight without touching the compiled step.

Two **cache layouts** (``serve/cache_layout.py``) sit under those
programs:

* ``layout="dense"`` (default) — the original fixed-slot cache:
  ``max_slots × (sinks + window | max_len)`` rows per layer,
  ring-buffer + pinned sinks when windowed (sized EXACTLY: the dynamic
  valid-length prefill operand gates pad writes out of the ring, so no
  slack rows are reserved).  HBM scales with capacity.
* ``layout="paged"`` — a shared pool of ``kv_blocks`` fixed-size KV
  blocks per layer with per-slot page tables carried as device-side
  int32 *data*, so HBM scales with live tokens and freed blocks return
  to the pool on EOS.  Prefill runs in fixed-size **chunks** written
  straight through the page table (no splice program), which lets the
  scheduler interleave a long prompt's chunks with decode ticks; with
  ``prefix_cache=True`` completed prompt blocks are hash-keyed and
  refcounted so shared prefixes prefill once.  Page-table updates are
  data fed to the same compiled programs — the ONE-decode-compile
  invariant holds across admissions, frees, growth and prefix reuse.

Greedy decoding is token-for-token identical to sequential
:func:`models.generate` under BOTH layouts (the golden parity tests,
tests/test_serve_engine.py and tests/test_serve_paged.py); temperature
sampling uses an independent per-request key stream (``fold``-free:
keys split inside the compiled step), so it is distribution-identical
but not key-stream-identical.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models.transformer_lm import KV_QUANTS, TransformerLM, make_decode_cache
from .cache_layout import DenseLayout, PagedLayout

__all__ = ["LMEngine", "DEFAULT_BUCKETS", "DEFAULT_KV_BLOCK_SIZE"]

DEFAULT_BUCKETS = (128, 512, 2048)
DEFAULT_KV_BLOCK_SIZE = 16

#: cache leaves that carry one row per slot (everything else is a
#: shared block pool in the paged layout)
_PER_ROW_LEAVES = ("cache_index", "pos_index", "page_table", "slot_pos",
                   "slot_live", "valid_len")


def _jit_cache_size(fn) -> int:
    """Compile count of a jitted callable (-1 if this jax can't say).
    The decode bench asserts steady state holds at ONE decode compile."""
    probe = getattr(fn, "_cache_size", None)
    try:
        return int(probe()) if callable(probe) else -1
    except Exception:
        return -1


def _leaf_name(path) -> Optional[str]:
    return getattr(path[-1], "key", None)


class _PrefillState:
    """In-flight prefill for one slot — the scheduler advances it one
    chunk per call so a long prompt interleaves with decode ticks."""

    __slots__ = ("slot", "tokens", "temperature", "key", "plen", "pos",
                 "small", "padded", "rid")

    def __init__(self, slot, tokens, temperature, key, pos=0, small=None,
                 rid=None):
        self.slot = slot
        self.tokens = [int(t) for t in tokens]
        self.temperature = float(temperature)
        self.key = key
        self.plen = len(self.tokens)
        self.pos = pos        # next prompt position to process
        self.small = small    # dense layout: carried batch-1 cache
        self.padded = 0       # padded tokens computed so far
        self.rid = rid        # request trace id (obs.reqtrace) — pure
        #                       host metadata; never enters a program


class LMEngine:
    """Compiled-program pool + slot cache for continuous batching.

    ``model`` is the TRAINING-mode ``TransformerLM`` (the engine derives
    its own ``decode=True`` clones); ``params`` its trained parameters.
    The engine is not thread-safe by itself — the scheduler serializes
    all calls onto one loop thread.

    Cold start (:mod:`fluxdistributed_tpu.compilation`): ``prewarm=True``
    runs :meth:`warmup` at construction — every program compiles before
    the first request instead of inside its latency.  ``aot_dir`` goes
    further: each program is loaded from a serialized on-disk executable
    when one matches this topology + model, else compiled now and
    serialized for the next process (a restarted server skips its whole
    compile pool).

    Layout knobs:

    * ``layout`` — ``"dense"`` (default, the original fixed-slot cache)
      or ``"paged"`` (shared KV block pool + per-slot page tables).
    * ``kv_block_size`` / ``kv_blocks`` — paged pool geometry: rows per
      block and blocks per layer.  ``kv_blocks=None`` sizes the pool for
      full capacity (``max_slots`` worst-case slots — no memory saving,
      but never refuses what dense would serve); size it SMALLER to make
      HBM scale with live tokens and let admission backpressure handle
      the tail.
    * ``prefill_chunk`` — prompt positions per prefill chunk.  Paged
      prefill is always chunked (default 128); a dense engine stays on
      whole-bucket prefill unless a chunk size is given.
    * ``prefix_cache`` — paged only, plain attention only: completed
      prompt blocks are prefix-hash-keyed and refcounted, so repeated
      system prompts prefill once (copy-on-write at the divergence
      block — shared blocks are never written).
    """

    def __init__(
        self,
        model: TransformerLM,
        params,
        *,
        max_slots: int = 8,
        max_len: int = 1024,
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        prewarm: bool = False,
        aot_dir: str | None = None,
        layout: str = "dense",
        kv_block_size: int = DEFAULT_KV_BLOCK_SIZE,
        kv_blocks: int | None = None,
        prefill_chunk: int | None = None,
        prefix_cache: bool = False,
        attention_impl: str = "xla",
        kv_dtype: str | None = None,
    ):
        if getattr(model, "no_decode", None):
            # a model without a decode path says what it lacks: fail with
            # that, before any clone or cast
            raise NotImplementedError(model.no_decode)
        if model.moe_every:
            raise ValueError(
                "the serving engine supports dense models only (MoE decode "
                "routes per-token expert dispatch; build the model with "
                "moe_every=0)")
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        if layout not in ("dense", "paged"):
            raise ValueError(f"unknown cache layout {layout!r} (dense|paged)")
        if not model.use_rope:
            if model.max_len is None or model.max_len < max_len:
                raise ValueError(
                    f"use_rope=False needs the model's learned positional "
                    f"table to cover the engine's max_len ({max_len}); got "
                    f"model.max_len={model.max_len}")
        if prefix_cache and layout != "paged":
            raise ValueError(
                "prefix_cache=True needs layout='paged' (the dense layout "
                "has no shareable blocks)")
        if prefix_cache and model.window is not None:
            raise ValueError(
                "prefix_cache is not supported with sliding-window "
                "attention: ring eviction makes a stored block's contents "
                "depend on everything decoded after it, so equal prefixes "
                "stop implying equal blocks. Drop window= or prefix_cache.")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if attention_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown attention_impl {attention_impl!r} (xla|pallas)")
        kv_quant = kv_dtype or "none"
        if kv_quant not in KV_QUANTS:
            raise ValueError(
                f"unknown kv_dtype {kv_dtype!r} "
                f"(None|{'|'.join(q for q in KV_QUANTS if q != 'none')})")
        self.attention_impl = attention_impl
        self.kv_quant = kv_quant
        self.layout_name = layout
        self.max_slots = max_slots
        self.max_len = max_len
        if layout == "paged":
            self.prefill_chunk: Optional[int] = min(
                prefill_chunk or 128, max_len)
            self.buckets: Tuple[int, ...] = ()
        else:
            self.prefill_chunk = (
                min(prefill_chunk, max_len) if prefill_chunk else None)
            # clamp buckets to the cache and always top out AT max_len:
            # without the top bucket, a prompt in (largest bucket,
            # max_len] would be rejected even though the slot cache can
            # hold it
            bl = sorted({int(b) for b in buckets if 0 < int(b) < max_len}
                        | {max_len})
            self.buckets = tuple(bl)
        #: chunked prefill (paged always; dense with prefill_chunk=)
        #: advances through prefill_begin/prefill_step — the scheduler
        #: interleaves chunks with decode ticks
        self.prefill_incremental = self.prefill_chunk is not None
        # store weights in the model's COMPUTE dtype once, up front.
        # flax casts f32-stored params to `dtype` inside every apply;
        # generate()'s scan hoists that cast out of its loop, but the
        # engine's per-token step would pay the full-tree cast EVERY
        # step (it dominated the step on CPU).  Pre-casting is the same
        # rounding, applied once — numerics identical, and the resident
        # weight footprint halves for bf16 models.
        self.params = jax.tree.map(
            lambda x: jnp.asarray(
                x, model.dtype if jnp.issubdtype(
                    jnp.asarray(x).dtype, jnp.floating) else None),
            params)
        self.model = model
        # decode=True rejects attn_fn by design (the cache path always
        # uses the dense core — the math is identical for gathered
        # weights); dropout is inference-irrelevant.  Padded prefill is
        # made safe by the DYNAMIC VALID-LENGTH operand: every prefill/
        # chunk program receives the call's real token count as cache
        # data (``valid_len`` — see models.transformer_lm.VALID_UNGATED)
        # and the model gates pad positions out of the windowed ring
        # write, so a pad can never write OR evict an in-band key.  The
        # ring is therefore sized exactly sinks + window — the old
        # ``ring_slack`` over-allocation (largest pad run: inter-bucket
        # gap / prefill chunk) is gone, and the reclaimed rows show up
        # directly in ``reserved_kv_bytes``.
        #: per-slot per-layer KV rows logically addressable:
        #: sinks + window for windowed models (exact), max_len otherwise
        self.kv_rows_per_slot = (
            max_len if model.window is None
            else min(model.window + model.sinks, max_len))
        if layout == "paged":
            pages_per_slot = -(-self.kv_rows_per_slot // kv_block_size)
            if kv_blocks is None:
                kv_blocks = max_slots * pages_per_slot
            self.layout = PagedLayout(
                max_slots, self.kv_rows_per_slot, kv_block_size,
                kv_blocks, prefix_cache=prefix_cache, kv_quant=kv_quant)
            paged_kw = dict(kv_block_size=kv_block_size, kv_blocks=kv_blocks)
        else:
            self.layout = DenseLayout(max_slots, self.kv_rows_per_slot,
                                      kv_quant=kv_quant)
            paged_kw = dict()
        # ring_slack pinned to 0 on the clones: the engine's layout
        # math (kv_rows_per_slot, pages_per_slot, reserved_kv_bytes)
        # sizes the ring at exactly sinks + window — a user model's
        # retention slack must not silently desynchronize the cache
        # allocation from that accounting
        self.decode_model = model.clone(
            decode=True, slot_decode=True, attn_fn=None, dropout=0.0,
            ring_slack=0, attention_impl=attention_impl,
            kv_quant=kv_quant, **paged_kw)
        self.cache = make_decode_cache(self.decode_model, max_slots, max_len)
        if layout == "dense":
            # the prefill program runs whole buckets/chunks (t > 1), so
            # its attention stays XLA whatever the decode impl — but it
            # must share the decode model's QUANT setting: the cache it
            # fills is the cache the splice hands to the decode step
            self.prefill_model = model.clone(
                decode=True, slot_decode=False, attn_fn=None, dropout=0.0,
                ring_slack=0, attention_impl=attention_impl,
                kv_quant=kv_quant)
            # reusable zero template: _prefill never mutates its input,
            # so one template serves every admission
            self._prefill_zero = make_decode_cache(
                self.prefill_model, 1, max_len)
        else:
            # paged prefill is the decode model itself at chunk shape —
            # chunks write straight through the page table, no splice
            self.prefill_model = None
            self._prefill_zero = None
        # per-slot sampling state lives ON DEVICE between steps — the
        # decode loop's only host traffic is the one token sync the
        # scheduler needs for stop checks and streaming
        self._tok = jnp.zeros((max_slots,), jnp.int32)
        self._temp = jnp.zeros((max_slots,), jnp.float32)
        self._keys = jnp.zeros((max_slots, 2), jnp.uint32)
        # paged host mirrors: which slots are decoding, and each slot's
        # next write position (drives just-in-time block allocation)
        self._decoding: set = set()
        self._host_pos = [0] * max_slots
        self._prefill_jit = jax.jit(self._prefill_impl)
        # donate the carried state (slot cache, tokens, keys): every
        # step/splice REPLACES them, so XLA may update the KV in place
        # instead of copying the whole slot cache each call — at serving
        # scale that copy is the step's largest memory traffic after the
        # weights themselves
        self._insert_jit = jax.jit(self._insert_impl, donate_argnums=(0,))
        self._step_jit = jax.jit(self._step_impl, donate_argnums=(1, 2, 4))
        self._sample1_jit = jax.jit(self._sample)
        self._chunk_jit = jax.jit(self._chunk_impl, donate_argnums=(1,))
        self._bind_jit = jax.jit(self._bind_impl, donate_argnums=(0,))
        self._release_jit = jax.jit(self._release_impl, donate_argnums=(0,))
        # AOT executables keyed by program name (prefill additionally by
        # bucket — one fixed shape each); populated by _load_aot, empty
        # when aot_dir is None so every call falls through to the jits
        self._aot: dict = {}
        if aot_dir:
            self._load_aot(aot_dir)
        if prewarm:
            self.warmup()

    # ---- compiled programs ------------------------------------------------

    def _prefill_impl(self, params, cache0, toks, plen):
        """Whole padded prompt (or one chunk of it) in one parallel
        pass; returns the filled batch-1 cache and the logits at the
        LAST REAL position (the distribution of the next token).

        ``plen`` — the call's REAL token count — is also the dynamic
        valid-length operand: it arms the windowed ``valid_len`` write
        gate (cache DATA, so every prompt length shares ONE compiled
        program per bucket) so pad positions never write into, or
        evict from, the exactly-sized ring."""
        if self.model.window is not None:
            def arm(path, leaf):
                if _leaf_name(path) == "valid_len":
                    return jnp.full_like(leaf, plen)
                return leaf

            cache0 = jax.tree_util.tree_map_with_path(arm, cache0)
        logits, mut = self.prefill_model.apply(
            {"params": params, "cache": cache0}, toks, train=False,
            mutable=["cache"],
        )
        last = jax.lax.dynamic_slice_in_dim(logits, plen - 1, 1, axis=1)[:, 0]
        return mut["cache"], last.astype(jnp.float32)

    def _insert_impl(self, big, small, slot, plen):
        """Splice a prefilled batch-1 cache into slot row ``slot``.

        Cursor leaves are set to the TRUE prompt length (the prefill ran
        over the padded bucket, so its own cursor reads bucket, not
        plen); pad K/V entries ride along and are masked/overwritten by
        construction (module docstring).
        """

        def leaf(path, bg, sm):
            name = _leaf_name(path)
            if name in ("cache_index", "pos_index"):
                return bg.at[slot].set(jnp.asarray(plen, bg.dtype))
            if name == "slot_pos":
                # scrub PAD ring entries (position >= plen) back to -1
                # ("unwritten, never attendable"): the spliced ring then
                # holds exactly what a batch-1 unpadded prefill of plen
                # tokens would hold — the parity invariant
                return bg.at[slot].set(jnp.where(sm < plen, sm, -1))
            if name == "valid_len":
                # decode rows run UNGATED (every decode write is real);
                # the gate is a per-prefill-call operand, not slot state
                return bg
            if name in ("cached_k", "cached_v",
                        "cached_k_scale", "cached_v_scale"):
                return bg.at[slot].set(sm[0])
            raise ValueError(f"unknown cache leaf {name!r}")

        return jax.tree_util.tree_map_with_path(leaf, big, small)

    def _chunk_impl(self, params, cache, toks, slot, start, nvalid, arm):
        """One paged prefill chunk straight into slot ``slot``'s pages.

        A batch-1 view of the slot's rows (shared pools pass through
        untouched) runs the decode model at chunk shape; the writeback
        then pins the cursors to ``start + nvalid`` (host truth — the
        all-slot decode step may have drifted a mid-prefill slot's
        cursor, and a padded final chunk overshoots) and scrubs pad
        ``slot_pos`` entries, exactly the dense splice's invariant.
        The view forces the ``slot_live`` write gate open (the big
        cache keeps it 0 mid-prefill so decode-tick drift writes DROP);
        ``arm=1`` on the final chunk flips the big gate live for
        decode.  Page tables are read-only here: allocation is host
        bookkeeping applied through :meth:`_bind_impl`, all of it DATA
        — this one compiled program serves every chunk of every
        prompt."""

        def take(path, leaf):
            name = _leaf_name(path)
            if name in _PER_ROW_LEAVES:
                row = jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=0)
                if name in ("cache_index", "pos_index"):
                    row = jnp.full_like(row, start)
                if name == "slot_live":
                    row = jnp.ones_like(row)  # the chunk itself writes
                if name == "valid_len":
                    # the dynamic valid-length operand: only nvalid of
                    # this chunk's positions are real — the windowed
                    # write gate drops the pads (no ring slack needed)
                    row = jnp.full_like(row, nvalid)
                if name == "slot_pos":
                    # every ring entry >= start is cursor-drift garbage
                    # from before the slot_live gate existed for this
                    # row (e.g. a fresh admission over a just-released
                    # slot) — scrub with host truth so the windowed
                    # read-before-write can never see a position this
                    # slot has not actually written
                    row = jnp.where(row < start, row, -1)
                return row
            return leaf  # shared block pools

        view = jax.tree_util.tree_map_with_path(take, cache)
        logits, mut = self.decode_model.apply(
            {"params": params, "cache": view}, toks, train=False,
            mutable=["cache"],
        )
        new = mut["cache"]
        end = start + nvalid

        def put(path, big, small):
            name = _leaf_name(path)
            if name in ("cache_index", "pos_index"):
                return big.at[slot].set(jnp.asarray(end, big.dtype))
            if name == "slot_live":
                return big.at[slot].set(arm.astype(big.dtype))
            if name == "valid_len":
                return big  # decode rows stay ungated (VALID_UNGATED)
            if name == "slot_pos":
                return big.at[slot].set(
                    jnp.where(small[0] < end, small[0], -1))
            if name == "page_table":
                return big  # engine-owned; the model never writes it
            return small  # shared pools, mutated through the page table

        cache2 = jax.tree_util.tree_map_with_path(put, cache, new)
        last = jax.lax.dynamic_slice_in_dim(logits, nvalid - 1, 1, axis=1)[:, 0]
        return cache2, last.astype(jnp.float32)

    def _bind_impl(self, cache, slot, row):
        """Write slot ``slot``'s WHOLE page-table row in every layer
        (block ids are layer-agnostic: layer L's pool uses the same
        numbering).  The row has a fixed length (``pages_per_slot``), so
        one dispatch covers an admission's entire claimed prefix, a
        chunk's block growth, or a decode tick's boundary crossing —
        never one dispatch per page.  Page-table growth is DATA — the
        compiled decode and chunk programs never change."""

        def leaf(path, bg):
            if _leaf_name(path) == "page_table":
                return bg.at[slot].set(row.astype(bg.dtype))
            return bg

        return jax.tree_util.tree_map_with_path(leaf, cache)

    def _release_impl(self, cache, slot):
        """Park a freed paged slot: cursors to zero, page-table row and
        ring positions to -1 ("unallocated / unwritten") — writes drop,
        reads are mask-excluded, and the freed blocks are back on the
        host free list."""

        def leaf(path, bg):
            name = _leaf_name(path)
            if name in ("cache_index", "pos_index", "slot_live"):
                return bg.at[slot].set(jnp.zeros((), bg.dtype))
            if name in ("page_table", "slot_pos"):
                return bg.at[slot].set(jnp.full((), -1, bg.dtype))
            return bg

        return jax.tree_util.tree_map_with_path(leaf, cache)

    def _sample(self, logits, temp, keys):
        """Greedy/temperature next-token draw, per row.

        Same math as ``models.generate`` (f32 logits / temperature →
        categorical; argmax at temperature 0) but with an independent
        key per row, split inside the compiled program.
        """
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pairs = jax.vmap(partial(jax.random.split, num=2))(keys)
        new_keys, subs = pairs[:, 0], pairs[:, 1]
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        sampled = jax.vmap(jax.random.categorical)(subs, scaled)
        nxt = jnp.where(temp > 0, sampled.astype(jnp.int32), greedy)
        return nxt, new_keys

    def _step_impl(self, params, cache, tok, temp, keys):
        """One decode step over ALL slots: [S] tokens in, [S] out."""
        logits, mut = self.decode_model.apply(
            {"params": params, "cache": cache}, tok[:, None], train=False,
            mutable=["cache"],
        )
        nxt, new_keys = self._sample(
            logits[:, 0].astype(jnp.float32), temp, keys)
        return mut["cache"], nxt, new_keys

    # ---- cold-start: AOT executables + prewarm ----------------------------

    def _example_args(self, program: str, bucket: int | None = None):
        """Zero-filled arguments with each program's exact shapes — what
        AOT lowering and prewarm both trace/execute against."""
        if program == "prefill":
            return (self.params, self._prefill_zero,
                    jnp.zeros((1, bucket), jnp.int32),
                    jnp.asarray(1, jnp.int32))
        if program == "insert":
            return (self.cache, self._prefill_zero,
                    jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32))
        if program == "step":
            return (self.params, self.cache, self._tok, self._temp, self._keys)
        if program == "sample1":
            return (jnp.zeros((1, self.model.vocab), jnp.float32),
                    jnp.zeros((1,), jnp.float32),
                    jnp.zeros((1, 2), jnp.uint32))
        if program == "chunk":
            return (self.params, self.cache,
                    jnp.zeros((1, self.prefill_chunk), jnp.int32),
                    jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32),
                    jnp.asarray(1, jnp.int32), jnp.asarray(0, jnp.int32))
        if program == "bind":
            return (self.cache, jnp.asarray(0, jnp.int32),
                    jnp.full((self.layout.pages_per_slot,), -1, jnp.int32))
        if program == "release":
            return (self.cache, jnp.asarray(0, jnp.int32))
        raise ValueError(f"unknown engine program {program!r}")

    def _aot_jobs(self):
        """(name, jit, bucket) for every program this layout serves
        through — the AOT pool and warmup iterate the same list."""
        jobs = [("step", self._step_jit, None),
                ("sample1", self._sample1_jit, None)]
        if self.layout_name == "paged":
            jobs += [("chunk", self._chunk_jit, None),
                     ("bind", self._bind_jit, None),
                     ("release", self._release_jit, None)]
        else:
            jobs += [("insert", self._insert_jit, None)]
            shapes = set(self.buckets)
            if self.prefill_chunk:
                shapes.add(self.prefill_chunk)
            jobs += [("prefill", self._prefill_jit, b)
                     for b in sorted(shapes)]
        return jobs

    def _load_aot(self, aot_dir: str) -> None:
        """Load-or-compile every engine program as a serialized AOT
        executable under ``aot_dir``.  A process that finds matching
        files on disk skips tracing, lowering AND backend compilation
        for its entire program pool; any mismatch (topology, jaxlib,
        model shape) falls back to a fresh compile of that program,
        which is then serialized for the next process."""
        from .. import compilation

        # everything that changes a compiled program without changing
        # argument shapes (windowing, norms, rope, ...) is in the model
        # repr (config_tag scrubs the addresses a callable field like
        # attn_fn prints); max_len/buckets shape the cache and prefill,
        # and the layout knobs shape the paged pool and chunk programs
        tag = compilation.config_tag(
            repr(self.decode_model), self.max_slots, self.max_len,
            self.buckets, self.layout_name, self.prefill_chunk)
        fp = compilation.topology_fingerprint(tag=tag)
        for name, fn, bucket in self._aot_jobs():
            args = self._example_args(name, bucket)
            key = (name, bucket) if bucket is not None else name
            fname = f"serve_{name}" + (f"_b{bucket}" if bucket else "")
            self._aot[key] = compilation.load_or_compile(
                fn, args, directory=aot_dir, name=fname, fingerprint=fp)

    def _call_prefill(self, padded, plen, cache0=None):
        fn = self._aot.get(("prefill", int(padded.shape[1])))
        if fn is None:
            fn = self._prefill_jit
        if cache0 is None:
            cache0 = self._prefill_zero
        return fn(self.params, cache0, padded, plen)

    def _call_insert(self, small, slot, plen):
        fn = self._aot.get("insert", self._insert_jit)
        return fn(self.cache, small, slot, plen)

    def _call_step(self):
        fn = self._aot.get("step", self._step_jit)
        return fn(self.params, self.cache, self._tok, self._temp, self._keys)

    def _call_sample1(self, logits, temp, keys):
        fn = self._aot.get("sample1", self._sample1_jit)
        return fn(logits, temp, keys)

    def _call_chunk(self, toks, slot, start, nvalid, arm):
        fn = self._aot.get("chunk", self._chunk_jit)
        return fn(self.params, self.cache, toks,
                  jnp.asarray(slot, jnp.int32),
                  jnp.asarray(start, jnp.int32),
                  jnp.asarray(nvalid, jnp.int32),
                  jnp.asarray(arm, jnp.int32))

    def _call_bind(self, slot):
        """Push slot ``slot``'s host page-table row to the device —
        ONE dispatch regardless of how many pages just changed."""
        fn = self._aot.get("bind", self._bind_jit)
        row = np.asarray(self.layout.slot_pages[slot], np.int32)
        self.cache = fn(self.cache, jnp.asarray(slot, jnp.int32),
                        jnp.asarray(row))

    def _call_release(self, slot):
        fn = self._aot.get("release", self._release_jit)
        self.cache = fn(self.cache, jnp.asarray(slot, jnp.int32))

    def warmup(self) -> dict:
        """Pre-pay every compile before the first request — then rebuild
        pristine slot state, so the warmed engine is indistinguishable
        from a fresh one except that no program compiles on the serving
        path again (the ONE-decode-compile invariant holds with the
        compile moved ahead of traffic).

        Returns ``{"seconds": ..., "compiles": ...}`` (compiles == 0
        when an AOT pool or a warm persistent cache made even warmup
        free of backend compilation... the jit-cache invariant is what
        :meth:`compile_stats` reports either way)."""
        import time

        from ..obs import jaxmon

        jaxmon.install()
        c0 = jaxmon.compile_count()
        t0 = time.perf_counter()
        if self.layout_name == "paged":
            # chunk against the pristine all-unallocated page tables:
            # every write drops, every read is masked — pure compile
            self.cache, last = self._call_chunk(
                jnp.zeros((1, self.prefill_chunk), jnp.int32), 0, 0, 1, 0)
            self._call_sample1(
                last, jnp.zeros((1,), jnp.float32),
                jnp.zeros((1, 2), jnp.uint32))
            self._call_bind(0)
            self._call_release(0)
        else:
            small = last = None
            for b in self.buckets:
                small, last = self._call_prefill(
                    jnp.zeros((1, b), jnp.int32), jnp.asarray(1, jnp.int32))
            if self.prefill_chunk and self.prefill_chunk not in self.buckets:
                small, last = self._call_prefill(
                    jnp.zeros((1, self.prefill_chunk), jnp.int32),
                    jnp.asarray(1, jnp.int32))
            self._call_sample1(
                last, jnp.zeros((1,), jnp.float32),
                jnp.zeros((1, 2), jnp.uint32))
            # the splice and step donate the live slot state; the dummy
            # data they leave behind is discarded with the rebuild below
            self.cache = self._call_insert(
                small, jnp.asarray(0, jnp.int32), jnp.asarray(1, jnp.int32))
        self.cache, self._tok, self._keys = self._call_step()
        jax.block_until_ready(self._tok)
        self.cache = make_decode_cache(
            self.decode_model, self.max_slots, self.max_len)
        self._tok = jnp.zeros((self.max_slots,), jnp.int32)
        self._temp = jnp.zeros((self.max_slots,), jnp.float32)
        self._keys = jnp.zeros((self.max_slots, 2), jnp.uint32)
        return {"seconds": time.perf_counter() - t0,
                "compiles": int(jaxmon.compile_count() - c0)}

    # ---- host-side API (called by the scheduler loop thread) --------------

    def pick_bucket(self, plen: int) -> int:
        """Smallest warm bucket covering ``plen`` (jit caches stay warm)."""
        for b in self.buckets:
            if plen <= b:
                return b
        raise ValueError(
            f"prompt length {plen} exceeds the largest prefill bucket "
            f"({self.buckets[-1]}). Either shorten the prompt or construct "
            f"the engine with a larger bucket (buckets={self.buckets}, "
            f"max_len={self.max_len}).")

    def validate_request(self, prompt_len: int, max_new_tokens: int) -> None:
        """Admission-time shape checks — every error is actionable."""
        if prompt_len < 1:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if self.buckets:
            self.pick_bucket(prompt_len)
        if prompt_len + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"= {prompt_len + max_new_tokens} exceeds the engine's slot "
                f"cache (max_len={self.max_len}). Lower max_new_tokens or "
                "rebuild the engine with a larger max_len.")
        if self.layout_name == "paged":
            need = self.layout.pages_for(prompt_len + max_new_tokens)
            total = self.layout.pool.num_blocks
            if need > total:
                raise ValueError(
                    f"request needs {need} KV blocks at its token budget "
                    f"(prompt {prompt_len} + max_new_tokens "
                    f"{max_new_tokens}, block size "
                    f"{self.layout.block_size}) but the pool only has "
                    f"{total}. Lower max_new_tokens, or rebuild the engine "
                    f"with kv_blocks >= {need}.")

    def can_admit(self, prompt: Sequence[int], max_new_tokens: int) -> bool:
        """Admission gate beyond free slots: in the paged layout a
        request is only admitted when the block pool can cover its
        WORST-CASE footprint on top of every already-admitted slot's —
        so an admitted request can always run to its budget and pool
        exhaustion surfaces as queueing, never as a stuck slot."""
        return self.layout.can_admit(prompt, max_new_tokens)

    # ---- prefill (whole-prompt and incremental) ---------------------------

    def prefill_begin(self, slot: int, tokens: Sequence[int],
                      temperature: float, key: np.ndarray,
                      max_new_tokens: Optional[int] = None,
                      rid: Optional[str] = None) -> _PrefillState:
        """Start prefilling ``tokens`` into ``slot``; the scheduler
        advances the returned state one chunk per :meth:`prefill_step`
        call (interleaving chunks with decode ticks).  ``max_new_tokens``
        sizes the paged worst-case reservation (default: the whole slot
        budget) — pass the request's real bound so the reservation
        matches what :meth:`can_admit` agreed to.  ``rid`` is the
        request's trace id (obs.reqtrace): it rides this state so
        engine-side chunk advances stay attributable to the request —
        host metadata only, never an input to a compiled program."""
        st = _PrefillState(slot, tokens, temperature, key, rid=rid)
        if self.layout_name == "paged":
            budget = (self.max_len - st.plen if max_new_tokens is None
                      else max_new_tokens)
            start = self.layout.admit(slot, st.tokens, budget)
            st.pos = start
            if start:
                # claimed prefix pages go live on device now — one
                # row-bind dispatch however long the cached prefix is
                self._call_bind(slot)
            self._host_pos[slot] = start
        else:
            st.small = self._prefill_zero
        return st

    def prefill_step(self, st: _PrefillState):
        """Advance one chunk (or, without chunking, the whole prompt).
        Returns ``(first_token | None, real_tokens, padded_tokens)`` —
        a non-None first token means prefill completed and the slot is
        armed for decode."""
        if not self.prefill_incremental:
            first, bucket = self._prefill_whole(
                st.slot, st.tokens, st.temperature, st.key)
            return first, st.plen, bucket
        chunk = self.prefill_chunk
        nvalid = min(chunk, st.plen - st.pos)
        final = st.pos + nvalid >= st.plen
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :nvalid] = st.tokens[st.pos:st.pos + nvalid]
        if self.layout_name == "paged":
            if self.layout.alloc_rows(st.slot, st.pos + nvalid):
                self._call_bind(st.slot)
            # arm flips the slot_live write gate on the final chunk —
            # until then decode-tick drift writes drop for this row
            self.cache, last = self._call_chunk(
                jnp.asarray(padded), st.slot, st.pos, nvalid,
                1 if final else 0)
        else:
            start = st.pos
            if start + chunk > self.max_len:
                # a padded FINAL chunk would write past the batch-1
                # cache and dynamic_update_slice clamps the start back,
                # corrupting earlier rows — shift the window back
                # instead: re-prefilled positions rewrite identical K/V
                # (same token, same position), pad rows land in
                # [plen, max_len) where decode's own write precedes any
                # attending query (the whole-bucket padding argument)
                start = self.max_len - chunk
                padded[0] = 0
                padded[0, :st.plen - start] = st.tokens[start:st.plen]
                nvalid_w = st.pos + nvalid - start

                def rewind(path, leaf):
                    if _leaf_name(path) in ("cache_index", "pos_index"):
                        return jnp.full_like(leaf, start)
                    return leaf

                st.small = jax.tree_util.tree_map_with_path(
                    rewind, st.small)
            else:
                nvalid_w = nvalid
            st.small, last = self._call_prefill(
                jnp.asarray(padded), jnp.asarray(nvalid_w, jnp.int32),
                cache0=st.small)
        st.pos += nvalid
        st.padded += chunk
        if st.pos < st.plen:
            return None, nvalid, chunk
        # final chunk: splice (dense), arm sampling state, first token
        if self.layout_name == "dense":
            self.cache = self._call_insert(
                st.small, jnp.asarray(st.slot, jnp.int32),
                jnp.asarray(st.plen, jnp.int32))
        else:
            self.layout.register_prompt(st.slot, st.tokens)
            self._host_pos[st.slot] = st.plen
            self._decoding.add(st.slot)
        first = self._arm(st.slot, last, st.temperature, st.key)
        return first, nvalid, chunk

    def _arm(self, slot: int, last_logits, temperature: float, key) -> int:
        """Sample the first token from the prefill logits and arm the
        slot's on-device sampling state."""
        nxt, new_key = self._call_sample1(
            last_logits, jnp.asarray([temperature], jnp.float32),
            jnp.asarray(key)[None])
        first = int(np.asarray(nxt)[0])
        self._tok = self._tok.at[slot].set(first)
        self._temp = self._temp.at[slot].set(float(temperature))
        self._keys = self._keys.at[slot].set(new_key[0])
        return first

    def _prefill_whole(self, slot: int, tokens: Sequence[int],
                       temperature: float, key: np.ndarray):
        """The original dense whole-prompt path: one bucketed prefill
        spliced into the slot; returns ``(first_token, bucket)``."""
        plen = len(tokens)
        bucket = self.pick_bucket(plen)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :plen] = np.asarray(tokens, np.int32)
        small, last = self._call_prefill(
            jnp.asarray(padded), jnp.asarray(plen, jnp.int32))
        self.cache = self._call_insert(
            small, jnp.asarray(slot, jnp.int32), jnp.asarray(plen, jnp.int32))
        first = self._arm(slot, last, temperature, key)
        return first, bucket

    def prefill(self, slot: int, tokens: Sequence[int], temperature: float,
                key: np.ndarray):
        """Prefill ``tokens`` into slot ``slot`` and arm its on-device
        sampling state; returns ``(first_token, padded_tokens)``.  Runs
        every chunk back-to-back — the scheduler uses the incremental
        API instead when it wants chunks interleaved with decode."""
        st = self.prefill_begin(slot, tokens, temperature, key)
        if not self.prefill_incremental:
            return self.prefill_step(st)[0], self.pick_bucket(st.plen)
        while True:
            first, _, _ = self.prefill_step(st)
            if first is not None:
                return first, st.padded

    # ---- decode / teardown ------------------------------------------------

    def step_decode(self) -> np.ndarray:
        """One compiled step over all slots; per-slot input tokens, keys
        and temperatures live on device — the only host traffic is the
        returned ``next[S]`` (the scheduler's stop checks/streaming).
        Parked rows compute too; their output is discarded.  In the
        paged layout, each decoding slot's next write position is
        covered by a just-in-time block bind BEFORE the compiled step
        (reservation guarantees the pool can serve it)."""
        if self.layout_name == "paged":
            for slot in self._decoding:
                if self.layout.alloc_rows(slot, self._host_pos[slot] + 1):
                    self._call_bind(slot)
                self._host_pos[slot] += 1
        self.cache, self._tok, self._keys = self._call_step()
        return np.asarray(self._tok)

    def reset_slot(self, slot: int) -> None:
        """Park a freed slot: zero its cursor (so it cannot creep toward
        int32 wraparound across very long serving sessions) and its
        temperature.  Parked slots still ride the compiled step; their
        writes/outputs are masked/discarded.  The paged layout also
        returns the slot's blocks to the pool (prefix-cached blocks stay
        reclaimable) and clears its device page-table row."""
        if self.layout_name == "paged":
            self.layout.release(slot)
            self._call_release(slot)
            self._decoding.discard(slot)
            self._host_pos[slot] = 0
        else:
            def leaf(path, bg):
                name = _leaf_name(path)
                if name in ("cache_index", "pos_index"):
                    return bg.at[slot].set(jnp.zeros((), bg.dtype))
                return bg

            self.cache = jax.tree_util.tree_map_with_path(leaf, self.cache)
        self._temp = self._temp.at[slot].set(0.0)

    # ---- reporting --------------------------------------------------------

    def pool_stats(self) -> dict:
        """The layout's stats: block-pool occupancy and prefix-cache
        counters for the paged layout; both layouts report their
        ``kv_quant`` storage scenario."""
        return self.layout.stats()

    def kv_cache_bytes(self) -> dict:
        """KV HBM accounting: ``reserved`` is what the cache tensors
        occupy (measured off the live leaves); ``live`` is the fraction
        actually backing live tokens (== reserved for dense — the whole
        point of the paged layout is the gap between the two);
        ``predicted`` is the layout's own sizing model
        (:func:`..serve.cache_layout.reserved_kv_bytes` — the ONE
        source of truth admission control and the benches share),
        parity-pinned against ``reserved`` by test in BOTH layouts for
        every kv_quant scenario including the int8/fp8 scale leaves."""
        from .cache_layout import reserved_kv_bytes

        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(self.cache)[0]:
            # K/V rows plus their quantization scales (the scales are
            # real HBM the quantized layouts pay — counting them keeps
            # the bytes-per-token comparison honest)
            if _leaf_name(path) in ("cached_k", "cached_v",
                                    "cached_k_scale", "cached_v_scale"):
                total += leaf.size * leaf.dtype.itemsize
        model = self.model
        predicted = reserved_kv_bytes(
            self.layout, int(model.depth),
            int(model.num_kv_heads or model.num_heads),
            int(model.dim // model.num_heads),
            jnp.dtype(model.dtype).itemsize)
        out = {"reserved": total, "live": total, "predicted": predicted}
        if self.layout_name == "paged":
            s = self.layout.stats()
            frac = s["kv_blocks_active"] / max(1, s["kv_blocks_total"])
            out["live"] = int(total * frac)
        return out

    def compile_stats(self) -> dict:
        """Compile counts per program — the no-recompile steady-state
        assertion reads ``decode_compiles == 1`` after warmup (a
        ``prewarm=True`` engine satisfies it before the first request).
        An AOT engine serves through deserialized executables instead of
        the jits, so its jit cache sizes stay 0 and ``aot_programs``
        reports the loaded pool instead.  The paged layout's prefill
        program is the chunk program; its page-table maintenance
        programs (``bind``/``release``) are reported so tests can pin
        the WHOLE pool at one compile each."""
        stats = {
            "decode_compiles": _jit_cache_size(self._step_jit),
            "insert_compiles": (
                _jit_cache_size(self._insert_jit)
                if self.layout_name == "dense" else 0),
            "aot_programs": len(self._aot),
        }
        if self.layout_name == "paged":
            stats["prefill_compiles"] = _jit_cache_size(self._chunk_jit)
            stats["bind_compiles"] = _jit_cache_size(self._bind_jit)
            stats["release_compiles"] = _jit_cache_size(self._release_jit)
        else:
            stats["prefill_compiles"] = _jit_cache_size(self._prefill_jit)
        return stats
