"""EVA attention (Zheng, Yuan, Wang, Kong, *Efficient Attention via
Control Variates*, arXiv:2302.04542) as EvaByte trains it: a row is cut
into windows and the windows into chunks; a query attends the keys of
its own window exactly (causally) and every earlier window as one
learned summary a chunk, under ONE softmax over both.

For chunk ``c`` (``chunk`` positions), head ``a`` with learned ``mu_a``,
``phi_a``: ``k~_c = sum_m softmax_m(mu_a . k_m) k_m`` and ``v~_c = sum_m
softmax_m(phi_a . k_m) v_m`` (:func:`chunk_summaries`, float32 inside).
A query at ``t`` in window ``w = t // window`` sees the keys ``m <= t``
of window ``w`` and the summaries of the ``w * window / chunk`` chunks
of all earlier windows; none of its own, so the layer is causal.

Two ways to the same numbers (:func:`eva_attention`):

* ``impl="pallas"``: no new kernel.  The exact part is one causal flash
  call on windows folded into rows (``[B * T / window, window, H, D]``).
  The summarised part falls on whole windows: the queries of window
  ``w`` see the first ``w * window / chunk`` summaries and no other, so
  it is one NON-causal flash call a window after the first, each over
  tiles that are all inside the band (no mask, nothing dead).  The two
  parts are merged by their ``lse`` (:func:`merge_by_lse`), which
  ``flash_attention_lse`` hands back differentiably.
* ``impl="xla"``: one masked softmax over the concatenated keys, the
  plain path for the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.metrics import get_registry
from .attention import NEG_INF

__all__ = ["chunk_summaries", "merge_by_lse", "eva_attention", "eva_pairs"]


def eva_pairs(t: int, window: int, chunk: int) -> dict:
    """Attended (query, key) pairs of one row and head, by part: the
    causal squares of the row's windows, and every query of window ``w``
    against the ``w * window / chunk`` summaries before it."""
    nw, per_window = t // window, window // chunk
    return {"local": nw * (window * (window + 1) // 2),
            "summary": per_window * window * (nw * (nw - 1) // 2)}


def _publish_pairs(t, window, chunk):
    gauge = get_registry().gauge(
        "fdtpu_eva_pairs", "attended query-key pairs of one row and head "
        "of the EVA layer traced last, by part", ("part",))
    for part, n in eva_pairs(t, window, chunk).items():
        gauge.labels(part).set(n)


def chunk_summaries(k, v, mu, phi, chunk: int):
    """``k``, ``v`` [B, T, H, D]; ``mu``, ``phi`` [H, D] -> the chunks'
    pooled keys and values, each [B, T / chunk, H, D] in ``k``'s type.
    Both softmaxes run over a chunk's positions in float32."""
    b, t, h, d = k.shape
    f32 = jnp.float32
    kc = k.astype(f32).reshape(b, t // chunk, chunk, h, d)
    vc = v.astype(f32).reshape(b, t // chunk, chunk, h, d)
    wk = jax.nn.softmax(jnp.sum(kc * mu.astype(f32), axis=-1), axis=2)
    wv = jax.nn.softmax(jnp.sum(kc * phi.astype(f32), axis=-1), axis=2)
    ksum = jnp.sum(wk[..., None] * kc, axis=2)
    vsum = jnp.sum(wv[..., None] * vc, axis=2)
    return ksum.astype(k.dtype), vsum.astype(v.dtype)


def merge_by_lse(o1, lse1, o2, lse2):
    """Two softmaxes over disjoint keys as the one over both: ``o =
    (e^l1 o1 + e^l2 o2) / (e^l1 + e^l2)``, exact.  ``o`` [B, T, H, D],
    ``lse`` [B, H, T] float32.  A part with no key (``lse`` about -1e30,
    as ``flash_attention_lse`` gives a row that attends nothing) gets a
    weight that underflows to exactly nought."""
    lse = jnp.logaddexp(lse1, lse2)
    w1 = jnp.exp(lse1 - lse).transpose(0, 2, 1)[..., None]
    w2 = jnp.exp(lse2 - lse).transpose(0, 2, 1)[..., None]
    out = w1 * o1.astype(jnp.float32) + w2 * o2.astype(jnp.float32)
    return out.astype(o1.dtype)


def _eva_xla(q, k, v, ksum, vsum, window, chunk):
    t, d = q.shape[1], q.shape[-1]
    f32 = jnp.float32
    scale = 1.0 / (d ** 0.5)
    pos = jnp.arange(t)
    w = pos // window
    local = (w[:, None] == w[None, :]) & (pos[None, :] <= pos[:, None])
    first = jnp.arange(t // chunk) * chunk  # a chunk's first position
    remote = (first // window)[None, :] < w[:, None]
    keys = jnp.concatenate([k, ksum], axis=1).astype(f32)
    vals = jnp.concatenate([v, vsum], axis=1).astype(f32)
    s = scale * jnp.einsum("bqhd,bkhd->bhqk", q.astype(f32), keys)
    s = jnp.where(jnp.concatenate([local, remote], axis=1), s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vals).astype(q.dtype)


def _eva_pallas(q, k, v, ksum, vsum, window, chunk, block_q, block_k):
    from .pallas_attention import flash_attention_lse

    b, t, h, d = q.shape
    nw, per_window = t // window, window // chunk
    fold = lambda x: x.reshape(b * nw, window, h, d)  # noqa: E731
    with jax.named_scope("fdtpu/eva_local"):
        o1, l1 = flash_attention_lse(fold(q), fold(k), fold(v), True,
                                     block_q, block_k)
    o1 = o1.reshape(b, nw, window, h, d)
    l1 = l1.reshape(b, nw, h, window)
    outs = [o1[:, 0]]  # the first window has nothing before it
    for w in range(1, nw):
        tk = w * per_window
        # every tile inside: the whole of the summaries where they make
        # one block, else blocks that divide a window's share of them
        bk = tk if tk <= block_k else (
            block_k if tk % block_k == 0 else per_window)
        with jax.named_scope("fdtpu/eva_remote"):
            o2, l2 = flash_attention_lse(
                q[:, w * window:(w + 1) * window], ksum[:, :tk], vsum[:, :tk],
                False, block_q, bk)
        with jax.named_scope("fdtpu/eva_merge"):
            outs.append(merge_by_lse(o1[:, w], l1[:, w], o2, l2))
    return jnp.concatenate(outs, axis=1)


def eva_attention(q, k, v, mu, phi, *, window: int, chunk: int,
                  impl: str = "xla", block_q: int = 128, block_k: int = 128):
    """``q``, ``k``, ``v`` [B, T, H, D] (rotary positions applied),
    ``mu``, ``phi`` [H, D] -> [B, T, H, D].  ``T`` a multiple of
    ``window``, ``window`` of ``chunk``."""
    if impl not in ("xla", "pallas"):
        raise ValueError(f"unknown attention_impl {impl!r} (xla|pallas)")
    t = q.shape[1]
    if window % chunk:
        raise ValueError(f"window_size ({window}) must be a multiple of "
                         f"chunk_size ({chunk})")
    if t % window:
        raise ValueError(
            f"a row of {t} positions is no multiple of window_size "
            f"({window}): EVA attention takes whole windows")
    _publish_pairs(t, window, chunk)
    with jax.named_scope("fdtpu/eva_summaries"):
        ksum, vsum = chunk_summaries(k, v, mu, phi, chunk)
    if impl == "pallas":
        return _eva_pallas(q, k, v, ksum, vsum, window, chunk,
                           block_q, block_k)
    return _eva_xla(q, k, v, ksum, vsum, window, chunk)
