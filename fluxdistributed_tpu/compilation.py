"""Cold-start performance subsystem: persistent compile cache + AOT
executables + warmup.

The first process to touch a chip pays minutes of XLA compilation
before a single step runs, while the measurement itself takes seconds.
The compile-once/execute-many XLA contract (arXiv:1810.09868) means none
of that work is inherently per-process — this module makes it durable:

* :func:`enable_persistent_cache` — one call turns on JAX's persistent
  compilation cache (disk-backed, content-addressed by HLO + compile
  options + topology) at the ONE directory :func:`resolve_cache_dir`
  names: ``JAX_COMPILATION_CACHE_DIR`` when set, else an explicit
  ``--compile-cache DIR``, else the fixed ``.jax_cache/`` of the
  checkout.
* AOT helpers — :func:`aot_compile` (``lower → compile``),
  :func:`save_executable` / :func:`load_executable` (serialize the
  compiled XLA executable itself to disk, fingerprint-stamped), and
  :func:`load_or_compile` which falls back to a fresh compile whenever
  the topology/jaxlib fingerprint or argument signature mismatches.
  Where the persistent cache skips the *backend compile*, a serialized
  executable also skips tracing and lowering — the whole cold path.
* :func:`warmup_train` — run ONE donated dummy train step (fresh
  zero-filled buffers, the live state untouched) so every compile and
  allocator warm-up is paid before timing or traffic starts.  The serve
  side's analog is :meth:`LMEngine.warmup`.

Everything reports through the obs registry and the process tracer:
AOT loads/compiles are counters (``fdtpu_aot_loads_total`` /
``fdtpu_aot_compiles_total``) and each an ``aot`` span with its
``source``, a warm-up is a ``warmup`` span, and the cache's own hit/miss
stream lands via :mod:`obs.jaxmon`.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import re
import time
from typing import Any, Optional, Sequence

__all__ = [
    "enable_persistent_cache",
    "persistent_cache_dir",
    "resolve_cache_dir",
    "topology_fingerprint",
    "abstract_signature",
    "callable_tag",
    "config_tag",
    "aot_compile",
    "save_executable",
    "load_executable",
    "load_or_compile",
    "warmup_train",
    "compile_metrics",
]

#: format tag embedded in every serialized executable; bumping it
#: invalidates all on-disk executables at once (they fall back to a
#: fresh compile, never to a crash)
AOT_MAGIC = "fdtpu-aot-v2"

#: filename suffix for serialized executables
AOT_SUFFIX = ".jaxexec"

_cache_dir: Optional[str] = None


def topology_fingerprint(mesh=None, tag: str = "") -> str:
    """Digest of everything a serialized executable is specific to:
    jax/jaxlib versions, backend platform and device kind, device and
    process counts, optionally the mesh shape and a caller tag (e.g.
    the spmd mode knobs that change the compiled program without
    changing argument shapes).  Argument SHAPES are deliberately not
    here — :func:`abstract_signature` covers those, so the two compose
    into the on-disk key."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    parts = [
        jax.__version__,
        jaxlib.__version__,
        dev.platform,
        str(getattr(dev, "device_kind", "")),
        str(jax.device_count()),
        str(jax.process_count()),
    ]
    if mesh is not None:
        parts.append(repr(sorted(dict(mesh.shape).items())))
    if tag:
        parts.append(tag)
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


#: the variable jax itself reads for its persistent-cache directory
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when neither the variable nor an explicit
#: directory says otherwise: ONE fixed, git-ignored path in the checkout.
#: The directory is part of how a later process finds the entries again,
#: so it is never built from a temp name, a pid or a time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def resolve_cache_dir(explicit: Optional[str] = None) -> str:
    """THE compile-cache rule, shared by ``bench.py``, ``bin/driver.py``,
    ``bin/serve.py``, ``prepare_training`` and ``chip_smoke.py``:

    1. ``JAX_COMPILATION_CACHE_DIR`` set → exactly that directory (jax
       read it at import; nothing in code overrides it);
    2. else an explicit directory (``--compile-cache DIR``);
    3. else :data:`DEFAULT_CACHE_DIR`.
    """
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    if explicit:
        return os.path.abspath(os.path.expanduser(explicit))
    return DEFAULT_CACHE_DIR


def enable_persistent_cache(
    cache_dir: Optional[str] = None,
    *,
    min_entry_size_bytes: int = -1,
    min_compile_time_secs: float = 0.0,
) -> str:
    """Turn on JAX's persistent compilation cache at
    :func:`resolve_cache_dir` ``(cache_dir)`` and return that directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set this sets NO directory in
    code — jax already holds the variable's value — and only applies
    the thresholds.  Those default to "cache everything":
    ``min_entry_size_bytes=-1`` (jax's use-min-compile-time sentinel)
    and ``min_compile_time_secs=0.0`` — on TPU the compiles that matter
    are all multi-second, and on CPU (tests, smoke runs) the point is
    exactly the small entries jax's 1s default would skip.

    Call it BEFORE the first compile; when something already compiled,
    the enablement still takes effect for later compiles (jax's
    once-per-task cache-usage check is reset).
    """
    global _cache_dir
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    path = resolve_cache_dir(cache_dir)
    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_entry_size_bytes", min_entry_size_bytes)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", min_compile_time_secs)
    # jax decides once per process whether the cache is usable and then
    # memoizes the answer; clear that memo so enabling the cache AFTER
    # an early compile (a REPL, a test that ran first) still takes
    # effect for every later compile
    cc.reset_cache()
    _cache_dir = path
    # surface enablement in the registry: a scrape answers "is this
    # process even using the cache" without reading logs
    from .obs import get_registry, jaxmon

    jaxmon.install()
    get_registry().gauge(
        "fdtpu_compile_cache_enabled",
        "1 when the persistent XLA compilation cache is configured",
    ).set(1)
    return path


def persistent_cache_dir() -> Optional[str]:
    """The resolved cache directory of the last
    :func:`enable_persistent_cache` call in this process (None when the
    cache was never enabled here)."""
    return _cache_dir


def abstract_signature(args: Sequence[Any], kwargs: Optional[dict] = None) -> str:
    """Digest of the tree structure + shapes/dtypes of a call's
    arguments — the part of an executable's identity the topology
    fingerprint does not cover.  Two calls with the same signature and
    fingerprint may share a serialized executable; anything else must
    not.  fdtpu-lint's FDT204 retrace check builds on this digest: a
    program whose trace moves under a fixed signature would break these
    on-disk keys on every restart (docs/analysis.md).

    Pallas interpret-mode note: the kernels resolve "interpreter or
    compiled" at TRACE time from the backend
    (``ops.pallas_attention.interpret_mode``) rather than taking an
    ``interpret`` argument, so the flag can never appear in this digest
    — CPU- and TPU-built executables are keyed apart by the PLATFORM
    field of :func:`topology_fingerprint` instead, which is the
    deliberate split (interpretation is a consequence of the platform,
    not an independent key axis)."""
    import jax

    leaves, treedef = jax.tree.flatten((tuple(args), kwargs or {}))

    def aval(x):
        shape = tuple(getattr(x, "shape", ()))
        dtype = str(getattr(x, "dtype", type(x).__name__))
        return f"{shape}:{dtype}"

    payload = str(treedef) + "|" + ";".join(aval(x) for x in leaves)
    return hashlib.sha256(payload.encode()).hexdigest()[:16]


def callable_tag(fn, depth: int = 2) -> str:
    """Stable identity string for a configured callable: its name plus
    any scalar constants (and, one level down, callables) closed over —
    e.g. ``momentum(0.1, 0.9).update`` → ``update:0.1:0.9``.  This is
    what distinguishes two optimizers/losses whose hyperparameters are
    baked into the compiled program as constants without changing any
    argument shape.  Deliberately address-free: reprs of functions or
    objects (which embed ``0x...`` ids) never enter the tag, so the
    same configuration hashes identically across processes."""
    parts = [getattr(fn, "__name__", type(fn).__name__)]
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:  # pragma: no cover — empty cell
            continue
        if isinstance(v, (bool, int, float, str, bytes, type(None))):
            parts.append(repr(v))
        elif isinstance(v, (tuple, frozenset)) and all(
                isinstance(e, (bool, int, float, str)) for e in v):
            parts.append(repr(v))
        elif callable(v) and depth > 0:
            parts.append(callable_tag(v, depth - 1))
    return ":".join(parts)


_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def config_tag(*parts) -> str:
    """Digest arbitrary configuration parts into the short tag that
    feeds :func:`topology_fingerprint` — THE one place AOT key
    construction lives, shared by the trainer and the serve engine so
    the two cannot drift.  Callables route through :func:`callable_tag`;
    everything else stringifies with memory addresses scrubbed — a
    ``repr(model)`` whose ``attn_fn`` field prints ``<function ... at
    0x7f...>`` must hash identically across processes or on-disk
    executables are never reused."""
    norm = []
    for p in parts:
        if callable(p) and not isinstance(p, type):
            norm.append(callable_tag(p))
        else:
            norm.append(_ADDR_RE.sub("0x", str(p)))
    return hashlib.sha256("|".join(norm).encode()).hexdigest()[:12]


def aot_compile(fn, *args, **kwargs):
    """``lower → compile`` of a jitted callable at the given (concrete
    or ShapeDtypeStruct) arguments.  The result executes those argument
    shapes only — that is the point: it can be serialized."""
    if not hasattr(fn, "lower"):
        raise ValueError(
            f"{getattr(fn, '__name__', fn)!r} has no .lower — AOT "
            "compilation needs a jax.jit-wrapped callable")
    return fn.lower(*args, **kwargs).compile()


def save_executable(path: str, compiled, *, fingerprint: Optional[str] = None) -> str:
    """Serialize an AOT-compiled executable to ``path`` (atomic write).
    The file carries a format magic and the topology fingerprint;
    :func:`load_executable` refuses anything that does not match."""
    from jax.experimental.serialize_executable import serialize

    payload, in_tree, out_tree = serialize(compiled)
    blob = pickle.dumps({
        "magic": AOT_MAGIC,
        "fingerprint": fingerprint or topology_fingerprint(),
        # the devices the program was compiled for, in assignment order:
        # deserialize_and_load otherwise assumes EVERY device of the
        # backend and a 1-device program then refuses its 1-shard args
        "device_ids": [
            d.id for d in compiled.runtime_executable().local_devices()],
        "payload": payload,
        "in_tree": in_tree,
        "out_tree": out_tree,
    })
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def load_executable(path: str, *, fingerprint: Optional[str] = None):
    """Deserialize an executable saved by :func:`save_executable`.

    Returns ``None`` — never raises — on a missing/corrupt file, a
    format-magic mismatch, or a topology fingerprint mismatch: every
    load site falls back to a fresh compile, so a stale artifact can
    only ever cost the compile it failed to save."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load

    expected = fingerprint or topology_fingerprint()
    try:
        with open(path, "rb") as f:
            blob = pickle.loads(f.read())
        if blob.get("magic") != AOT_MAGIC or blob.get("fingerprint") != expected:
            return None
        by_id = {d.id: d for d in jax.devices()}
        return deserialize_and_load(
            blob["payload"], blob["in_tree"], blob["out_tree"],
            execution_devices=[by_id[i] for i in blob["device_ids"]])
    except Exception:  # noqa: BLE001 — any load failure means "recompile"
        return None


def load_or_compile(
    fn,
    args: Sequence[Any] = (),
    kwargs: Optional[dict] = None,
    *,
    directory: str,
    name: str,
    fingerprint: Optional[str] = None,
    save: bool = True,
    registry=None,
):
    """The AOT workflow in one call: look for a serialized executable of
    ``fn`` at these arguments under ``directory``, else lower + compile
    (and serialize the result for the next process).

    The on-disk key is ``<name>-<topology fp>-<argument signature>`` —
    a jaxlib upgrade, a different device count, or a shape change each
    select a different file, so a mismatch is an automatic miss, not a
    crash.  Outcomes are counted in the obs registry
    (``fdtpu_aot_loads_total`` / ``fdtpu_aot_compiles_total``); the
    seconds are the ``aot`` span's, whose ``source`` says ``"load"`` or
    ``"compile"`` (a failed look for a file counts with the compile).
    """
    from .obs import get_registry, get_tracer

    reg = registry or get_registry()
    fp = fingerprint or topology_fingerprint()
    sig = abstract_signature(args, kwargs)
    path = os.path.join(directory, f"{name}-{fp}-{sig}{AOT_SUFFIX}")
    with get_tracer().span("aot", source="load") as span:
        compiled = load_executable(path, fingerprint=fp)
        if compiled is not None:
            reg.counter(
                "fdtpu_aot_loads_total",
                "AOT executables deserialized from disk (compile skipped)",
            ).inc()
            return compiled
        span.args["source"] = "compile"
        compiled = aot_compile(fn, *args, **(kwargs or {}))
    reg.counter(
        "fdtpu_aot_compiles_total",
        "AOT executables compiled fresh (no matching serialized file)",
    ).inc()
    if save:
        try:
            save_executable(path, compiled, fingerprint=fp)
        except Exception as e:  # noqa: BLE001 — serialization is best-effort
            import sys

            print(f"compilation: could not serialize {name!r} to {path}: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
    return compiled


def _sharded_zeros_like(tree):
    """Fresh zero-filled buffers with the SAME shardings as ``tree``,
    assembled shard-by-shard: a model whose state only fits sharded
    never materializes a dense copy on one device, and mixed device
    sets across leaves (a replicated param tree next to a
    single-device step counter) are fine — each leaf is built
    independently."""
    import jax
    import numpy as np

    def shard_shape(shape, idx):
        out = list(shape)
        for d, sl in enumerate(idx):
            start, stop, _ = sl.indices(shape[d])
            out[d] = max(0, stop - start)
        return tuple(out)

    def zeros(x):
        if not isinstance(x, jax.Array):
            return x
        return jax.make_array_from_callback(
            x.shape, x.sharding,
            lambda idx: np.zeros(shard_shape(x.shape, idx), dtype=x.dtype))

    return jax.tree.map(zeros, tree)


def warmup_train(task, batch, *, eval_too: bool = True) -> dict:
    """Pre-pay the training cold start: run ONE optimizer step on
    donated dummy inputs (zero-filled copies with the live state's
    shardings — the real :class:`TrainState` is never touched, so this
    composes with ``donate=True`` steps) and block until it lands.

    ``batch`` must have the exact layout training will feed (the
    trainer's ``prepare_training(warmup=True)`` builds it from the
    dataset).  With ``eval_too`` the compiled eval step warms up
    against the task's val batch when one exists.

    Returns ``{"seconds": ..., "compiles": ..., "compile_seconds": ...}``
    — what the cold start actually cost, so callers can log it against
    the steps it saves.
    """
    import jax

    from .obs import get_tracer, jaxmon

    jaxmon.install()
    c0, s0 = jaxmon.compile_count(), jaxmon.compile_seconds()
    t0 = time.perf_counter()
    with get_tracer().span("warmup"):
        dummy_state = _sharded_zeros_like(task.state)
        out = task.step_fn(dummy_state, batch)
        jax.block_until_ready(jax.tree.leaves(out))
        if eval_too and task.val_batch is not None:
            # the dummy state was (possibly) donated to the step above —
            # eval gets its own fresh zeros
            ev = task.eval_fn(_sharded_zeros_like(task.state), task.val_batch)
            jax.block_until_ready(jax.tree.leaves(ev))
    return {
        "seconds": time.perf_counter() - t0,
        "compiles": jaxmon.compile_count() - c0,
        "compile_seconds": jaxmon.compile_seconds() - s0,
    }


def compile_metrics() -> dict:
    """The cold-start ledger of this process, from the jaxmon counters:
    compile count/seconds plus persistent-cache hits/misses and the
    compile seconds the cache saved.  The bench harness embeds this in
    its JSON line (success AND timeout paths) so a dead round says
    whether the time went to compilation or to the hardware."""
    from .obs import jaxmon

    jaxmon.install()
    return {
        "compiles": int(jaxmon.compile_count()),
        "compile_seconds": round(jaxmon.compile_seconds(), 3),
        "cache_hits": int(jaxmon.cache_hits()),
        "cache_misses": int(jaxmon.cache_misses()),
        "compile_seconds_saved": round(jaxmon.compile_seconds_saved(), 3),
    }
