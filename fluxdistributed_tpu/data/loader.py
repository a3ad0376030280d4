"""Prefetching device-resident data loader.

TPU-native replacement for the reference's forked-Flux ``DataLoader(f,
src; buffersize=5)`` — a background task that keeps a channel of
device-resident batches filled ahead of the training loop
(src/ddp_tasks.jl:277-284; the fork is pinned in the Manifest, see
SURVEY §1).  Here: a thread pool assembles host batches (sampling +
one-hot) and ``jax.device_put``s them with the batch sharding so every
step's input is already laid out across the mesh when the train loop
asks for it — host→HBM transfer overlaps compute exactly as the
reference's prefetch loader overlapped H2D copies.

The loader owns the epoch→cycle accounting the reference does in
``prepare_training`` (``cycles = nrow*epochs ÷ ndev ÷ nsamples``,
src/ddp_tasks.jl:256).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Callable, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import mesh as mesh_lib

__all__ = ["PrefetchLoader", "batch_to_dict", "host_onehot", "model_input"]


def host_onehot(labels, nclasses: int) -> np.ndarray:
    """:func:`~fluxdistributed_tpu.ops.onehot` in numpy, for batches made
    on the host: the same float32 0/1 array for any leading shape, an
    all-zero row for a label outside ``[0, nclasses)``.  A loader thread
    that called the jitted one would queue its tiny program on the chip
    behind the running train step and block on the read-back."""
    y = np.asarray(labels)
    return (y[..., None] == np.arange(nclasses)).astype(np.float32)


def batch_to_dict(out, nclasses=None, one_hot: bool = True) -> dict:
    """Normalize a ``dataset.batch()`` return to the framework batch dict.

    THE single implementation of the three dataset protocols (tuple /
    dict / bare array) — the loader, the trainer's val draw, and init
    shape inference all go through here so the protocols cannot drift.
    """
    if isinstance(out, tuple):
        imgs, labels = out
        y = np.asarray(labels)
        if one_hot:
            if nclasses is None:
                raise ValueError(
                    "one_hot labels need nclasses (dataset lacks .nclasses)"
                )
            y = host_onehot(y, nclasses)
        return {"image": np.asarray(imgs), "label": y}
    if isinstance(out, dict):
        return {k: np.asarray(v) for k, v in out.items()}
    return {"tokens": np.asarray(out)}


def apply_transform(transform, out):
    """Dispatch a host-side batch hook per the dataset protocol: tuple
    draws unpack to ``transform(imgs, labels)``, dict/bare-array draws
    pass as one argument.  The ONE place the dispatch rule lives —
    PrefetchLoader, prepare_training, and evaluate all route through it
    so training/eval always see the same layout."""
    if transform is None:
        return out
    return transform(*out) if isinstance(out, tuple) else transform(out)


def model_input(out) -> np.ndarray:
    """The array a model's ``init`` should trace from a ``batch()`` draw:
    ``image`` / ``tokens`` by convention, else the dict's first entry."""
    d = batch_to_dict(out, one_hot=False)
    for k in ("image", "tokens"):
        if k in d:
            return d[k]
    return next(iter(d.values()))


class PrefetchLoader:
    """Iterate device-sharded batches with background prefetch.

    The dataset's ``batch(rng, n)`` return decides the batch layout:

    * ``(imgs, labels)`` tuple → ``{"image", "label"}`` (one-hot per
      ``one_hot``) — the image-classification protocol;
    * a dict of arrays → sharded as-is (each leaf's leading dim split);
    * a single array → ``{"tokens": ...}`` — the LM protocol
      (:class:`~fluxdistributed_tpu.data.SyntheticTextDataset`).

    Parameters
    ----------
    dataset: object with ``batch(rng, n)`` as above (``nclasses`` needed
        only for the tuple protocol's one-hot labels)
    mesh: the device mesh; batches are sharded on ``axis``
    batch_size: *global* batch size (reference semantics: per-device batch
        × number of devices; README.md:43's 96/device × N)
    cycles: number of batches to produce; ``None`` derives it from
        ``len(dataset) * epochs // batch_size`` (the reference's
        epoch→cycle conversion, src/ddp_tasks.jl:256)
    buffersize: prefetch depth (reference default 5, src/ddp_tasks.jl:278)
    one_hot: emit one-hot labels (the reference's ``onehotbatch``,
        src/imagenet.jl:47); integer labels otherwise
    transform: optional host-side hook, called per the dataset protocol:
        ``transform(imgs, labels)`` for tuple datasets, ``transform(out)``
        (one argument) for dict / bare-array datasets
    start: first item index to yield (resume cursor).  Batch content is
        a pure function of ``(seed, process, index)``, so a resumed run
        starting at the preempted run's ``next_item`` sees byte-identical
        batches from there on — the loss-parity contract
        (docs/robustness.md)
    retries: transient host-side assembly failures (I/O hiccups in a
        real decode pipeline; injected faults in tests) are retried this
        many times per batch before surfacing to the consumer
    """

    def __init__(
        self,
        dataset,
        mesh: Mesh,
        batch_size: int,
        cycles: Optional[int] = None,
        epochs: int = 1,
        buffersize: int = 5,
        seed: int = 0,
        axis: str = mesh_lib.DATA_AXIS,
        one_hot: bool = True,
        num_threads: int = 2,
        transform: Optional[Callable] = None,
        chunk: int = 1,
        start: int = 0,
        retries: int = 2,
    ):
        from ..sharding import axis_size, batch_entry

        n = axis_size(mesh, axis)
        if batch_size % n:
            raise ValueError(
                f"global batch {batch_size} not divisible by mesh axis '{axis}' size {n}"
            )
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.dataset = dataset
        self.mesh = mesh
        self.batch_size = batch_size
        self.buffersize = buffersize
        self.one_hot = one_hot
        self.transform = transform
        self.seed = seed
        self.num_threads = max(1, num_threads)
        # chunk > 1: the device-loop layout for steps_per_call training —
        # each yielded item stacks `chunk` per-step batches on a NEW
        # leading dim, sharded [K(replicated), batch(data axis), ...].
        # Sub-batch j of item c is bit-identical to step c*chunk+j of an
        # unchunked run (same rng derivation), so chunking never changes
        # what the model sees, only how many dispatches feed it.
        self.chunk = chunk
        if start < 0:
            raise ValueError(f"start must be >= 0, got {start}")
        self.start = start
        self.retries = max(0, retries)
        # the dim of a yielded item that holds the batch's rows, and the
        # sharding that splits it: a stacked item's rows live on dim 1
        self._batch_dim = int(chunk > 1)
        rows = batch_entry(axis)
        self.sharding = NamedSharding(
            mesh, P(None, rows) if chunk > 1 else P(rows))
        # observability: queue depth + assemble/h2d timing land in the
        # process registry so /metrics can answer "is the input pipeline
        # keeping up"; the same brackets are spans of the loader item on
        # the worker threads' own rows of the process tracer's timeline
        from ..obs import get_registry, get_tracer

        reg = get_registry()
        self._tracer = get_tracer()
        self._m_depth = reg.gauge(
            "fdtpu_data_prefetch_depth",
            "device-ready batches waiting in the prefetch queue "
            "(0 at read time = the train loop is data-bound)")
        self._m_h2d = reg.histogram(
            "fdtpu_data_h2d_seconds",
            "seconds per batch for host->device transfer (device_put "
            "inside a prefetch worker, overlapped with compute)")
        self._m_assemble = reg.histogram(
            "fdtpu_data_assemble_seconds",
            "seconds per batch for host-side assembly (sampling, "
            "decode, one-hot, transform)")
        self._m_batches = reg.counter(
            "fdtpu_data_batches_total", "batches produced by the loader")
        # Multi-host: each process assembles only its rows of the global
        # batch (the analog of each reference worker sampling its own
        # minibatch, src/sync.jl:135); jax.make_array_from_process_local_data
        # stitches them into one globally-sharded array.
        from ..parallel import multihost

        self._local_batch = multihost.local_batch_size(batch_size)
        if cycles is None:
            if not hasattr(dataset, "__len__"):
                raise ValueError(
                    f"{type(dataset).__name__} has no __len__ (an unbounded "
                    "stream, e.g. a generated token dataset) — pass cycles= "
                    "explicitly instead of deriving it from epochs"
                )
            # derived count: round down to a chunk multiple (a caller
            # never chose this exact number, so don't error on it)
            cycles = max(1, (len(dataset) * epochs) // batch_size)
            cycles = max(self.chunk, cycles // self.chunk * self.chunk)
        if cycles % self.chunk:
            raise ValueError(
                f"cycles ({cycles}) must be a multiple of chunk ({self.chunk})"
            )
        self.cycles = cycles

    # -- host-side batch assembly ------------------------------------
    def _make_batch(self, i: int):
        # Per-batch stream keyed on (seed, process, batch index): batch
        # content is a pure function of the index, so runs with the same
        # seed are bit-reproducible no matter which prefetch thread
        # assembles which batch.  Distinct per process, so hosts sample
        # different rows (the analog of the reference's per-worker
        # sampling, src/sync.jl:135).
        from .. import faults

        faults.fire("loader", index=i)
        rng = np.random.default_rng((self.seed, jax.process_index(), i))
        out = self.dataset.batch(rng, self._local_batch)
        return apply_transform(self.transform, out)

    def _make_item(self, c: int):
        """Host-side assembly of yielded item ``c``, complete down to the
        one-hot: one batch dict, or ``chunk`` consecutive step batches
        stacked on a new leading dim."""
        nclasses = getattr(self.dataset, "nclasses", None)
        ds = [
            batch_to_dict(
                self._make_batch(c * self.chunk + j), nclasses, self.one_hot
            )
            for j in range(self.chunk)
        ]
        if self.chunk == 1:
            return ds[0]
        return {k: np.stack([d[k] for d in ds]) for k in ds[0]}

    def _put(self, host: dict) -> dict:
        """``device_put`` of a finished host item's numpy leaves — the
        only thing a worker does to the device."""
        from ..parallel.multihost import global_batch_put

        return {
            k: global_batch_put(v, self.sharding, batch_dim=self._batch_dim)
            for k, v in host.items()
        }

    # -- iteration ----------------------------------------------------
    def __len__(self) -> int:
        """Number of yielded items (= optimizer steps / chunk)."""
        return self.cycles // self.chunk

    def __iter__(self) -> Iterator[dict]:
        from .. import faults

        if self.start > len(self):
            raise ValueError(
                f"start item {self.start} is past the end of the run "
                f"({len(self)} items) — a stale RESUME manifest?")
        q: queue.Queue = queue.Queue(maxsize=self.buffersize)
        counter = iter(range(self.start, len(self)))
        lock = threading.Lock()
        stop = threading.Event()

        # Backpressure: workers may run at most ``buffersize`` batches
        # ahead of the consumer (the reorder buffer would otherwise grow
        # unboundedly while the consumer waits on one slow index, holding
        # arbitrarily many device-resident batches in HBM).
        ahead = threading.Semaphore(self.buffersize)

        def worker():
            while not stop.is_set():
                if not ahead.acquire(timeout=0.5):
                    continue
                with lock:
                    i = next(counter, None)
                if i is None:
                    ahead.release()
                    break
                try:
                    # device_put from a worker thread: transfer overlaps
                    # the consumer's compute, like the reference's
                    # prefetch tasks
                    t0 = time.perf_counter()
                    # transient assembly failures (real I/O or injected
                    # via the fault plan) cost a short backoff, not the
                    # run; batch content is index-pure so a retry is
                    # bit-identical
                    with self._tracer.span("assemble", item=i,
                                           parent="item"):
                        host = faults.with_retries(
                            lambda: self._make_item(i),
                            tries=self.retries + 1, backoff=0.05,
                            site="loader")
                    t1 = time.perf_counter()
                    self._m_assemble.observe(t1 - t0)
                    with self._tracer.span("h2d", item=i, parent="item"):
                        dev = self._put(host)
                    self._m_h2d.observe(time.perf_counter() - t1)
                    self._m_batches.inc()
                    item = (i, dev, None)
                except Exception as e:  # surface to the consumer, don't die silently
                    item = (i, None, e)
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.5)
                        break
                    except queue.Full:
                        continue
                if item[2] is not None:
                    return

        threads = [
            threading.Thread(target=worker, daemon=True)
            for _ in range(self.num_threads)
        ]
        for t in threads:
            t.start()

        # Deliver strictly in batch-index order (threads may finish out of
        # order): determinism costs only a small reorder buffer.
        pending: dict = {}
        next_idx = self.start
        try:
            while next_idx < len(self):
                while next_idx not in pending:
                    i, batch, err = q.get()
                    if err is not None:
                        raise RuntimeError(
                            "prefetch worker failed while assembling a batch"
                        ) from err
                    pending[i] = batch
                # ready-ahead depth as the consumer sees it: queued items
                # plus out-of-order arrivals already buffered
                self._m_depth.set(q.qsize() + len(pending) - 1)
                yield pending.pop(next_idx)
                next_idx += 1
                ahead.release()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
