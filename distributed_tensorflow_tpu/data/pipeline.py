"""Host-side input pipeline: batching, shuffling, device prefetch.

The reference has no input pipeline at all — batches are contiguous Python
list slices fed through ``feed_dict`` every step (reference
example.py:207-213), a per-step host→runtime transfer on the hot path.
On TPU that synchronous feed is the anti-pattern (SURVEY.md §7): here the
iterator stays on the host but ``prefetch_to_device`` keeps a small queue of
batches already resident (and already laid out with the right sharding), so
the compiled step never waits on PCIe/DCN.

Also unlike the reference (which never reshuffles between epochs), epochs are
reshuffled with a per-epoch PRNG fold-in, and each process sees only its own
shard of the global batch (``process_shard``) for multi-host feeding.
"""
from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator, Sequence, Tuple

import jax
import numpy as np

from ..obs import goodput as goodput_lib
from ..resilience import faults as faults_lib

__all__ = ["Dataset", "prefetch_to_device"]


class Dataset:
    """In-memory (x, y) dataset with shuffled minibatch iteration.

    ``backend``: ``"numpy"`` (default) is the portable pure-Python path with
    the documented (seed, epoch) numpy shuffle stream — same batches on every
    machine.  ``"auto"`` opts into the native C++ threaded gather loader
    (``utils.native.NativeLoader``) when the library is available and the
    dataset shape fits it (1–2 arrays, full batches), falling back to numpy
    otherwise — NOTE its shuffle stream differs from numpy's, so same-seed
    runs are only reproducible within one backend.  ``"native"`` requires
    the native path (raises if unavailable).
    """

    def __init__(self, arrays: Sequence[np.ndarray], batch_size: int,
                 shuffle: bool = True, drop_remainder: bool = True,
                 seed: int = 0, process_index: int = 0,
                 process_count: int = 1, backend: str = "numpy",
                 transform=None):
        n = arrays[0].shape[0]
        for a in arrays:
            if a.shape[0] != n:
                raise ValueError("all arrays must share the leading dim")
        if process_count > 1:
            # Per-process shard of the data (between-graph replication's
            # "each worker reads its own slice", minus the PS).
            shard = n // process_count
            lo = process_index * shard
            arrays = [a[lo:lo + shard] for a in arrays]
            n = shard
        self.arrays = list(arrays)
        self.n = n
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_remainder = drop_remainder
        self.seed = seed
        self.epoch = 0
        if backend not in ("auto", "native", "numpy"):
            raise ValueError(f"unknown backend {backend!r}")
        self.backend = backend
        if backend == "native" and not self._native_usable():
            raise RuntimeError(
                "backend='native' but the native loader is unavailable or "
                "the dataset shape does not fit it")
        # Per-batch augmentation (data.augment.compose(...)); runs on the
        # host after gather, on BOTH the numpy and native paths.
        self.transform = transform

    def _native_usable(self) -> bool:
        from ..utils import native
        return (len(self.arrays) in (1, 2) and self.drop_remainder
                and self.n >= self.batch_size and native.native_available())

    @property
    def batches_per_epoch(self) -> int:
        if self.drop_remainder:
            return self.n // self.batch_size
        return -(-self.n // self.batch_size)

    def __len__(self) -> int:
        return self.batches_per_epoch

    def __iter__(self) -> Iterator[Tuple[np.ndarray, ...]]:
        if self.backend != "numpy" and self._native_usable():
            yield from self._iter_native()
            return
        if self.shuffle:
            rng = np.random.default_rng((self.seed, self.epoch))
            order = rng.permutation(self.n)
        else:
            order = np.arange(self.n)
        t_rng = np.random.default_rng((self.seed, self.epoch, 1))
        self.epoch += 1
        stop = (self.n - self.batch_size + 1 if self.drop_remainder
                else self.n)
        for lo in range(0, stop, self.batch_size):
            idx = order[lo:lo + self.batch_size]
            batch = tuple(a[idx] for a in self.arrays)
            yield batch if self.transform is None \
                else self.transform(t_rng, batch)

    def _iter_native(self) -> Iterator[Tuple[np.ndarray, ...]]:
        """One epoch through the C++ threaded gather loader; a fresh loader
        per epoch with a seed fold-in keeps the per-epoch reshuffle contract
        of the numpy path (and makes partial epoch consumption safe)."""
        from ..utils import native
        x = self.arrays[0]
        y = self.arrays[1] if len(self.arrays) == 2 else None
        seed = (self.seed * 1_000_003 + self.epoch) & 0xFFFFFFFFFFFFFFFF
        t_rng = np.random.default_rng((self.seed, self.epoch, 1))
        self.epoch += 1
        loader = native.NativeLoader(x, y, self.batch_size, seed=seed,
                                     shuffle=self.shuffle)
        try:
            for _ in range(loader.batches_per_epoch):
                batch = loader.next()
                yield batch if self.transform is None \
                    else self.transform(t_rng, batch)
        finally:
            loader.close()

    def epochs(self, num_epochs: int) -> Iterator[Tuple[np.ndarray, ...]]:
        for _ in range(num_epochs):
            yield from self


def prefetch_to_device(iterator: Iterable, size: int = 2,
                       sharding=None, sharding_fn=None) -> Iterator:
    """Asynchronously stage upcoming batches onto device(s).

    A background thread uploads with ``jax.device_put`` (laid out per
    ``sharding`` when given, so multi-chip batches land already sharded over
    the mesh's data axis) while the current step computes — replacing the
    reference's per-step synchronous ``feed_dict`` upload.

    ``sharding_fn``: optional ``item -> sharding`` override for streams
    whose items need different layouts (Sequential's steps_per_execution
    mixes [K, batch, ...] groups with plain-batch epoch tails).

    The consumer may abandon the generator at any point (break out of an
    epoch, ``.close()``, garbage collection): the producer thread is
    unblocked and terminated, releasing the up-to-``size`` device
    batches it was pinning.  Handoff is a blocking ``queue.Queue`` —
    no busy-polling on either side.
    """
    # Unbounded handoff queue + a semaphore bounding device-RESIDENT
    # batches to ``size``: the capacity ticket is taken BEFORE the
    # device_put, so at most ``size`` uploaded batches exist at once
    # (a bounded queue would admit size+1: one blocked mid-put).
    handoff: queue.Queue = queue.Queue()
    sem = threading.Semaphore(size)
    stop = threading.Event()
    done = object()
    err: list = []

    def put(item):
        sh = sharding_fn(item) if sharding_fn is not None else sharding
        if sh is not None and jax.process_count() > 1:
            # Multi-host: each process holds only its local shard; assemble
            # the global array from per-process data.
            return jax.tree.map(
                lambda a: jax.make_array_from_process_local_data(sh, a),
                item)
        return jax.device_put(item, sh)

    def producer():
        try:
            for item in iterator:
                plan = faults_lib.active()
                if plan is not None:
                    # chaos harness: may poison this batch or kill this
                    # producer (the raise lands in err[] below and the
                    # consumer re-raises — the real dead-producer path)
                    item = plan.on_batch(item)
                sem.acquire()
                # checked after acquire: an abandoning consumer releases
                # the semaphore once to unblock exactly this wait
                if stop.is_set():
                    return
                handoff.put(put(item))
        except Exception as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            handoff.put(done)

    thread = threading.Thread(target=producer, daemon=True,
                              name="dttpu-prefetch")
    thread.start()

    try:
        while True:
            # goodput "data_stall" == the "data.prefetch_wait" span: the
            # consumer's blocking wait on the handoff IS the
            # input-starvation time (a full queue returns immediately
            # and accrues ~nothing); closed before the yield so the
            # caller's step time never lands here
            with goodput_lib.account("data_stall"):
                item = handoff.get()     # blocking handoff, no poll
            if item is done:
                if err:
                    raise err[0]
                return
            yield item               # GeneratorExit lands here on close
            sem.release()
    finally:
        # Normal exhaustion, consumer abandonment, or an error: wake the
        # producer if it is parked in sem.acquire and let it exit.
        stop.set()
        sem.release()
        thread.join(timeout=5.0)
