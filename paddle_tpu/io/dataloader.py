"""DataLoader (reference: python/paddle/fluid/dataloader/dataloader_iter.py,
worker.py; C++ side operators/reader + blocking_queue.h).

TPU-native design: the loader is a host-side prefetch pipeline feeding numpy
batches; device transfer happens at ``to_tensor`` time (one H2D per batch).
num_workers>0 uses spawned worker processes with an index queue / result queue
pair and an in-order reordering buffer — the process topology of the
reference's _DataLoaderIterMultiProcess without the C++ blocking queue (jax
owns the device; the host queue is plain multiprocessing).
"""
from __future__ import annotations

import itertools
import os
import queue
import threading
from dataclasses import dataclass
from typing import Any, Callable, Optional

import numpy as np

from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler, SequenceSampler, RandomSampler


@dataclass
class WorkerInfo:
    id: int
    num_workers: int
    dataset: Any
    seed: int = 0


_worker_info: Optional[WorkerInfo] = None


def get_worker_info():
    return _worker_info


def _collate(batch, leaf):
    """Shared batch traversal; `leaf(ndarray) -> leaf value` decides whether
    stacked arrays become Tensors (host path) or stay numpy (worker path)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return leaf(np.stack(batch))
    if isinstance(sample, (int, np.integer)):
        return leaf(np.asarray(batch, dtype=np.int64))
    if isinstance(sample, (float, np.floating)):
        return leaf(np.asarray(batch, dtype=np.float32))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [_collate(list(items), leaf) for items in transposed]
    if isinstance(sample, dict):
        return {k: _collate([d[k] for d in batch], leaf) for k in sample}
    from ..core.tensor import Tensor

    if isinstance(sample, Tensor):
        return leaf(np.stack([np.asarray(s.numpy()) for s in batch]))
    return batch  # unknown sample types pass through unbatched


def default_collate_fn(batch):
    """Stack samples into batched Tensors (reference: collate.py)."""
    from ..core.tensor import to_tensor

    return _collate(batch, to_tensor)


def numpy_collate_fn(batch):
    """default_collate_fn's traversal producing numpy arrays only — the
    worker-process collate.  Workers must NEVER create device arrays: a
    chip belongs to one process, and the parent — the trainer — holds it,
    so a child that initializes the TPU backend fails or blocks forever
    waiting for it (this exact deadlock shipped in round 2)."""
    return _collate(batch, lambda a: a)


def _fetch_batch(dataset, indices, collate_fn):
    if isinstance(dataset, IterableDataset):
        raise RuntimeError("internal: iterable datasets fetch by iterator")
    samples = [dataset[i] for i in indices]
    return collate_fn(samples)


def _np_ify(obj):
    """Convert Tensors to numpy for cross-process transport."""
    from ..core.tensor import Tensor

    if isinstance(obj, Tensor):
        return obj.numpy()
    if isinstance(obj, (list, tuple)):
        return type(obj)(_np_ify(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _np_ify(v) for k, v in obj.items()}
    return obj


def _tensor_ify(obj):
    from ..core.tensor import to_tensor

    if isinstance(obj, np.ndarray):
        return to_tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tensor_ify(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tensor_ify(v) for k, v in obj.items()}
    return obj


_SHM_MARKER = "__shm_ring__"


def _worker_loop(dataset, index_queue, result_queue, collate_fn,
                 worker_init_fn, worker_id, num_workers, ring_name=None):
    global _worker_info
    # One process per chip (see numpy_collate_fn): if anything in this
    # child does touch jax — a user dataset or worker_init_fn may — it
    # must initialize the CPU backend, never the chip the parent holds.
    import jax

    jax.config.update("jax_platforms", "cpu")
    ring = None
    if ring_name is not None:
        try:
            from .native import ShmRing

            ring = ShmRing(ring_name)
        except Exception:
            ring = None
    _worker_info = WorkerInfo(worker_id, num_workers, dataset)
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    import pickle

    while True:
        item = index_queue.get()
        if item is None:
            break
        batch_id, indices = item
        try:
            data = _np_ify(_fetch_batch(dataset, indices, collate_fn))
            if ring is not None:
                # bulk payload rides the native shared-memory ring; the
                # queue carries only the control tuple (reference: C++
                # blocking_queue + shm numpy transport)
                try:
                    ring.push(pickle.dumps(
                        (batch_id, data), protocol=pickle.HIGHEST_PROTOCOL))
                    result_queue.put(
                        (batch_id, (_SHM_MARKER, worker_id), None))
                    continue
                except ValueError:   # batch larger than the ring
                    pass
            result_queue.put((batch_id, data, None))
        except Exception:  # propagate to parent
            import traceback

            result_queue.put((batch_id, None, traceback.format_exc()))
    if ring is not None:
        ring.close_producer()


class _MultiProcessIter:
    def __init__(self, loader):
        import multiprocessing as mp

        self.loader = loader
        ctx = mp.get_context("spawn" if loader.use_spawn else "fork")
        self.index_queues = []
        self.result_queue = ctx.Queue()
        self.workers = []
        self.batches = list(loader.batch_sampler)
        self.n_batches = len(self.batches)
        self.next_dispatch = 0
        self.next_yield = 0
        self.reorder = {}
        n = loader.num_workers
        # workers get the numpy collate unless the user supplied one
        wcollate = (numpy_collate_fn if loader.collate_fn
                    is default_collate_fn else loader.collate_fn)
        # native shared-memory transport: one SPSC ring per worker (see
        # io/native/shm_ring.cc); queue degrades gracefully when the
        # toolchain or shm is unavailable
        self.rings = [None] * n
        ring_names = [None] * n
        if loader.use_shared_memory:
            try:
                from .native import ShmRing, available

                # size rings to the tmpfs actually backing /dev/shm: the
                # segment is sparse at create time, so over-allocation
                # would SIGBUS on first touch instead of failing cleanly
                cap = 64 * 1024 * 1024
                try:
                    st = os.statvfs("/dev/shm")
                    free = st.f_bavail * st.f_frsize
                    cap = min(cap, int(free * 0.5) // max(n, 1))
                except OSError:
                    pass
                if available() and cap >= 1 * 1024 * 1024:
                    import uuid

                    base = f"/ptpu_{os.getpid()}_{uuid.uuid4().hex[:8]}"
                    for wid in range(n):
                        name = f"{base}_{wid}"
                        self.rings[wid] = ShmRing(name, capacity=cap,
                                                  create=True)
                        ring_names[wid] = name
            except Exception:
                self.rings = [None] * n
                ring_names = [None] * n
        for wid in range(n):
            iq = ctx.Queue()
            w = ctx.Process(
                target=_worker_loop,
                args=(loader.dataset, iq, self.result_queue, wcollate,
                      loader.worker_init_fn, wid, n, ring_names[wid]),
                daemon=True,
            )
            w.start()
            self.workers.append(w)
            self.index_queues.append(iq)
        # prime the pipeline
        for _ in range(min(2 * n, self.n_batches)):
            self._dispatch()

    def _dispatch(self):
        if self.next_dispatch >= self.n_batches:
            return
        wid = self.next_dispatch % len(self.workers)
        self.index_queues[wid].put(
            (self.next_dispatch, self.batches[self.next_dispatch]))
        self.next_dispatch += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self.next_yield >= self.n_batches:
            self._shutdown()
            raise StopIteration
        while self.next_yield not in self.reorder:
            batch_id, data, err = self.result_queue.get(
                timeout=self.loader.timeout or 600)
            if err is not None:
                self._shutdown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            if isinstance(data, tuple) and len(data) == 2 and \
                    data[0] == _SHM_MARKER:
                import pickle

                payload = self.rings[data[1]].pop(
                    timeout_ms=int((self.loader.timeout or 600) * 1000))
                if payload is None:
                    self._shutdown()
                    raise RuntimeError(
                        "DataLoader worker closed its shm ring before "
                        "delivering a announced batch")
                rid, data = pickle.loads(payload)
                if rid != batch_id:
                    self._shutdown()
                    raise RuntimeError(
                        f"shm ring desync: expected batch {batch_id}, "
                        f"got {rid}")
            self.reorder[batch_id] = data
        data = self.reorder.pop(self.next_yield)
        self.next_yield += 1
        self._dispatch()
        return _tensor_ify(data)

    def _shutdown(self):
        for iq in self.index_queues:
            try:
                iq.put(None)
            except Exception:
                pass
        for w in self.workers:
            w.join(timeout=1)
            if w.is_alive():
                w.terminate()
        self.workers = []
        for r in getattr(self, "rings", []):
            if r is not None:
                try:
                    r.close()
                except Exception:
                    pass
        self.rings = []

    def __del__(self):
        try:
            self._shutdown()
        except Exception:
            pass


class DataLoader:
    """paddle.io.DataLoader (reference: python/paddle/fluid/reader.py:326)."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = int(num_workers)
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.use_spawn = True
        self.use_shared_memory = bool(use_shared_memory)
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle,
                batch_size=batch_size if batch_size is not None else 1,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def __iter__(self):
        if self._iterable_mode:
            return self._iter_iterable()
        if self.num_workers > 0:
            return _MultiProcessIter(self)
        return self._iter_single()

    def _iter_single(self):
        for indices in self.batch_sampler:
            yield _fetch_batch(self.dataset, indices, self.collate_fn)

    def _iter_iterable(self):
        batch = []
        for sample in self.dataset:
            batch.append(sample)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)
