"""DataLoader: multiprocess input pipeline with device prefetch.

Reference analog: `python/paddle/io/reader.py:262` DataLoader +
`dataloader_iter.py` single/multi-process iterators (worker procs, blocking
queue, pinned-buffer double-buffering into the device). The TPU-native
version keeps the worker-pool design but stages batches into HBM with async
PJRT host→device transfers, double-buffered by a background thread
(SURVEY.md §7 table: "same worker-pool design, staging into HBM").
Workers produce numpy (no device context in children); the parent does the
device placement.
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import os
import queue
import threading
import traceback
from typing import Callable, Optional

import numpy as np

from ..core.tensor import Tensor
from .dataset import BatchSampler, Dataset, IterableDataset


def default_collate_fn(batch):
    """Reference: python/paddle/io/dataloader/collate.py."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._data) for s in batch])
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (str, bytes)):
        return list(batch)
    if isinstance(sample, dict):
        return {k: default_collate_fn([s[k] for s in batch]) for k in sample}
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn(list(items)) for items in zip(*batch))
    return np.asarray(batch)


def _to_device(collated):
    if isinstance(collated, np.ndarray):
        return Tensor(collated)
    if isinstance(collated, dict):
        return {k: _to_device(v) for k, v in collated.items()}
    if isinstance(collated, (list, tuple)):
        return type(collated)(_to_device(v) for v in collated)
    return collated


class WorkerInfo:
    """get_worker_info() payload inside a worker process."""

    def __init__(self, wid, dataset):
        self.id = wid
        self.dataset = dataset
        self.num_workers = int(os.environ.get("PADDLE_TPU_NUM_WORKERS", "1"))


_worker_info = None


def _worker_loop(dataset, index_queue, data_queue, collate_fn, worker_init_fn,
                 worker_id, ring_name=None):
    """ring_name set = shared-memory transport: results are pickled into
    this worker's SPSC ShmRing (core/native) instead of the mp.Queue —
    the reference's mmap worker transfer (dataloader_iter.py shared-mem
    worker pool). The queue stays as the error/fallback channel contract
    when ring_name is None."""
    import pickle

    global _worker_info
    _worker_info = WorkerInfo(worker_id, dataset)

    ring = None
    if ring_name is not None:
        from ..core import native

        ring = native.ShmRing(ring_name, create=False)

    def emit(payload):
        if ring is not None:
            try:
                ring.push(pickle.dumps(payload, protocol=5))
                return
            except ValueError:
                # batch larger than the ring: the mp.Queue relay is always
                # drained — fall back for this batch instead of failing
                pass
        data_queue.put(payload)

    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    try:
        while True:
            item = index_queue.get()
            if item is None:
                break
            batch_id, indices = item
            try:
                samples = [dataset[i] for i in indices]
                emit((batch_id, collate_fn(samples), None))
            except Exception:
                emit((batch_id, None, traceback.format_exc()))
    except EOFError:
        return  # parent closed the ring mid-push: teardown in progress
    finally:
        if ring is not None:
            ring.close()


class _MultiProcessIter:
    """Reference analog: _DataLoaderIterMultiProcess (dataloader_iter.py:~400)."""

    def __init__(self, loader):
        self._loader = loader
        self._batches = list(loader.batch_sampler)
        self._num_workers = loader.num_workers
        self._collate = loader.collate_fn or default_collate_fn
        # spawn, not fork: the parent holds the multithreaded JAX/PJRT runtime
        # and fork() of a thread-holding process can deadlock in the child
        ctx = mp.get_context("spawn")
        self._index_queues = [ctx.SimpleQueue() for _ in range(self._num_workers)]
        self._data_queue = ctx.Queue()
        self._workers = []
        # shared-memory transport (use_shared_memory=True + native lib):
        # one SPSC ring per worker; drainer threads feed the same receive
        # path the queue transport uses
        self._rings = []
        self._drainers = []
        ring_names = [None] * self._num_workers
        if getattr(loader, "use_shared_memory", False):
            from ..core import native

            if native.available():
                cap = max(1 << 26, 4 * getattr(loader, "batch_size", 1)
                          * (1 << 16))
                self._ring_cap = cap
                for wid in range(self._num_workers):
                    name = (f"/ptdl_{os.getpid()}_{id(self) & 0xffffff:x}"
                            f"_{wid}")
                    try:
                        self._rings.append(native.ShmRing(name, capacity=cap,
                                                          create=True))
                        ring_names[wid] = name
                    except OSError:
                        self._rings.append(None)
        # Workers are numpy-only: force XLA-CPU and strip the TPU runtime's
        # env so child interpreters never reach for the chip the parent holds.
        scrubbed = {"JAX_PLATFORMS": "cpu"}
        removed = [k for k in os.environ if k.startswith("TPU_")]
        saved = {k: os.environ.get(k) for k in list(scrubbed) + removed}
        try:
            os.environ.update(scrubbed)
            for k in removed:
                os.environ.pop(k, None)
            os.environ["PADDLE_TPU_NUM_WORKERS"] = str(self._num_workers)
            for wid in range(self._num_workers):
                w = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, self._index_queues[wid], self._data_queue,
                          self._collate, loader.worker_init_fn, wid,
                          ring_names[wid]),
                    daemon=True,
                )
                w.start()
                self._workers.append(w)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        # one receive funnel: ring drainer threads and the mp.Queue relay
        # both land results here, so __next__ has a single wait point
        self._recv_queue: "queue.Queue" = queue.Queue()
        self._ring_active = any(r is not None for r in self._rings)
        for ring in self._rings:
            if ring is None:
                continue
            t = threading.Thread(target=self._drain_ring, args=(ring,),
                                 daemon=True)
            t.start()
            self._drainers.append(t)
        t = threading.Thread(target=self._drain_mp_queue, daemon=True)
        t.start()
        self._drainers.append(t)
        self._send_idx = 0
        self._rcv_buffer = {}
        self._next_batch = 0
        self._prefetch_depth = max(2 * self._num_workers, 2)
        for _ in range(min(self._prefetch_depth, len(self._batches))):
            self._dispatch()
        self._shutdown = False

    def _drain_ring(self, ring):
        import pickle

        small = 1 << 20
        while True:
            try:
                try:
                    msg = ring.pop(small)
                except ValueError:
                    # message larger than the fast buffer: retry at the
                    # ring's full capacity (push guarantees <= capacity)
                    msg = ring.pop(self._ring_cap)
            except EOFError:
                return
            self._recv_queue.put(pickle.loads(msg))

    def _drain_mp_queue(self):
        while True:
            item = self._data_queue.get()
            if item is None:
                return
            self._recv_queue.put(item)

    def _dispatch(self):
        if self._send_idx < len(self._batches):
            wid = self._send_idx % self._num_workers
            self._index_queues[wid].put((self._send_idx, self._batches[self._send_idx]))
            self._send_idx += 1

    def __iter__(self):
        return self

    def __next__(self):
        if self._next_batch >= len(self._batches):
            self._teardown()
            raise StopIteration
        while self._next_batch not in self._rcv_buffer:
            try:
                batch_id, data, err = self._recv_queue.get(timeout=5.0)
            except queue.Empty:
                dead = [w for w in self._workers if not w.is_alive()]
                if dead:
                    self._teardown()
                    raise RuntimeError(
                        f"DataLoader worker(s) exited unexpectedly (exitcodes "
                        f"{[w.exitcode for w in dead]})"
                    )
                continue
            if err is not None:
                self._teardown()
                raise RuntimeError(f"DataLoader worker failed:\n{err}")
            self._rcv_buffer[batch_id] = data
        data = self._rcv_buffer.pop(self._next_batch)
        self._next_batch += 1
        self._dispatch()
        out = _to_device(data)
        return out

    def _teardown(self):
        if getattr(self, "_shutdown", False):
            return
        self._shutdown = True
        for q in self._index_queues:
            q.put(None)
        # close rings BEFORE joining: a worker blocked in push on a full
        # ring wakes with EOF and exits cleanly — terminating it mid-push
        # would orphan the (non-robust) process-shared mutex and deadlock
        # every later ring call
        for ring in self._rings:
            if ring is not None:
                ring.close()   # also wakes the drainer with EOF
        for w in self._workers:
            w.join(timeout=2)
            if w.is_alive():
                w.terminate()
        try:
            self._data_queue.put(None)  # wakes the mp-queue relay
        except Exception:
            pass
        for t in self._drainers:
            t.join(timeout=2)
        for ring in self._rings:
            if ring is not None:
                ring.free()

    def __del__(self):
        try:
            self._teardown()
        except Exception:
            pass


class _SingleProcessIter:
    def __init__(self, loader):
        self._loader = loader
        self._collate = loader.collate_fn or default_collate_fn
        self._batch_iter = iter(loader.batch_sampler)
        # double-buffer: prefetch the next device batch while the current one
        # is being consumed (the reference's create_py_reader double buffering)
        self._buffer: "queue.Queue" = queue.Queue(maxsize=loader.prefetch_factor)
        self._done = object()
        self._stop = False
        self._thread = threading.Thread(target=self._produce, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        while not self._stop:
            try:
                self._buffer.put(item, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def _produce(self):
        try:
            for indices in self._batch_iter:
                if self._stop:
                    return
                samples = [self._loader.dataset[i] for i in indices]
                if not self._put(_to_device(self._collate(samples))):
                    return
            self._put(self._done)
        except Exception:
            self._put(RuntimeError(traceback.format_exc()))

    def __iter__(self):
        return self

    def __next__(self):
        item = self._buffer.get()
        if item is self._done:
            raise StopIteration
        if isinstance(item, RuntimeError):
            raise item
        return item

    def close(self):
        # unblock the producer so abandoned iterators don't pin device batches
        self._stop = True
        try:
            while True:
                self._buffer.get_nowait()
        except queue.Empty:
            pass

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _IterableDatasetIter:
    def __init__(self, loader):
        self._loader = loader
        self._collate = loader.collate_fn or default_collate_fn
        self._it = iter(loader.dataset)
        self._batch_size = loader.batch_size
        self._drop_last = loader.drop_last

    def __iter__(self):
        return self

    def __next__(self):
        batch = list(itertools.islice(self._it, self._batch_size))
        if not batch or (self._drop_last and len(batch) < self._batch_size):
            raise StopIteration
        return _to_device(self._collate(batch))


class _TimedIter:
    """Feeds reader_cost into the profiler throughput timer (reference:
    dataloader_iter.py:298 hooks into paddle.profiler.utils.benchmark)."""

    def __init__(self, inner):
        self._inner = inner

    def __iter__(self):
        return self

    def __next__(self):
        from ..profiler import benchmark

        hub = benchmark()
        hub.before_reader()
        try:
            return next(self._inner)
        finally:
            hub.after_reader()

    def __getattr__(self, name):
        return getattr(self._inner, name)


class DataLoader:
    """Reference: python/paddle/io/reader.py:262."""

    def __init__(
        self,
        dataset: Dataset,
        feed_list=None,
        places=None,
        return_list=True,
        batch_sampler: Optional[BatchSampler] = None,
        batch_size: int = 1,
        shuffle: bool = False,
        drop_last: bool = False,
        collate_fn: Optional[Callable] = None,
        num_workers: int = 0,
        use_buffer_reader: bool = True,
        prefetch_factor: int = 2,
        use_shared_memory: bool = True,
        timeout: int = 0,
        worker_init_fn: Optional[Callable] = None,
        persistent_workers: bool = False,
    ):
        self.dataset = dataset
        self.collate_fn = collate_fn
        self.num_workers = num_workers
        self.worker_init_fn = worker_init_fn
        self.prefetch_factor = prefetch_factor
        self.batch_size = batch_size
        self.drop_last = drop_last
        # shared-memory worker transport (native ShmRing) when available;
        # silently falls back to mp.Queue otherwise — paddle's
        # use_shared_memory contract (reference: reader.py:262)
        self.use_shared_memory = use_shared_memory
        self._is_iterable = isinstance(dataset, IterableDataset)
        if self._is_iterable:
            self.batch_sampler = None
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = getattr(batch_sampler, "batch_size", batch_size)
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
            )

    def __iter__(self):
        if self._is_iterable:
            return _TimedIter(_IterableDatasetIter(self))
        if self.num_workers > 0:
            return _TimedIter(_MultiProcessIter(self))
        return _TimedIter(_SingleProcessIter(self))

    def __len__(self):
        if self._is_iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)
