"""Data pipeline (reference: `python/paddle/fluid/reader.py:113-954` —
DataLoader.from_generator feeding a C++ blocking queue; multiprocess
dataloader in `fluid/dataloader/dataloader_iter.py`).

TPU-native: the bottleneck to hide is host->HBM transfer; DataLoader
prefetches batches through the C++ native blocking channel
(paddle_tpu.core.native.NativeChannel — the analogue of the reference's
lod_tensor_blocking_queue) on a background thread, and map-style loading
fans out to multiprocess workers like the reference's _DataLoaderIter.
With `use_double_buffer` and an accelerator place, the double buffer now
extends past the host channel into HBM: a second stage
(reader/prefetcher.py) issues non-blocking `jax.device_put`s
`FLAGS_tpu_prefetch_depth` batches ahead, so the consuming step finds
its feeds already on device (reference analogue:
`operators/reader/buffered_reader.cc`'s device-side copy stream).
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import queue as _queue
import threading
from typing import Callable, List, Optional

import numpy as np


class _ReaderError:
    """Wraps an exception raised in the producer thread so the consumer
    re-raises it instead of seeing a silently truncated epoch."""

    def __init__(self, exc):
        self.exc = exc


def _default_collate(samples):
    first = samples[0]
    if isinstance(first, (list, tuple)):
        return [np.stack([np.asarray(s[i]) for s in samples])
                for i in range(len(first))]
    return np.stack([np.asarray(s) for s in samples])


class DataLoaderBase:
    def __iter__(self):
        raise NotImplementedError


class _PrefetchQueue:
    """Bounded blocking handoff between the producer thread and the
    consumer. Same-process, so items pass by reference through a python
    queue — the C++ NativeChannel is reserved for paths that cross a
    language/process boundary (the native MultiSlotDataFeed uses it
    internally), where its byte-buffer semantics pay for themselves."""

    def __init__(self, capacity: int):
        self._q = _queue.Queue(maxsize=capacity)
        self._stop = object()

    def push(self, item):
        self._q.put(item)

    def close(self):
        self._q.put(self._stop)

    def __iter__(self):
        while True:
            item = self._q.get()
            if item is self._stop:
                return
            yield item


class _GeneratorLoader(DataLoaderBase):
    def __init__(self, feed_list=None, capacity=64, use_double_buffer=True,
                 iterable=True, return_list=False, drop_last=True):
        self._feed_list = feed_list or []
        self._capacity = capacity
        self._iterable = iterable
        self._return_list = return_list
        self._batch_reader = None
        self._places = None
        self._use_double_buffer = use_double_buffer

    # -- wiring ------------------------------------------------------------
    def set_sample_generator(self, reader, batch_size, drop_last=True,
                             places=None):
        def batched():
            batch = []
            for sample in reader():
                batch.append(sample if isinstance(sample, (list, tuple))
                             else (sample,))
                if len(batch) == batch_size:
                    yield [np.stack([b[i] for b in batch])
                           for i in range(len(batch[0]))]
                    batch = []
            if batch and not drop_last:
                yield [np.stack([b[i] for b in batch])
                       for i in range(len(batch[0]))]

        self._batch_reader = batched
        self._places = places
        return self

    def set_sample_list_generator(self, reader, places=None):
        def batched():
            for samples in reader():
                n = len(samples[0])
                yield [np.stack([np.asarray(s[i]) for s in samples])
                       for i in range(n)]

        self._batch_reader = batched
        self._places = places
        return self

    def set_batch_generator(self, reader, places=None):
        self._batch_reader = reader
        self._places = places
        return self

    # -- iteration ---------------------------------------------------------
    def _device_buffered(self):
        """True when the host double buffer should extend to HBM: the
        loader targets an accelerator place (host numpy stays the
        contract for CPU places — dygraph consumers expect it)."""
        if not self._use_double_buffer:
            return False
        places = self._places
        if places is None:
            return False
        from ..core.place import CUDAPlace, TPUPlace

        seq = places if isinstance(places, (list, tuple)) else [places]
        return any(isinstance(p, (TPUPlace, CUDAPlace)) for p in seq)

    def _host_iter(self):
        q = _PrefetchQueue(self._capacity)

        def produce():
            try:
                for batch in self._batch_reader():
                    q.push(batch)
            except BaseException as e:  # surface reader errors downstream
                q.push(_ReaderError(e))
            finally:
                q.close()

        t = threading.Thread(target=produce, daemon=True)
        t.start()

        feed_names = [getattr(v, "name", v) for v in self._feed_list]
        for item in q:
            if isinstance(item, _ReaderError):
                raise RuntimeError(
                    "DataLoader generator raised") from item.exc
            if isinstance(item, dict):
                yield item
            elif feed_names and not self._return_list:
                yield dict(zip(feed_names, item))
            else:
                yield item

    def __iter__(self):
        if self._batch_reader is None:
            raise RuntimeError("DataLoader: no generator set")
        if not self._device_buffered():
            yield from self._host_iter()
            return
        from ..reader.prefetcher import prefetch_to_device

        pf = prefetch_to_device(self._host_iter())
        try:
            yield from pf
        finally:
            pf.close()  # early break drains in-flight device buffers

    def start(self):
        pass

    def reset(self):
        pass


def _worker_loop(dataset, collate_fn, index_queue, result_queue,
                 worker_init_fn, worker_id):
    """Runs in a child process: pull index batches, push collated arrays
    (reference: dataloader/dataloader_iter.py _worker_loop)."""
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    collate = collate_fn or _default_collate
    while True:
        job = index_queue.get()
        if job is None:
            break
        batch_idx, indices = job
        try:
            samples = [dataset[int(i)] for i in indices]
            result_queue.put((batch_idx, collate(samples), None))
        except Exception as e:  # surface worker errors to the parent
            result_queue.put((batch_idx, None, repr(e)))
    result_queue.put((None, worker_id, None))  # worker-done marker


class DataLoader:
    @staticmethod
    def from_generator(feed_list=None, capacity=64, use_double_buffer=True,
                       iterable=True, return_list=False,
                       use_multiprocess=False, drop_last=True):
        return _GeneratorLoader(feed_list, capacity, use_double_buffer,
                                iterable, return_list, drop_last)

    @staticmethod
    def from_dataset(dataset, places, drop_last=True):
        raise NotImplementedError("dataset loader: use train_from_dataset")

    def __init__(self, dataset=None, feed_list=None, places=None,
                 return_list=False, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, timeout=0,
                 worker_init_fn=None):
        # map-style dataset loader (2.0 API)
        self._dataset = dataset
        self._batch_size = batch_size
        self._batch_sampler = batch_sampler
        self._shuffle = shuffle
        self._drop_last = drop_last
        self._return_list = return_list
        self._feed_list = feed_list or []
        self._collate = collate_fn
        self._num_workers = max(0, int(num_workers))
        self._timeout = timeout
        self._worker_init_fn = worker_init_fn
        self._places = places
        self._use_buffer_reader = use_buffer_reader

    def _batches(self):
        if self._batch_sampler is not None:
            yield from self._batch_sampler
            return
        n = len(self._dataset)
        idx = np.arange(n)
        if self._shuffle:
            np.random.shuffle(idx)
        for i in range(0, n, self._batch_size):
            sel = idx[i:i + self._batch_size]
            if len(sel) < self._batch_size and self._drop_last:
                continue
            yield sel

    def _device_buffered(self):
        """Map-style analogue of _GeneratorLoader._device_buffered: with
        `use_buffer_reader` (the default) and an accelerator place, the
        buffer reader extends past host numpy into HBM — batches arrive
        as pre-put jax arrays (reader/prefetcher.py issues the async
        device_puts) and the dygraph train loops consume them without a
        host round-trip (hapi _as_variables / to_variable pass device
        arrays through)."""
        if not self._use_buffer_reader:
            return False
        places = self._places
        if places is None:
            return False
        from ..core.place import CUDAPlace, TPUPlace

        seq = places if isinstance(places, (list, tuple)) else [places]
        return any(isinstance(p, (TPUPlace, CUDAPlace)) for p in seq)

    def _iter_host(self):
        if self._num_workers == 0:
            collate = self._collate or _default_collate
            for sel in self._batches():
                yield collate([self._dataset[int(j)] for j in sel])
            return
        yield from self._iter_multiprocess()

    def __iter__(self):
        if not self._device_buffered():
            yield from self._iter_host()
            return
        from ..reader.prefetcher import prefetch_to_device

        pf = prefetch_to_device(self._iter_host())
        try:
            yield from pf
        finally:
            pf.close()  # early break drains in-flight device buffers

    def _iter_multiprocess(self):
        """Fan out to worker processes; results are reordered so batch
        order matches the single-process loader."""
        ctx = mp.get_context("fork")
        n_workers = self._num_workers
        index_queues = [ctx.Queue() for _ in range(n_workers)]
        result_queue = ctx.Queue()
        workers = [
            ctx.Process(target=_worker_loop,
                        args=(self._dataset, self._collate, index_queues[w],
                              result_queue, self._worker_init_fn, w),
                        daemon=True)
            for w in range(n_workers)
        ]
        for w in workers:
            w.start()
        try:
            # bounded dispatch: at most prefetch_window index batches are
            # outstanding, so results (and the reorder buffer) stay
            # O(window) rather than O(epoch) when the consumer is slower
            # than the workers (reference: _DataLoaderIter prefetch depth)
            prefetch_window = 2 * n_workers
            batch_iter = enumerate(self._batches())
            sent = 0
            exhausted = False

            def dispatch_one():
                nonlocal sent, exhausted
                if exhausted:
                    return
                try:
                    batch_idx, sel = next(batch_iter)
                except StopIteration:
                    exhausted = True
                    for q in index_queues:
                        q.put(None)
                    return
                index_queues[batch_idx % n_workers].put(
                    (batch_idx, [int(i) for i in sel]))
                sent += 1

            for _ in range(prefetch_window):
                dispatch_one()

            reorder = {}
            next_idx = 0
            done_ids = set()
            timeout = self._timeout if self._timeout else None
            while not (exhausted and next_idx >= sent):
                if next_idx in reorder:
                    yield reorder.pop(next_idx)
                    next_idx += 1
                    dispatch_one()
                    continue
                try:
                    batch_idx, data, err = result_queue.get(
                        timeout=timeout or 5.0)
                except _queue.Empty:
                    if timeout:
                        raise RuntimeError(
                            "DataLoader timed out after %ss" % timeout)
                    dead = [w.pid for wid, w in enumerate(workers)
                            if wid not in done_ids and not w.is_alive()]
                    if dead:
                        raise RuntimeError(
                            "DataLoader worker(s) %s died unexpectedly "
                            "(killed / crashed) before finishing" % dead)
                    continue
                if batch_idx is None:
                    done_ids.add(data)  # data slot carries the worker id
                    if len(done_ids) == n_workers and next_idx < sent \
                            and not reorder:
                        raise RuntimeError("DataLoader workers exited "
                                           "before producing all batches")
                    continue
                if err is not None:
                    raise RuntimeError("DataLoader worker failed: " + err)
                reorder[batch_idx] = data
        finally:
            for w in workers:
                if w.is_alive():
                    w.terminate()
            for w in workers:
                # a worker forked from a many-threaded process may never
                # act on SIGTERM (seen under pytest-xdist: the join below
                # waited for ever), so one that outlives its grace is
                # killed outright
                w.join(timeout=5.0)
                if w.is_alive():
                    w.kill()
                    w.join()

    def __len__(self):
        if self._batch_sampler is not None:
            return len(self._batch_sampler)
        n = len(self._dataset)
        if self._drop_last:
            return n // self._batch_size
        return (n + self._batch_size - 1) // self._batch_size


class BatchSampler:
    """Reference: fluid/dataloader/batch_sampler.py BatchSampler."""

    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self._n = len(dataset) if dataset is not None else None
        # materialize once: a generator sampler must survive repeated
        # __len__/__iter__ calls
        self._indices = list(sampler) if sampler is not None else None
        self._shuffle = shuffle
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __iter__(self):
        if self._indices is not None:
            idx = self._indices
        else:
            idx = np.arange(self._n)
            if self._shuffle:
                np.random.shuffle(idx)
        batch = []
        for i in idx:
            batch.append(int(i))
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = self._n if self._indices is None else len(self._indices)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class PyReader(_GeneratorLoader):
    """Legacy PyReader API (reference: reader.py PyReader)."""

    def __init__(self, feed_list=None, capacity=64, use_double_buffer=True,
                 iterable=True, return_list=False):
        super().__init__(feed_list, capacity, use_double_buffer, iterable,
                         return_list)

    def decorate_sample_generator(self, sample_generator, batch_size,
                                  drop_last=True, places=None):
        return self.set_sample_generator(sample_generator, batch_size,
                                         drop_last, places)

    def decorate_sample_list_generator(self, reader, places=None):
        return self.set_sample_list_generator(reader, places)

    def decorate_batch_generator(self, reader, places=None):
        return self.set_batch_generator(reader, places)
