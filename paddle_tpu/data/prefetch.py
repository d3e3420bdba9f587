"""Async host→device prefetch.

≙ reference double-buffered readers (operators/reader/buffered_reader.h:27,
create_double_buffer_reader_op.cc) and the py_reader blocking queue
(reader/lod_tensor_blocking_queue.h:31). TPU translation: a worker thread
stages upcoming batches onto the device with jax.device_put while the current
step runs, overlapping host input with device compute.
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Dict, Iterator, Optional

import jax


class DevicePrefetcher:
    """Wrap a feed-dict iterator; yields batches already resident on device.

    `stage_threads` workers stage batches CONCURRENTLY (order preserved via
    futures): a host-to-device link has per-transfer latency (a busy PCIe
    queue), so a single staging stream idles the link between transfers;
    two in flight keep it saturated."""

    _END = object()

    def __init__(self, feed_iter_fn: Callable[[], Iterator[Dict]],
                 capacity: int = 2, device=None, sharding=None,
                 staging: Optional[Dict] = None, stage_threads: int = 2):
        """staging: {var_name: (wire_dtype, device_scale)} — convert those
        entries to their byte-lean wire dtype on the worker thread before
        staging (see data.feeder.staging_specs / layers.data staging_dtype).
        Through a bandwidth-limited host->device link this is the difference
        between 1/4 and full fp32 bytes per image batch."""
        self._fn = feed_iter_fn
        self._capacity = max(capacity, stage_threads)
        self._device = device
        self._sharding = sharding
        self._staging = staging or {}
        self._stage_threads = max(1, stage_threads)

    def _put(self, batch: Dict):
        if self._staging:
            from .feeder import stage_batch
            batch = stage_batch(batch, self._staging)
        target = self._sharding or self._device
        if target is None:
            return {k: jax.device_put(v) for k, v in batch.items()}
        return {k: jax.device_put(v, target) for k, v in batch.items()}

    def __iter__(self):
        from concurrent.futures import ThreadPoolExecutor

        q: queue.Queue = queue.Queue(maxsize=self._capacity)
        err = []
        pool = ThreadPoolExecutor(max_workers=self._stage_threads)
        # set when the consumer abandons the iterator (break / exception
        # in the training loop): the producer must not stay blocked in
        # put() forever, pinning its thread, the pool, and up to
        # `capacity` staged device batches for process lifetime
        closed = threading.Event()

        def put_open(item) -> bool:
            while not closed.is_set():
                try:
                    q.put(item, timeout=0.2)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            try:
                for b in self._fn():
                    # bounded queue of FUTURES: up to `capacity` batches
                    # are staging/staged ahead, in iterator order
                    if not put_open(pool.submit(self._put, b)):
                        return
            except Exception as e:  # propagate to consumer
                err.append(e)
            finally:
                put_open(self._END)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is self._END:
                    if err:
                        raise err[0]
                    return
                yield item.result()
        finally:
            closed.set()
            try:  # drop queued futures so staged batches free promptly
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
            pool.shutdown(wait=False)
