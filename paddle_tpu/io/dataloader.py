"""DataLoader: multiprocess workers + host->device prefetch.

Parity target: ``python/paddle/io/dataloader/`` in the reference (DataLoader
with worker subprocesses, shared-memory tensor transport, buffered reader,
IterableDataset worker splitting). TPU redesign (SURVEY §7 hard-part 6 —
keep the MXUs fed):

* workers are ``fork`` subprocesses that ONLY touch numpy (they must never
  initialize the PJRT client); batches cross process boundaries as pickled
  numpy arrays and are wrapped to Tensors in the parent,
* ``use_buffer_reader=True`` adds a host->device double-buffer: the next
  ``prefetch_factor`` batches are ``jax.device_put`` issued ahead of use, so
  the async dispatch overlaps the device step (the TPU analogue of the
  reference's pin-memory + CUDA-stream copy pipeline).
"""

from __future__ import annotations

import collections
import itertools
import multiprocessing as mp
import queue as pyqueue
import signal as _signal
import time
import traceback
import warnings
from typing import Any, Callable, List, Optional

import numpy as np

from .dataset import Dataset, IterableDataset
from .sampler import BatchSampler

__all__ = ["DataLoader", "get_worker_info", "default_collate_fn",
           "default_convert_fn", "WorkerInfo", "prefetch_to_device"]


def _describe_exit(code: Optional[int]) -> str:
    """Human-readable worker exit: decodes the signal for negative codes
    (multiprocessing convention) so 'exit code -9' reads as the OOM kill
    it almost always is."""
    if code is None:
        return "still exiting"
    if code < 0:
        try:
            name = _signal.Signals(-code).name
        except ValueError:
            name = f"signal {-code}"
        hint = " (likely the kernel OOM killer)" if -code == 9 else \
            " (segfault in dataset/native code)" if -code == 11 else ""
        return f"killed by {name}{hint}"
    return f"exit code {code}"


def _fetch_sample(dataset, idx, retries: int, backoff_s: float):
    """``dataset[idx]`` with bounded retry + exponential backoff — the
    self-healing path for transient failures (flaky remote reads, racing
    decoders). Deterministic failures exhaust the retries and re-raise
    for the caller's quarantine/raise decision."""
    attempt = 0
    while True:
        try:
            return dataset[idx]
        except Exception:
            if attempt >= retries:
                raise
            time.sleep(backoff_s * (2 ** attempt))
            attempt += 1


class _SkippedBatch:
    """Worker->parent marker: every index of this batch is quarantined —
    the batch is dropped, the epoch continues."""


def _gather_batch(dataset, indices, quarantined: set, retries: int,
                  backoff_s: float, quarantine: bool, who: str = "DataLoader",
                  on_quarantine: Optional[Callable] = None):
    """Fetch a batch's samples with the self-healing policy — shared by
    the worker loop and the single-process path so the retry/quarantine
    semantics cannot drift apart. Mutates ``quarantined`` in place; calls
    ``on_quarantine(idx)`` for each NEWLY quarantined index.

    Returns the item list, or ``None`` when quarantine healing left the
    batch EMPTY (every index bad) — the batch is skipped, not fatal: a
    self-healing loader must survive even a fully-poisoned batch."""
    items, last_exc = [], None
    for i in indices:
        if i in quarantined:
            continue
        try:
            items.append(_fetch_sample(dataset, i, retries, backoff_s))
        except Exception as e:
            if not quarantine:
                raise
            # self-healing: drop the sample, remember the index so it is
            # never re-fetched (and never re-pays the retries)
            last_exc = e
            quarantined.add(i)
            if on_quarantine is not None:
                on_quarantine(i)
            warnings.warn(
                f"{who}: sample {i} failed {retries + 1}x and was "
                f"quarantined ({type(e).__name__}: {e}); the batch "
                f"continues without it")
    if not items:
        if quarantine:
            if last_exc is not None:   # newly emptied this epoch: say so
                warnings.warn(f"{who}: every index of a batch is "
                              f"quarantined; skipping the batch")
            return None
        raise last_exc if last_exc is not None else RuntimeError(
            "batch: every index quarantined")
    return items


class WorkerInfo:
    def __init__(self, id: int, num_workers: int, seed: int, dataset):  # noqa: A002
        self.id = id
        self.num_workers = num_workers
        self.seed = seed
        self.dataset = dataset


_worker_info: Optional[WorkerInfo] = None


def get_worker_info() -> Optional[WorkerInfo]:
    """Inside a worker: this worker's (id, num_workers, seed, dataset);
    ``None`` in the main process (reference parity)."""
    return _worker_info


def default_convert_fn(batch):
    return batch


def default_collate_fn(batch: List[Any]):
    """Stack a list of samples into batched numpy arrays (nested structures
    follow the reference: dict -> dict of stacks, tuple -> tuple of stacks)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (np.floating, float)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (np.integer, int)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (str, bytes)):
        return batch
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    if isinstance(sample, (tuple, list)):
        return type(sample)(default_collate_fn(list(fields))
                            for fields in zip(*batch))
    # Tensor / jax array / anything array-like
    try:
        return np.stack([np.asarray(s) for s in batch])
    except Exception:
        return batch


class _ExceptionWrapper:
    def __init__(self, exc):
        self.exc_type = type(exc).__name__
        self.msg = f"{exc}\n{traceback.format_exc()}"

    def reraise(self):
        raise RuntimeError(
            f"DataLoader worker raised {self.exc_type}: {self.msg}")


_RING_FALLBACK_WARNED = False


class _RingSource:
    """Round-robin poll of per-worker shm rings behind a Queue-like .get.
    ``rings`` is mutated in place by worker resurrection (a replacement
    worker gets a FRESH ring — the dead worker may have died mid-push,
    leaving its old ring's slot state unusable)."""

    def __init__(self, rings):
        self.rings = list(rings)
        self._next = 0

    def swap(self, idx, new_ring):
        old = self.rings[idx]
        self.rings[idx] = new_ring
        try:
            old.close()
        except Exception:
            pass

    def get(self, timeout=None):
        import pickle
        import time
        deadline = None if timeout is None else time.time() + timeout
        while True:
            for _ in range(len(self.rings)):
                r = self.rings[self._next]
                self._next = (self._next + 1) % len(self.rings)
                data = r.pop(timeout_ms=2)
                if data is not None:
                    return pickle.loads(data)
            if deadline is not None and time.time() > deadline:
                raise pyqueue.Empty


def _worker_loop(dataset, index_queue, result_queue, collate_fn, init_fn,
                 worker_id, num_workers, seed, iterable, ring=None,
                 all_rings=(), retry_cfg=(0, 0.05, False, frozenset())):
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, seed, dataset)
    np.random.seed(seed % (2 ** 31))
    # forked children inherit owner=True ring handles; they must not destroy
    # the parent's semaphores / shm at interpreter exit (ADVICE r2)
    for r in all_rings:
        try:
            r.disown()
        except Exception:
            pass
    if ring is not None:
        import pickle

        class _RingPut:
            def put(self, item):
                try:
                    ring.push(pickle.dumps(item,
                                           protocol=pickle.HIGHEST_PROTOCOL))
                except ValueError as e:  # payload exceeds slot capacity
                    ring.push(pickle.dumps((item[0], _ExceptionWrapper(e))))
        result_queue = _RingPut()
    try:
        if init_fn is not None:
            init_fn(worker_id)
    except Exception as e:  # init failure poisons every batch
        result_queue.put((-1, _ExceptionWrapper(e)))
        return
    if iterable:
        # stream split: worker w takes items w, w+N, w+2N, ... and batches
        # arrive pre-chunked as (batch_idx, batch_size) requests
        it = itertools.islice(iter(dataset), worker_id, None, num_workers)
        while True:
            req = index_queue.get()
            if req is None:
                return
            bidx, bsize = req
            items = list(itertools.islice(it, bsize))
            if not items:
                result_queue.put((bidx, StopIteration()))
                continue
            try:
                result_queue.put((bidx, collate_fn(items)))
            except Exception as e:
                result_queue.put((bidx, _ExceptionWrapper(e)))
    else:
        retries, backoff_s, quarantine, initial_q = retry_cfg
        # seeded from the parent loader's set at fork: indices quarantined
        # in earlier epochs (reported back via the (-2, idx) notice) are
        # skipped immediately instead of re-paying the retries
        quarantined: set = set(initial_q)
        while True:
            req = index_queue.get()
            if req is None:
                return
            bidx, indices = req
            try:
                items = _gather_batch(
                    dataset, indices, quarantined, retries, backoff_s,
                    quarantine, who=f"DataLoader worker {worker_id}",
                    # tell the parent so the NEXT epoch's workers inherit
                    on_quarantine=lambda i: result_queue.put((-2, i)))
                result_queue.put((bidx, _SkippedBatch() if items is None
                                  else collate_fn(items)))
            except Exception as e:
                result_queue.put((bidx, _ExceptionWrapper(e)))


def _to_tensors(batch, device=None):
    """numpy batch -> Tensor pytree (device transfer happens here; under the
    buffered reader several of these are in flight ahead of consumption)."""
    from ..core.tensor import Tensor, to_tensor
    if isinstance(batch, np.ndarray):
        return to_tensor(batch, place=device)
    if isinstance(batch, dict):
        return {k: _to_tensors(v, device) for k, v in batch.items()}
    if isinstance(batch, (tuple, list)):
        return type(batch)(_to_tensors(v, device) for v in batch)
    return batch


def prefetch_to_device(iterable, size: int = 2, device=None):
    """Double-buffered host->device prefetch iterator (the TPU analogue of
    the reference's pin-memory + CUDA-stream copy pipeline, as a standalone
    generator usable over ANY batch iterable, not just DataLoader).

    Keeps ``size`` batches' transfers in flight ahead of the consumer:
    ``jax.device_put`` dispatch is async, so while the device runs step N
    the host is collating batch N+1 ("data" span) and its H2D transfer
    ("h2d" span) streams concurrently — the input pipeline disappears from
    the step time once ``host+h2d < step``.

    CPU degradation: there is no host/device overlap to win and "transfers"
    are memcpys, so the buffer collapses to a plain convert-and-yield loop
    (single-buffer fallback) — no extra batch latency in tier-1 tests.

    Batches may be numpy arrays, Tensors, or nested dict/tuple/list pytrees
    of them; ``device`` is an optional Place to pin transfers to.
    """
    from ..profiler import annotate

    it = iter(iterable)
    if not donation_like_backend_supports_overlap():
        for b in it:
            yield _to_tensors(b, device)
        return
    size = max(1, int(size))
    buf = collections.deque()

    def _fill():
        with annotate("data"):
            try:
                b = next(it)
            except StopIteration:
                return False
        with annotate("h2d"):
            buf.append(_to_tensors(b, device))
        return True

    while len(buf) < size and _fill():
        pass
    while buf:
        out = buf.popleft()
        # issue the next transfer BEFORE handing the current batch out, so
        # the H2D copy overlaps the consumer's device step
        _fill()
        yield out


def donation_like_backend_supports_overlap() -> bool:
    """Async-dispatch H2D overlap exists off-CPU (same backend split as
    jit.train_step.donation_supported; kept separate so io never imports
    jit)."""
    import jax
    return jax.default_backend() not in ("cpu",)


class _WorkerSet:
    """Worker processes + transport + in-flight bookkeeping, with
    resurrection: a dead worker (OOM kill, segfault in dataset code) is
    replaced by a fresh fork — same worker id, FRESH index queue and shm
    ring (the old ones may hold a torn request/push from the death) — and
    every batch that was in flight on it is re-queued, so one lost worker
    costs a recompute instead of the epoch.

    Resurrection is map-style only: an IterableDataset worker's stream
    position died with the process, so replaying its requests would
    silently skip or duplicate samples — those keep the fail-fast path.
    """

    def __init__(self, loader: "DataLoader"):
        self.loader = loader
        self.ctx = mp.get_context("fork")  # workers reuse the parent dataset
        self.nw = loader.num_workers
        self.result_queue = self.ctx.Queue()
        self.rings = loader._make_rings(self.nw)
        self.result_src = (_RingSource(self.rings) if self.rings
                           else self.result_queue)
        self.base_seed = np.random.randint(0, 2 ** 31 - 1)
        self.index_queues: List = []
        self.procs: List = []
        self.inflight: dict = {}       # bidx -> (worker_id, payload)
        self.restarts_left = (0 if loader._iterable
                              else loader.worker_restarts)
        self.generation = 0
        for w in range(self.nw):
            self.index_queues.append(self.ctx.Queue())
            self.procs.append(self._spawn(w))

    def _spawn(self, w: int):
        ring = self.rings[w] if self.rings else None
        p = self.ctx.Process(
            target=_worker_loop,
            args=(self.loader.dataset, self.index_queues[w],
                  self.result_queue, self.loader.collate_fn,
                  self.loader.worker_init_fn, w, self.nw,
                  self.base_seed + w + self.generation * self.nw,
                  self.loader._iterable, ring,
                  tuple(self.rings) if self.rings else (),
                  (self.loader.sample_retries,
                   self.loader.sample_retry_backoff,
                   self.loader.quarantine_bad_samples,
                   frozenset(self.loader._quarantined))),
            daemon=True)
        p.start()
        return p

    # -- in-flight bookkeeping (map-style) ----------------------------------
    def submit(self, bidx: int, payload):
        w = bidx % self.nw
        self.index_queues[w].put((bidx, payload))
        self.inflight[bidx] = (w, payload)

    def done(self, bidx: int):
        self.inflight.pop(bidx, None)

    def revive(self, dead) -> bool:
        """Replace dead workers and re-queue their in-flight batches.
        Returns False (caller raises) when the restart budget is spent or
        the dataset is iterable."""
        if self.restarts_left < len(dead):
            return False
        self.restarts_left -= len(dead)
        self.generation += 1
        for w, code in dead:
            warnings.warn(
                f"DataLoader worker {w} died ({_describe_exit(code)}); "
                f"resurrecting it and re-queuing "
                f"{sum(1 for ww, _ in self.inflight.values() if ww == w)} "
                f"in-flight batch(es) "
                f"({self.restarts_left} restart(s) left)")
            try:
                self.procs[w].join(timeout=0.1)
            except Exception:
                pass
            # fresh queue + ring: the old ones may be torn mid-operation
            self.index_queues[w] = self.ctx.Queue()
            if self.rings:
                try:
                    new_ring = self.loader._make_ring(w, self.generation)
                except Exception:
                    return False     # can't rebuild transport — fail fast
                self.rings[w] = new_ring
                self.result_src.swap(w, new_ring)
            self.procs[w] = self._spawn(w)
            for bidx, (ww, payload) in sorted(self.inflight.items()):
                if ww == w:
                    self.index_queues[w].put((bidx, payload))
        return True

    def shutdown(self):
        for iq in self.index_queues:
            try:
                iq.put(None)
            except Exception:
                pass
        for p in self.procs:
            p.join(timeout=1.0)
            if p.is_alive():
                p.terminate()
        if self.rings:
            for r in self.rings:
                try:
                    r.close()
                except Exception:
                    pass


class DataLoader:
    """ref: paddle.io.DataLoader (return_list=True semantics only — the
    legacy feed-dict mode targets the static graph executor, which this
    framework replaces with jit; pass ``feed_list`` for API compat, it is
    ignored)."""

    def __init__(self, dataset: Dataset, feed_list=None, places=None,
                 return_list: bool = True, batch_sampler=None,
                 batch_size: int = 1, shuffle: bool = False,
                 drop_last: bool = False, collate_fn: Optional[Callable] = None,
                 num_workers: int = 0, use_buffer_reader: bool = True,
                 prefetch_factor: int = 2, use_shared_memory: bool = True,
                 timeout: float = 0, worker_init_fn: Optional[Callable] = None,
                 persistent_workers: bool = False,
                 sample_retries: Optional[int] = None,
                 sample_retry_backoff: Optional[float] = None,
                 quarantine_bad_samples: Optional[bool] = None,
                 worker_restarts: Optional[int] = None):
        """Self-healing knobs (docs/FAULT_TOLERANCE.md "Runtime anomalies";
        defaults come from the FLAGS_health_* flags, which default OFF so
        error propagation is unchanged unless opted in):

        * ``sample_retries`` — retry a failing ``Dataset.__getitem__``
          with bounded exponential backoff (transient I/O);
        * ``quarantine_bad_samples`` — after the retries, drop the sample
          and quarantine its index (warn once) instead of poisoning the
          epoch (defaults on when retries are enabled);
        * ``worker_restarts`` — resurrect a dead worker process
          (OOM-kill, segfault) up to N times, re-queuing its in-flight
          batches (map-style datasets; an iterable worker's stream
          position died with it, so those still fail fast).
        """
        from ..flags import flag
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = max(0, int(num_workers))
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = bool(use_shared_memory)
        self.shm_slot_bytes = 32 << 20
        self.prefetch_factor = max(1, int(prefetch_factor))
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.sample_retries = int(
            flag("FLAGS_health_data_retries") if sample_retries is None
            else sample_retries)
        self.sample_retry_backoff = float(
            flag("FLAGS_health_data_backoff_s")
            if sample_retry_backoff is None else sample_retry_backoff)
        self.quarantine_bad_samples = bool(
            self.sample_retries > 0 if quarantine_bad_samples is None
            else quarantine_bad_samples)
        self.worker_restarts = int(
            flag("FLAGS_health_worker_restarts") if worker_restarts is None
            else worker_restarts)
        self._quarantined: set = set()   # num_workers=0 path
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            if batch_sampler is not None or shuffle:
                raise ValueError(
                    "IterableDataset does not accept batch_sampler/shuffle")
            self.batch_size = int(batch_size)
            self.drop_last = bool(drop_last)
            self.batch_sampler = None
        elif batch_sampler is not None:
            if batch_size != 1 or shuffle or drop_last:
                raise ValueError(
                    "batch_sampler is mutually exclusive with "
                    "batch_size/shuffle/drop_last")
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)
            self.batch_size = int(batch_size)

    def __len__(self):
        if self._iterable:
            raise TypeError("DataLoader over an IterableDataset has no length")
        return len(self.batch_sampler)

    # -- iteration ----------------------------------------------------------

    def _raw_batches(self):
        """Yield collated numpy batches (single- or multi-process)."""
        if self.num_workers == 0:
            if self._iterable:
                it = iter(self.dataset)
                while True:
                    items = list(itertools.islice(it, self.batch_size))
                    if not items or (self.drop_last and
                                     len(items) < self.batch_size):
                        return
                    yield self.collate_fn(items)
            else:
                for indices in self.batch_sampler:
                    items = self._fetch_batch(indices)
                    if items is None:   # fully-quarantined batch: skip
                        continue
                    yield self.collate_fn(items)
            return
        yield from self._multiprocess_batches()

    def _fetch_batch(self, indices):
        """Single-process fetch with the same retry/quarantine healing the
        workers apply (shared quarantine set across epochs)."""
        return _gather_batch(self.dataset, indices, self._quarantined,
                             self.sample_retries, self.sample_retry_backoff,
                             self.quarantine_bad_samples)

    def _make_rings(self, nw):
        """Shared-memory transport (native C++ ring; reference shm parity).
        Falls back to mp.Queue when the native lib is unavailable — with
        ONE warning saying why, instead of silently downgrading every
        loader in the process to the slow path."""
        if not self.use_shared_memory:
            return None
        try:
            return [self._make_ring(w) for w in range(nw)]
        except Exception as e:
            global _RING_FALLBACK_WARNED
            if not _RING_FALLBACK_WARNED:
                _RING_FALLBACK_WARNED = True
                warnings.warn(
                    f"DataLoader: shared-memory ring transport unavailable "
                    f"({type(e).__name__}: {e}); falling back to the slower "
                    f"mp.Queue transport (pass use_shared_memory=False to "
                    f"silence)")
            return None

    def _make_ring(self, w: int, generation: int = 0):
        import os
        from ..native import ShmRing
        tag = f"/pt_dl_{os.getpid()}_{id(self) & 0xffffff}"
        suffix = f"_r{generation}" if generation else ""
        return ShmRing(f"{tag}_{w}{suffix}", slots=4,
                       slot_bytes=self.shm_slot_bytes)

    def _multiprocess_batches(self):
        ws = _WorkerSet(self)
        try:
            if self._iterable:
                yield from self._mp_iterable(ws.index_queues, ws.result_src,
                                             ws.nw, ws.procs)
            else:
                yield from self._mp_map(ws)
        finally:
            ws.shutdown()

    def _get(self, result_queue, workers=(), revive=None):
        """Queue get with a liveness watchdog: wait in short slices; when a
        worker died (OOM-kill/segfault) either resurrect it via ``revive``
        (self-healing map-style path) or fail fast with the worker's
        decoded exit signal instead of blocking forever."""
        from ..health import watchdog
        deadline = (None if not self.timeout
                    else time.monotonic() + self.timeout)
        while True:
            slice_t = 1.0
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        f"DataLoader timed out after {self.timeout}s waiting "
                        f"for a worker batch")
                slice_t = min(slice_t, left)
            try:
                out = result_queue.get(timeout=slice_t)
                # progress tick ONLY on a real batch: ticking the empty
                # poll slices would mask exactly the stalled-input hang
                # the watchdog exists to catch
                watchdog.touch()
                return out
            except pyqueue.Empty:
                dead = [(i, p.exitcode) for i, p in enumerate(workers)
                        if not p.is_alive()]
                if dead:
                    # final drain: a worker may have enqueued its result (or
                    # the real exception) just before exiting — surface that
                    # instead of a misleading died-unexpectedly error
                    try:
                        return result_queue.get(timeout=0.2)
                    except pyqueue.Empty:
                        pass
                    if revive is not None and revive(dead):
                        continue   # replacements spawned, work re-queued
                    descr = ", ".join(
                        f"worker {i}: {_describe_exit(c)}" for i, c in dead)
                    raise RuntimeError(
                        f"DataLoader worker(s) died unexpectedly ({descr}); "
                        f"the remaining batch will never arrive. Map-style "
                        f"datasets can self-heal via worker_restarts= / "
                        f"FLAGS_health_worker_restarts."
                    ) from None

    def _mp_map(self, ws: "_WorkerSet"):
        batches = list(self.batch_sampler)
        depth = min(len(batches), self.prefetch_factor * ws.nw)
        for nxt in range(depth):
            ws.submit(nxt, batches[nxt])
        nxt = depth
        reorder = {}
        for want in range(len(batches)):
            while want not in reorder:
                bidx, data = self._get(ws.result_src, ws.procs,
                                       revive=ws.revive)
                if bidx == -2:
                    # quarantine notice: the next epoch's workers (a fresh
                    # fork) inherit it and skip the index outright
                    self._quarantined.add(data)
                    continue
                if bidx == -1 or isinstance(data, _ExceptionWrapper):
                    if isinstance(data, _ExceptionWrapper):
                        data.reraise()
                ws.done(bidx)
                reorder[bidx] = data
            data = reorder.pop(want)
            if nxt < len(batches):
                ws.submit(nxt, batches[nxt])
                nxt += 1
            if isinstance(data, _SkippedBatch):
                continue            # fully-quarantined batch: dropped
            yield data

    def _mp_iterable(self, index_queues, result_queue, nw, workers=()):
        # request batches round-robin; a worker answering StopIteration is
        # retired, remaining workers drain their stream tails
        active = set(range(nw))
        bidx = 0
        inflight = collections.deque()
        depth = self.prefetch_factor * nw

        def request():
            nonlocal bidx
            if not active:
                return False
            w = bidx % nw
            if w not in active:
                w = next(iter(active))
            index_queues[w].put((bidx, self.batch_size))
            inflight.append(bidx)
            bidx += 1
            return True

        for _ in range(depth):
            request()
        reorder = {}
        want = 0
        done = set()
        while inflight:
            while inflight[0] not in reorder:
                i, data = self._get(result_queue, workers)
                if isinstance(data, _ExceptionWrapper):
                    data.reraise()
                reorder[i] = data
            i = inflight.popleft()
            data = reorder.pop(i)
            if isinstance(data, StopIteration):
                done.add(i)
                active.discard(i % nw)
                continue
            if len(data if isinstance(data, list) else [0]) and request():
                pass
            if self.drop_last and self._batch_len(data) < self.batch_size:
                continue
            yield data

    @staticmethod
    def _batch_len(data):
        if isinstance(data, np.ndarray):
            return data.shape[0]
        if isinstance(data, dict):
            return DataLoader._batch_len(next(iter(data.values())))
        if isinstance(data, (tuple, list)) and data:
            return DataLoader._batch_len(data[0])
        return 0

    def __iter__(self):
        raw = self._raw_batches()
        if not self.use_buffer_reader:
            for b in raw:
                yield _to_tensors(b)
            return
        # host->device double buffer: keep prefetch_factor batches' transfers
        # in flight (jax device_put is async — overlaps the device step)
        yield from prefetch_to_device(raw, size=self.prefetch_factor)
