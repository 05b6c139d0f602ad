"""paddle.io — datasets and data loading.

Reference: python/paddle/io/ (Dataset, DataLoader with multiprocess workers at
io/dataloader/worker.py). TPU-native design: workers are threads feeding a
bounded prefetch queue (numpy batches stay on host; device transfer happens at
first op use, letting XLA overlap H2D with compute). The GIL-bound hot loops
— batch collation and image normalize — run in the C++ core
(csrc/prefetch.cpp via io/native.py, ctypes calls release the GIL), so the
thread workers parallelize where it matters; see native.py for the
data_feed.cc analogy.
"""

from __future__ import annotations

import itertools
import math
import queue
import threading

import numpy as np

from ..core import rng as _rng
from ..core.tensor import Tensor

__all__ = [
    "Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
    "ChainDataset", "Subset", "random_split", "Sampler", "SequenceSampler",
    "RandomSampler", "WeightedRandomSampler", "BatchSampler",
    "DistributedBatchSampler", "DataLoader", "get_worker_info",
]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset does not support indexing")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths) and abs(sum(lengths) - 1) < 1e-6:
        n = len(dataset)
        sizes = [int(math.floor(n * l)) for l in lengths]
        rem = n - sum(sizes)
        for i in range(rem):
            sizes[i % len(sizes)] += 1
        lengths = sizes
    assert sum(lengths) == len(dataset)
    perm = np.random.permutation(len(dataset))
    out, offset = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset : offset + l].tolist()))
        offset += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(
            weights.numpy() if isinstance(weights, Tensor) else weights,
            np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1,
                 drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Reference: python/paddle/io/dataloader/batch_sampler.py
    DistributedBatchSampler — shards indices across data-parallel ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import get_rank, get_world_size

            num_replicas = num_replicas or get_world_size()
            rank = rank if rank is not None else get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            g = np.random.RandomState(self.epoch)
            indices = g.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: (self.total_size - len(indices))]
        indices = indices[self.local_rank : self.total_size : self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


def _np_collate(batch):
    """Numpy-only mirror of default_collate_fn for process workers: child
    processes must not build Tensors (that would initialize an accelerator
    backend per worker); the parent tensorizes the stacked arrays."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(b._data) for b in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return [_np_collate(list(t)) for t in zip(*batch)]
    if isinstance(sample, dict):
        return {k: _np_collate([b[k] for b in batch]) for k in sample}
    if isinstance(sample, str):
        return list(batch)
    return batch


def _tensorize(tree):
    return _tree_map(
        lambda t: Tensor(t) if isinstance(t, np.ndarray) else t, tree)


def _tree_map(fn, tree):
    """Map fn over the non-container leaves of a list/dict batch tree
    (the one walker shared by tensorize/pack/unpack)."""
    if isinstance(tree, list):
        return [_tree_map(fn, t) for t in tree]
    if isinstance(tree, dict):
        return {k: _tree_map(fn, v) for k, v in tree.items()}
    return fn(tree)


def _shm_pack(tree, min_bytes=1 << 20):
    """Move the numpy leaves of a collated batch into ONE shared-memory
    segment (reference use_shared_memory=True: io/dataloader/worker.py
    sends batches via shared memory instead of pickling through the pipe).
    Returns an ("shm", name, spec) token, or ("inline", tree) for small
    batches where the segment setup would cost more than the copy."""
    import multiprocessing.shared_memory as mshm

    arrays = []

    def mark(t):
        if isinstance(t, np.ndarray):
            arrays.append(np.ascontiguousarray(t))
            a = arrays[-1]
            # a.dtype (picklable) — a str() form can't round-trip
            # structured/record dtypes
            return ("__arr__", len(arrays) - 1, a.shape, a.dtype)
        return t

    spec = _tree_map(mark, tree)
    total = sum(a.nbytes for a in arrays)
    if not arrays or total < min_bytes:
        return ("inline", tree)
    seg = mshm.SharedMemory(create=True, size=total)
    off = 0
    offsets = []
    for a in arrays:
        view = np.ndarray(a.shape, a.dtype, buffer=seg.buf, offset=off)
        np.copyto(view, a)
        offsets.append(off)
        off += a.nbytes
    name = seg.name
    seg.close()
    return ("shm", name, spec, offsets)


def _is_arr_marker(t):
    return isinstance(t, tuple) and len(t) == 4 and t[0] == "__arr__"


def _shm_unpack(token):
    kind = token[0]
    if kind == "inline":
        return token[1]
    import multiprocessing.shared_memory as mshm

    _, name, spec, offsets = token
    seg = mshm.SharedMemory(name=name)
    try:
        def restore(t):
            if _is_arr_marker(t):
                _, idx, shape, dtype = t
                view = np.ndarray(shape, dtype, buffer=seg.buf,
                                  offset=offsets[idx])
                return view.copy()  # own the data before the segment dies
            return t

        return _tree_map(restore, spec)
    finally:
        seg.close()
        seg.unlink()


def _shm_discard(token):
    """Unlink a packed batch without reading it (early-exit cleanup)."""
    if token[0] != "shm":
        return
    import multiprocessing.shared_memory as mshm

    try:
        seg = mshm.SharedMemory(name=token[1])
        seg.close()
        seg.unlink()
    except FileNotFoundError:
        pass


_PROC_BUILDER = None  # per-worker-process task state (set by initializer)


def _proc_worker_init(builder):
    """Spawn-process initializer: pins the child to CPU before anything
    imports jax, receives the builder ONCE (one dataset pickle per worker,
    not per batch) and runs worker_init_fn once — the reference's
    once-per-worker contract (io/dataloader/worker.py:_worker_loop)."""
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"
    global _PROC_BUILDER
    _PROC_BUILDER = builder
    builder._lazy_init()


def _proc_run_batch(indices):
    return _PROC_BUILDER(indices)


class _ProcBatchBuilder:
    """Picklable per-batch task for process workers (reference analog:
    python/paddle/io/dataloader/worker.py:1 _worker_loop — the reference
    forks long-lived workers fed by index queues; spawn + Pool.imap gives
    the same pipeline with order preservation on all platforms)."""

    def __init__(self, dataset, collate_fn, worker_init_fn, num_workers,
                 use_shared_memory=True):
        self.dataset = dataset
        self.collate_fn = collate_fn  # None = numpy default collate
        self.worker_init_fn = worker_init_fn
        self.num_workers = num_workers
        self.use_shared_memory = use_shared_memory
        self._inited = False

    def _lazy_init(self):
        if self._inited:
            return
        self._inited = True
        import multiprocessing as mp

        ident = mp.current_process()._identity
        wid = (ident[0] - 1) % self.num_workers if ident else 0
        _worker_info.info = _WorkerInfo(wid, self.num_workers, self.dataset)
        if self.worker_init_fn is not None:
            self.worker_init_fn(wid)

    def __call__(self, indices):
        self._lazy_init()
        samples = [self.dataset[i] for i in indices]
        if self.collate_fn is None:
            batch = _np_collate(samples)
            if self.use_shared_memory:
                return _shm_pack(batch)
            return ("inline", batch)
        return ("inline", self.collate_fn(samples))


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        import jax.numpy as jnp

        return Tensor._wrap(jnp.stack([b._data for b in batch]))
    if isinstance(sample, np.ndarray):
        # native parallel-memcpy collator, only when the batch is big
        # enough to amortize thread spawn and ONLY if the library is
        # already loaded (never build on the hot path; DataLoader warms it)
        if len(batch) > 1 and len(batch) * sample.nbytes >= 1 << 20:
            from . import native

            if native.lib_ready() is not None:
                out = native.collate_samples(batch)
                if out is not None:
                    return Tensor(out)
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, float)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(t)) for t in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if isinstance(sample, str):
        return list(batch)
    return Tensor(np.asarray(batch))


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_process_workers=False):
        self.dataset = dataset
        self._custom_collate = collate_fn
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = bool(use_shared_memory)
        # process workers (reference worker.py uses processes always);
        # threads stay the default here because the C++ collate/prefetch
        # core already de-GILs the common path — processes pay pickling but
        # scale arbitrary Python __getitem__/transforms
        self.use_process_workers = bool(use_process_workers)
        from . import native as _native

        _native.warm()  # background-build the C++ core; no blocking here
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
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
            if batch_size is None:
                self.batch_sampler = None

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _index_batches(self):
        if self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield [i]
            return
        yield from self.batch_sampler

    def _make_batch(self, indices):
        samples = [self.dataset[i] for i in indices]
        return self.collate_fn(samples)

    def _iter_iterable(self):
        batch = []
        for item in self.dataset:
            batch.append(item)
            if len(batch) == self.batch_size:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.drop_last:
            yield self.collate_fn(batch)

    def __iter__(self):
        if self._iterable:
            yield from self._iter_iterable()
            return
        if self.num_workers <= 0:
            for indices in self._index_batches():
                yield self._make_batch(indices)
            return
        if self.use_process_workers:
            yield from self._process_iter()
            return
        yield from self._threaded_iter()

    def _process_iter(self):
        """Process-pool pipeline: spawn workers (pinned to CPU) run
        ``dataset[i]`` + collate off the parent's GIL; ``imap`` preserves
        batch order and a semaphore bounds in-flight batches to
        prefetch_factor * num_workers (buffered_reader backpressure).
        The dataset, collate_fn and worker_init_fn must be picklable —
        the same contract as the reference's process workers
        (python/paddle/io/dataloader/worker.py:1)."""
        import multiprocessing as mp

        ctx = mp.get_context("spawn")
        batches = list(self._index_batches())
        cap = max(1, self.prefetch_factor * self.num_workers)
        sem = threading.Semaphore(cap)
        stop = threading.Event()

        def feed():
            # the pool's task-handler thread runs this generator; it must
            # never block indefinitely, or Pool teardown (early consumer
            # exit, worker exception) would join it forever
            for b in batches:
                while not sem.acquire(timeout=0.1):
                    if stop.is_set():
                        return
                if stop.is_set():
                    return
                yield b

        builder = _ProcBatchBuilder(self.dataset, self._custom_collate,
                                    self.worker_init_fn, self.num_workers,
                                    use_shared_memory=self.use_shared_memory)
        with ctx.Pool(self.num_workers, initializer=_proc_worker_init,
                      initargs=(builder,)) as pool:
            it = pool.imap(_proc_run_batch, feed(), chunksize=1)
            try:
                for token in it:
                    sem.release()
                    res = _shm_unpack(token)
                    yield (_tensorize(res) if self._custom_collate is None
                           else res)
            finally:
                stop.set()
                sem.release()  # unblock a feed() waiting on backpressure
                # early exit / error: in-flight batches may hold shared-
                # memory segments — drain and unlink so /dev/shm doesn't
                # accumulate across abandoned iterators
                try:
                    for token in it:
                        _shm_discard(token)
                except Exception:
                    pass

    def _threaded_iter(self):
        """Thread-pool prefetch pipeline preserving batch order, with
        bounded in-flight batches (prefetch_factor * num_workers credits):
        workers take a credit before building, the consumer returns it
        after yielding — backpressure so a slow training loop can't let
        the workers buffer the whole epoch (buffered_reader semantics).
        The credit queue is the native C++ ring when built (blocking waits
        happen in C, off the GIL), queue.Queue otherwise."""
        from . import native as _native

        idx_q: queue.Queue = queue.Queue()
        out: dict[int, object] = {}
        done = threading.Event()
        lock = threading.Condition()
        batches = list(self._index_batches())
        for i, b in enumerate(batches):
            idx_q.put((i, b))

        cap = max(1, self.prefetch_factor * self.num_workers)
        ring = None
        if _native.lib_ready() is not None:
            try:
                ring = _native.Ring(cap)
            except RuntimeError:
                ring = None
        if ring is not None:
            for _ in range(cap):
                ring.push(1)
            take_credit = lambda: ring.pop(timeout_ms=200)[0] == 1
            give_credit = lambda: ring.push(1, timeout_ms=0)
        else:
            credits: queue.Queue = queue.Queue()
            for _ in range(cap):
                credits.put(1)

            def take_credit():
                try:
                    credits.get(timeout=0.2)
                    return True
                except queue.Empty:
                    return False

            give_credit = lambda: credits.put(1)

        def worker(wid):
            _worker_info.info = _WorkerInfo(wid, self.num_workers, self.dataset)
            if self.worker_init_fn:
                self.worker_init_fn(wid)
            while not done.is_set():
                if not take_credit():
                    continue  # backpressure; re-check done
                try:
                    i, indices = idx_q.get_nowait()
                except queue.Empty:
                    give_credit()
                    return
                batch = self._make_batch(indices)
                with lock:
                    out[i] = batch
                    lock.notify_all()

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()
        try:
            for i in range(len(batches)):
                with lock:
                    while i not in out:
                        lock.wait(timeout=60.0)
                    yield out.pop(i)
                give_credit()
        finally:
            done.set()
            if ring is not None:
                ring.close()

    def __call__(self):
        return iter(self)

    # -- resumable stream passthrough (crash recovery) -------------------
    def _resumable_sampler(self):
        bs = self.batch_sampler
        if bs is None or not hasattr(bs, "state_dict"):
            raise TypeError(
                "this DataLoader's batch sampler is not resumable; use "
                "io.BucketedBatchSampler (or any batch_sampler exposing "
                "state_dict/set_state_dict/advance) to checkpoint the "
                "data stream position")
        return bs

    def state_dict(self):
        """Resume point of the underlying batch sampler (epoch, consumed-
        batch cursor, shuffle seed) — what ``CheckpointManager.save(...,
        sampler=loader)`` persists."""
        return self._resumable_sampler().state_dict()

    def set_state_dict(self, sd):
        self._resumable_sampler().set_state_dict(sd)

    load_state_dict = set_state_dict

    def advance(self, n=1):
        """Report ``n`` consumed batches to the batch sampler (the resume
        cursor counts *trained* batches, never read-ahead)."""
        self._resumable_sampler().advance(n)

    def set_epoch(self, epoch):
        bs = self.batch_sampler
        if bs is not None and hasattr(bs, "set_epoch"):
            bs.set_epoch(epoch)


class SubsetRandomSampler(Sampler):
    """Reference io/sampler.py SubsetRandomSampler."""

    def __init__(self, indices, generator=None):
        if len(indices) == 0:
            raise ValueError(
                "SubsetRandomSampler requires a non-empty indices list")
        self.indices = list(indices)

    def __iter__(self):
        import numpy as np

        from ..core import rng as _rng

        import jax

        seed = int(jax.random.randint(_rng.next_key(), (), 0, 2**31 - 1))
        order = np.random.RandomState(seed).permutation(len(self.indices))
        return iter([self.indices[i] for i in order])

    def __len__(self):
        return len(self.indices)


class ConcatDataset(Dataset):
    """Reference io/dataset.py ConcatDataset: map-style concatenation with
    bisect-based index routing."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        if not self.datasets:
            raise ValueError("ConcatDataset needs at least one dataset")
        import itertools

        self.cumulative_sizes = list(itertools.accumulate(
            len(d) for d in self.datasets))

    def __len__(self):
        return self.cumulative_sizes[-1]

    def __getitem__(self, idx):
        import bisect

        if idx < 0:
            idx += len(self)
        di = bisect.bisect_right(self.cumulative_sizes, idx)
        prev = 0 if di == 0 else self.cumulative_sizes[di - 1]
        return self.datasets[di][idx - prev]


__all__ += ["SubsetRandomSampler", "ConcatDataset"]

# shape-bucketed batching (anti-recompile input pipeline; imported last —
# bucketing.py subclasses BatchSampler defined above)
from .bucketing import BucketedBatchSampler, PadToBucket  # noqa: E402,F401

__all__ += ["BucketedBatchSampler", "PadToBucket"]

# double-buffered host->device prefetch (overlap layer; composes with the
# bucketing above: staged batches are padded to bucket shapes off the
# critical path)
from .prefetch import DevicePrefetcher  # noqa: E402,F401

__all__ += ["DevicePrefetcher"]

# fault-tolerant streaming data plane (sharded ingestion over the fleet
# FS surface; resumable through the same sampler-state protocol)
from .streaming import (  # noqa: E402,F401
    ShardManifest, StreamCorruptionError, StreamReadError,
    StreamingDataset, pack_arrays, read_stream_shard, unpack_arrays,
    write_stream_shard)

__all__ += ["StreamingDataset", "ShardManifest", "StreamReadError",
            "StreamCorruptionError", "write_stream_shard",
            "read_stream_shard", "pack_arrays", "unpack_arrays"]


def resolve_resumable(stream):
    """Unwrap pipeline layers (DevicePrefetcher → its source, DataLoader →
    its batch sampler) down to the object that owns the resumable stream
    state, or ``None`` when nothing in the stack supports it. This is how
    ``CheckpointManager`` and ``FusedTrainStep.drive`` accept a prefetcher,
    a loader, or the sampler itself interchangeably as ``sampler=``."""
    obj = stream
    for _ in range(8):  # defensive bound on pathological nesting
        if isinstance(obj, DevicePrefetcher):
            obj = obj.source
        elif isinstance(obj, DataLoader):
            obj = obj.batch_sampler
        else:
            break
    if (obj is not None and hasattr(obj, "state_dict")
            and hasattr(obj, "set_state_dict") and hasattr(obj, "advance")):
        return obj
    return None


__all__ += ["resolve_resumable"]
