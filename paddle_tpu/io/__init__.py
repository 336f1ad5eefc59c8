"""paddle.io — datasets & DataLoader (reference: python/paddle/io/).

Host-side pipeline: numpy batches assembled by (optionally threaded) workers,
converted to device tensors at the boundary.  The reference's multiprocess
workers + pinned-memory path maps to background-thread prefetch + async
device_put (XLA manages the H2D stream).
"""

from __future__ import annotations

import itertools
import math
import pickle
import queue
import threading

import numpy as np

from ..framework.random import default_generator
from ..tensor import Tensor


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


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cum = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cum[-1]

    def __getitem__(self, idx):
        for i, c in enumerate(self.cum):
            if idx < c:
                prev = self.cum[i - 1] if i else 0
                return self.datasets[i][idx - prev]
        raise IndexError(idx)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        return itertools.chain(*self.datasets)


def random_split(dataset, lengths, generator=None):
    n = len(dataset)
    if sum(lengths) != n:
        raise ValueError("sum of lengths must equal dataset size")
    import jax

    key = default_generator.next_key()
    perm = np.asarray(jax.random.permutation(key, n))
    out = []
    off = 0
    for ln in lengths:
        out.append(Subset(dataset, perm[off : off + ln].tolist()))
        off += ln
    return out


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------


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
    def __init__(self, data_source, replacement=False, num_samples=None, generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        import jax

        n = len(self.data_source)
        key = default_generator.next_key()
        if self.replacement:
            idx = np.asarray(jax.random.randint(key, (self.num_samples,), 0, n))
        else:
            idx = np.asarray(jax.random.permutation(key, n))[: self.num_samples]
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.default_rng().choice(
            len(self.weights), self.num_samples, replace=self.replacement, p=p
        )
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False, batch_size=1, drop_last=False):
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


class BucketSampler(Sampler):
    """Length-bucketed batch sampler for ragged datasets (SURVEY §7 "hard
    parts: dynamic shapes").  Samples are grouped by the smallest
    `bucket_boundaries` entry >= their length and batched within a bucket;
    with `padded_collate` below every emitted batch has one of
    len(bucket_boundaries) static shapes, so a @to_static train step
    compiles AT MOST once per bucket — the retrace contract — instead of
    once per distinct tail length.

    lengths: per-sample sequence lengths (list/array), or None to derive
    as len(dataset[i][0]) (first field of each sample).
    """

    def __init__(self, dataset=None, lengths=None, bucket_boundaries=(64, 128, 256, 512),
                 batch_size=1, shuffle=False, drop_last=False, seed=0,
                 pad_last_batch=True):
        # pad_last_batch: wrap a bucket's tail batch with indices from the
        # same bucket (the DistributedBatchSampler precedent) so EVERY batch
        # is [batch_size, boundary]-shaped and the <= len(boundaries)
        # compiles contract holds; set False (or drop_last=True) to opt out.
        self.pad_last_batch = pad_last_batch
        if lengths is None:
            if dataset is None:
                raise ValueError("BucketSampler needs `dataset` or `lengths`")
            lengths = [len(dataset[i][0]) for i in range(len(dataset))]
        self.lengths = [int(x) for x in lengths]
        self.boundaries = sorted(int(b) for b in bucket_boundaries)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.seed = seed
        self.epoch = 0
        too_long = [i for i, n in enumerate(self.lengths) if n > self.boundaries[-1]]
        if too_long:
            raise ValueError(
                f"BucketSampler: {len(too_long)} samples exceed the largest "
                f"bucket boundary {self.boundaries[-1]} (first: index "
                f"{too_long[0]}, length {self.lengths[too_long[0]]})"
            )
        self._buckets = {}
        for i, n in enumerate(self.lengths):
            b = next(bd for bd in self.boundaries if n <= bd)
            self._buckets.setdefault(b, []).append(i)

    def bucket_of(self, idx):
        n = self.lengths[idx]
        return next(bd for bd in self.boundaries if n <= bd)

    def set_epoch(self, epoch):
        self.epoch = epoch

    def state_dict(self):
        return {"epoch": int(self.epoch)}

    def set_state_dict(self, state):
        self.set_epoch(int(state.get("epoch", 0)))

    def __iter__(self):
        rng = np.random.RandomState(self.seed + self.epoch) if self.shuffle else None
        batches = []
        for bd in self.boundaries:
            idxs = list(self._buckets.get(bd, []))
            if not idxs:
                continue
            if rng is not None:
                rng.shuffle(idxs)
            for i in range(0, len(idxs), self.batch_size):
                chunk = idxs[i : i + self.batch_size]
                if len(chunk) < self.batch_size:
                    if self.drop_last:
                        continue
                    if self.pad_last_batch:
                        wrap = idxs
                        while len(chunk) < self.batch_size:
                            chunk = chunk + wrap[: self.batch_size - len(chunk)]
                batches.append(chunk)
        if rng is not None:
            rng.shuffle(batches)
        return iter(batches)

    def __len__(self):
        n = 0
        for idxs in self._buckets.values():
            if self.drop_last:
                n += len(idxs) // self.batch_size
            else:
                n += (len(idxs) + self.batch_size - 1) // self.batch_size
        return n


def padded_collate(bucket_boundaries, ragged_fields=(0,), pad_value=0):
    """Collate-fn factory for BucketSampler batches: ragged fields are
    padded (axis 0) to the smallest bucket boundary >= the batch max
    length, and a `lengths` int32 vector is APPENDED to each sample tuple
    so models can build padding masks / flash-attention segment ids
    (models/bert.py turns exactly such masks into Pallas segment ids)."""
    boundaries = sorted(int(b) for b in bucket_boundaries)

    def collate(batch):
        lengths = np.asarray(
            [len(np.asarray(sample[ragged_fields[0]])) for sample in batch], np.int32
        )
        if int(lengths.max()) > boundaries[-1]:
            # an explicit error — a bare StopIteration from next() would
            # surface as an opaque "generator raised StopIteration" (PEP 479)
            raise ValueError(
                f"padded_collate: sample length {int(lengths.max())} exceeds "
                f"the largest bucket boundary {boundaries[-1]}"
            )
        target = next(bd for bd in boundaries if bd >= int(lengths.max()))
        padded = []
        for sample in batch:
            fields = list(sample) if isinstance(sample, (list, tuple)) else [sample]
            for fi in ragged_fields:
                a = np.asarray(fields[fi])
                if a.shape[0] < target:
                    pad = [(0, target - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
                    a = np.pad(a, pad, constant_values=pad_value)
                fields[fi] = a
            padded.append(tuple(fields) + (np.int32(len(np.asarray(sample[ragged_fields[0]]))),))
        return default_collate_fn(padded)

    return collate


class DistributedBatchSampler(BatchSampler):
    """Per-rank sharded sampler (reference:
    python/paddle/io/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None, shuffle=False, drop_last=False):
        from ..distributed import get_rank, get_world_size

        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = list(range(n))
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices += indices[: (self.total_size - n)]
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

    def set_epoch(self, epoch):
        self.epoch = epoch

    def state_dict(self):
        # the epoch seeds the shuffle, so it fully determines this
        # sampler's order — (epoch, batches_consumed) in the loader state
        # pins the exact next batch after a gang restart
        return {"epoch": int(self.epoch)}

    def set_state_dict(self, state):
        self.set_epoch(int(state.get("epoch", 0)))


# ---------------------------------------------------------------------------
# collate + loader
# ---------------------------------------------------------------------------


def _np_collate(batch):
    """default_collate_fn shape, but numpy-only: safe inside forked workers
    (touching jax after fork risks wedging the inherited XLA runtime)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, Tensor):
        return np.stack([s.numpy() for s in batch])
    if isinstance(sample, (int, np.integer)):
        return np.asarray(batch, np.int64)
    if isinstance(sample, (float, np.floating)):
        return np.asarray(batch, np.float32)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [_np_collate(list(items)) for items in transposed]
    if isinstance(sample, dict):
        return {k: _np_collate([d[k] for d in batch]) for k in sample}
    return batch


def _tensorize(obj):
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, list):
        return [_tensorize(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _tensorize(v) for k, v in obj.items()}
    return obj


def default_collate_fn(batch):
    return _tensorize(_np_collate(batch))


class DataLoader:
    def __init__(
        self,
        dataset,
        feed_list=None,
        places=None,
        return_list=True,
        batch_sampler=None,
        batch_size=1,
        shuffle=False,
        drop_last=False,
        collate_fn=None,
        num_workers=0,
        use_buffer_reader=True,
        prefetch_factor=2,
        use_shared_memory=True,
        timeout=0,
        worker_init_fn=None,
        persistent_workers=False,
        prefetch_to_device=False,
    ):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self._custom_collate = collate_fn is not None
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_shared_memory = use_shared_memory
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        # H2D prefetch depth: True -> classic double buffer (the next batch's
        # device_put overlaps the current step), int -> that many buffers
        self.prefetch_to_device = 2 if prefetch_to_device is True else int(prefetch_to_device or 0)
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size, drop_last=drop_last
            )
        # exactly-once resume state (persisted in the checkpoint manifest via
        # checkpoint.save_checkpoint(data_loader=...)): epoch ordinal, batches
        # the CONSUMER has taken this epoch, the global RNG state snapshotted
        # at epoch start (it determines every shuffle drawn from
        # default_generator), and the prefetch-queue high-water mark
        self._epoch = 0
        self._batches_consumed = 0
        self._resume_skip = 0
        self._epoch_rng_state = None
        self._prefetch_hwm = 0

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    # -- exactly-once resume ------------------------------------------------
    def state_dict(self):
        """Data-pipeline position for exactly-once resume.

        ``batches_consumed`` counts batches the consumer has TAKEN from the
        iterator (not what prefetch produced), so restoring it and skipping
        that many index-batches replays nothing and drops nothing — the
        resumed run's first batch is the exact next one."""
        rng = self._epoch_rng_state
        if rng is None:
            rng = np.asarray(default_generator.get_state()).tolist()
        state = {
            "epoch": int(self._epoch),
            "batches_consumed": int(self._batches_consumed),
            "rng_state": rng,
            "prefetch_hwm": int(self._prefetch_hwm),
        }
        bs = self.batch_sampler
        if bs is not None and hasattr(bs, "state_dict"):
            state["sampler"] = bs.state_dict()
        return state

    def set_state_dict(self, state):
        self._epoch = int(state.get("epoch", 0))
        self._resume_skip = int(state.get("batches_consumed", 0))
        self._batches_consumed = self._resume_skip
        # analysis: allow GRAFT010 — restore runs before the producer thread exists; live updates are a monotonic gauge
        self._prefetch_hwm = int(state.get("prefetch_hwm", 0))
        rng = state.get("rng_state")
        if rng is not None:
            # restoring the generator replays the epoch's sampler key draws,
            # so the skipped index-batches are the ones already consumed
            self._epoch_rng_state = [int(x) for x in rng]
            default_generator.set_state(np.asarray(rng, np.uint32))
        bs = self.batch_sampler
        samp = state.get("sampler")
        if bs is not None and samp is not None:
            if hasattr(bs, "set_state_dict"):
                bs.set_state_dict(samp)
            elif hasattr(bs, "set_epoch"):
                bs.set_epoch(int(samp.get("epoch", 0)))
        return self

    load_state_dict = set_state_dict

    def _iter_batches(self, skip=0):
        from ..fault import injection as _inj

        if self._iterable_mode:
            # no random access: count batch boundaries and discard the first
            # `skip` WITHOUT collating them
            emitted = 0
            batch = []
            for sample in self.dataset:
                batch.append(sample)
                if len(batch) == self.batch_size:
                    if emitted >= skip:
                        _inj.inject("dataloader.next")
                        yield self.collate_fn(batch)
                    emitted += 1
                    batch = []
            if batch and not self.drop_last:
                if emitted >= skip:
                    _inj.inject("dataloader.next")
                    yield self.collate_fn(batch)
        else:
            # skip at the index level: consumed batches are never fetched
            # from the dataset again
            for bi, idx_batch in enumerate(self.batch_sampler):
                if bi < skip:
                    continue
                _inj.inject("dataloader.next")
                samples = [self.dataset[i] for i in idx_batch]
                yield self.collate_fn(samples)

    def __iter__(self):
        from ..fault import injection as _inj
        from ..fault import watchdog as _wd

        skip = self._resume_skip
        self._resume_skip = 0
        if skip == 0 or self._epoch_rng_state is None:
            # snapshot BEFORE the sampler draws its shuffle key, so a
            # checkpoint taken mid-epoch can replay the same order
            self._epoch_rng_state = np.asarray(default_generator.get_state()).tolist()
        self._batches_consumed = skip
        src = self._make_iter(skip)
        if self.prefetch_to_device:
            src = self._iter_prefetch_device(src, self.prefetch_to_device)
        while True:
            with _wd.arm("dataloader.next"):
                _inj.inject_hang("dataloader.hang")
                try:
                    batch = next(src)
                except StopIteration:
                    break
            # counted before the consumer runs the step: a checkpoint taken
            # while batch k is being processed reports k+1 consumed
            self._batches_consumed += 1
            yield batch
        self._epoch += 1
        self._batches_consumed = 0
        self._epoch_rng_state = None

    def _make_iter(self, skip):
        if self.num_workers == 0:
            yield from self._iter_batches(skip)
            return
        if self.use_shared_memory and not self._iterable_mode:
            try:
                yield from self._iter_multiprocess(skip)
                return
            except _MPUnavailable:
                pass  # e.g. non-picklable dataset: thread prefetch below
        yield from self._iter_threaded(skip)

    def _iter_threaded(self, skip=0):
        # background-thread prefetch pipeline (GIL-bound but zero-copy)
        q = queue.Queue(maxsize=self.num_workers * self.prefetch_factor)
        sentinel = object()

        def producer():
            try:
                for b in self._iter_batches(skip):
                    q.put(b)
                    if q.qsize() > self._prefetch_hwm:
                        self._prefetch_hwm = q.qsize()
            except BaseException as e:
                # poison pill: without it a dying producer looks like a
                # clean end-of-epoch and the error is silently swallowed
                q.put(_Poison(e))
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            if isinstance(item, _Poison):
                raise item.exc
            yield item

    def _iter_prefetch_device(self, src, depth):
        """Double-buffered H2D stage: a background thread device_put()s the
        NEXT batch while the consumer's current step runs, so the host→HBM
        transfer overlaps compute instead of serializing ahead of each
        dispatch.  Placement is sharding-aware — it reuses the dp input
        placement from fleet.meta_parallel.parallel_wrappers, so prefetched
        batches arrive exactly where DataParallel would put them (its
        _shard_input then recognizes them as already placed).

        Sits BETWEEN the batch producer and __iter__'s consumer counting:
        batches sitting in the device buffer are not yet "consumed", so the
        exactly-once state_dict/resume contract is unchanged — a checkpoint
        taken mid-epoch replays nothing and drops nothing."""
        from ..distributed.fleet.meta_parallel.parallel_wrappers import dp_device_put

        def _put(obj):
            if isinstance(obj, Tensor):
                t = Tensor.__new__(Tensor)
                return t._init_from_array(dp_device_put(obj._raw), stop_gradient=obj.stop_gradient)
            if isinstance(obj, np.ndarray):
                t = Tensor.__new__(Tensor)
                return t._init_from_array(dp_device_put(obj))
            if isinstance(obj, list):
                return [_put(o) for o in obj]
            if isinstance(obj, tuple):
                return tuple(_put(o) for o in obj)
            if isinstance(obj, dict):
                return {k: _put(v) for k, v in obj.items()}
            return obj

        q = queue.Queue(maxsize=max(1, depth - 1))
        sentinel = object()

        def producer():
            try:
                for b in src:
                    q.put(_put(b))  # device_put dispatches async: the copy
                    # engines run while the consumer computes
                    if q.qsize() > self._prefetch_hwm:
                        self._prefetch_hwm = q.qsize()
            except BaseException as e:
                q.put(_Poison(e))  # original exception, not a silent epoch end
            finally:
                q.put(sentinel)

        t = threading.Thread(target=producer, daemon=True, name="h2d-prefetch")
        t.start()
        while True:
            item = q.get()
            if item is sentinel:
                break
            if isinstance(item, _Poison):
                raise item.exc
            yield item

    def _iter_multiprocess(self, skip=0):
        """Multiprocess workers (reference: paddle.io.DataLoader
        num_workers>0 — _DataLoaderIterMultiProcess): each worker process
        collates whole index-batches; results return via pickle over a
        multiprocessing queue, ordered by batch index.  Falls back to the
        thread path when the dataset/collate can't cross a fork."""
        import multiprocessing as mp

        try:
            ctx = mp.get_context("fork")
        except ValueError as e:
            raise _MPUnavailable(str(e))

        batches = list(self.batch_sampler)[skip:]
        nw = min(self.num_workers, max(len(batches), 1))
        task_q = ctx.Queue()
        out_q = ctx.Queue(maxsize=nw * self.prefetch_factor)

        # workers collate to NUMPY (never jax: touching the inherited XLA
        # runtime in a fork child can wedge it); the parent tensorizes.
        # A custom collate_fn runs in the worker as given — its output must
        # be picklable and should be numpy/python.
        collate = self.collate_fn if self._custom_collate else _np_collate

        def worker(wid):
            global _worker_info
            _worker_info = WorkerInfo(wid, nw, self.dataset)
            if self.worker_init_fn is not None:
                self.worker_init_fn(wid)
            while True:
                item = task_q.get()
                if item is None:
                    return
                bi, idxs = item
                try:
                    # pickle EXPLICITLY: mp.Queue serializes in a feeder
                    # thread, where a PicklingError would vanish into the
                    # child's stderr and hang the parent
                    blob = pickle.dumps(collate([self.dataset[i] for i in idxs]))
                    out_q.put((bi, blob, None))
                except Exception as e:  # surface in parent with batch index
                    out_q.put((bi, None, f"{type(e).__name__}: {e}"))

        procs = [ctx.Process(target=worker, args=(w,), daemon=True) for w in range(nw)]
        try:
            for p in procs:
                p.start()
        except Exception as e:
            raise _MPUnavailable(str(e))
        try:
            for bi, idxs in enumerate(batches):
                task_q.put((bi, list(idxs)))
            for _ in range(nw):
                task_q.put(None)
            # reorder: workers complete out of order, iteration must not
            pending = {}
            want = 0
            got = 0
            # paddle semantics: timeout=0 waits forever; a positive timeout
            # bounds the wait (useful because fork children of a
            # jax-threaded parent can, rarely, inherit a held lock and
            # wedge — set a timeout to get an actionable error)
            timeout = self.timeout if self.timeout else None
            while got < len(batches):
                try:
                    bi, blob, err = out_q.get(timeout=timeout)
                except queue.Empty:
                    raise RuntimeError(
                        f"DataLoader worker produced nothing for {timeout}s — "
                        "a fork()ed worker may have deadlocked on a lock "
                        "inherited from the jax-threaded parent; retry, or "
                        "use use_shared_memory=False for thread-based workers"
                    ) from None
                got += 1
                batch = None if blob is None else pickle.loads(blob)
                if err is not None:
                    raise RuntimeError(f"DataLoader worker failed on batch {bi}: {err}")
                pending[bi] = batch
                while want in pending:
                    b = pending.pop(want)
                    yield b if self._custom_collate else _tensorize(b)
                    want += 1
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    # forked with the parent's SIGTERM handler and wedged on
                    # an inherited lock, a worker never runs it: SIGTERM is
                    # swallowed, and the orphan keeps the parent's stderr open
                    p.kill()
                    p.join(timeout=5)


class _MPUnavailable(RuntimeError):
    pass


class _Poison:
    """Queue marker carrying a worker-thread exception to the consumer."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class WorkerInfo:
    def __init__(self, id, num_workers, dataset):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None  # set inside forked DataLoader workers


def get_worker_info():
    """Inside a DataLoader worker process: (id, num_workers, dataset) for
    per-worker sharding (reference: paddle.io.get_worker_info); None in the
    main process."""
    return _worker_info
