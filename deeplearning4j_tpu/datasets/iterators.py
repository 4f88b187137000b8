"""DataSet iterator framework with async (background-thread) prefetch.

Reference: datasets/iterator/ — AsyncDataSetIterator.java:30-64 (background
AsyncPrefetchThread + LinkedBlockingQueue; the ETL/compute overlap boundary
in the fit() stack, MultiLayerNetwork.java:1170), MultipleEpochsIterator,
EarlyTerminationDataSetIterator, SamplingDataSetIterator,
ExistingDataSetIterator, BenchmarkDataSetIterator (synthetic-data throughput
harness, impl/BenchmarkDataSetIterator.java:20).

TPU-native: prefetch overlaps host ETL with device compute; the device_put of
the next batch is issued by the fit loop while the current step runs
(training/engine.py, one batch of look-ahead).
"""
from __future__ import annotations

import itertools
import queue
import threading
import time
from typing import Iterator, List, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet


class DataSetIterator:
    """Iterator protocol: python-iterable over DataSet + reset()/batch().

    `set_pre_processor(normalizer)` attaches a DataSetPreProcessor
    (DataSetIterator.setPreProcessor in the reference — how normalizers
    ride the input pipeline): every yielded batch passes through
    `pre_processor.transform(ds)` (or a bare callable), applied centrally
    by wrapping each subclass's __next__ at class-creation time so no
    subclass needs to remember the hook.

    The arrays of a yielded batch must stay unmodified through the
    FOLLOWING `__next__`: the fit loop hands batch k to the runtime and
    asks for batch k+1 while that transfer may still be reading them
    (training/engine.py, one batch of look-ahead). From the call after
    that they may be overwritten — rotate two buffers, never refill one
    in place (docs/PERFORMANCE.md)."""

    pre_processor = None

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        raw = cls.__dict__.get("__next__")
        if raw is not None and not getattr(raw, "_applies_pre_processor",
                                           False):
            def wrapped(self, _raw=raw):
                ds = _raw(self)
                pp = self.pre_processor
                if pp is None:
                    return ds
                return (pp.transform(ds) if hasattr(pp, "transform")
                        else pp(ds))

            wrapped._applies_pre_processor = True
            cls.__next__ = wrapped

    def set_pre_processor(self, p) -> "DataSetIterator":
        self.pre_processor = p
        return self

    def __iter__(self) -> Iterator[DataSet]:
        self.reset()
        return self

    def __next__(self) -> DataSet:
        raise NotImplementedError

    def reset(self):
        pass

    def batch_size(self) -> int:
        raise NotImplementedError

    def total_outcomes(self) -> int:
        return -1

    def input_columns(self) -> int:
        return -1

    def async_supported(self) -> bool:
        return True


class ListDataSetIterator(DataSetIterator):
    """Iterate over an in-memory DataSet in minibatches
    (datasets/iterator/impl/ListDataSetIterator.java)."""

    def __init__(self, data: DataSet, batch: int = 32, shuffle_each_epoch: bool = False,
                 seed: int = 0):
        self.data = data
        self.batch = batch
        self.shuffle_each_epoch = shuffle_each_epoch
        self._seed = seed
        self._epoch = 0
        self._pos = 0

    def reset(self):
        self._pos = 0
        if self.shuffle_each_epoch:
            self.data.shuffle(self._seed + self._epoch)
            self._epoch += 1

    def __next__(self):
        if self._pos >= self.data.num_examples():
            raise StopIteration
        lo, hi = self._pos, self._pos + self.batch
        self._pos = hi
        return DataSet(
            self.data.features[lo:hi], self.data.labels[lo:hi],
            None if self.data.features_mask is None else self.data.features_mask[lo:hi],
            None if self.data.labels_mask is None else self.data.labels_mask[lo:hi],
        )

    def batch_size(self):
        return self.batch

    def total_outcomes(self):
        return int(self.data.labels.shape[-1])

    def input_columns(self):
        return int(np.prod(self.data.features.shape[1:]))


class ExistingDataSetIterator(DataSetIterator):
    """Wrap a python iterable of DataSets."""

    def __init__(self, iterable: Sequence[DataSet]):
        self._src = list(iterable)
        self._pos = 0

    def reset(self):
        self._pos = 0

    def __next__(self):
        if self._pos >= len(self._src):
            raise StopIteration
        d = self._src[self._pos]
        self._pos += 1
        return d

    def batch_size(self):
        return self._src[0].num_examples() if self._src else 0


class AsyncDataSetIterator(DataSetIterator):
    """Background-thread prefetch with bounded queue
    (AsyncDataSetIterator.java:30-64). Wraps any DataSetIterator; fit() wraps
    automatically like MultiLayerNetwork.fit :1170 does.

    The producer thread is named (``AsyncDataSetIterator-prefetch-N``) and
    daemonized so it is attributable in thread dumps — and, when telemetry
    is on, registered as its own lane in the Chrome trace. Each producer
    carries a stop event: ``reset()``/``shutdown()`` signal it, drain the
    queue to its sentinel, and join, so a stale producer can never keep
    feeding a replaced queue and no queue ever holds a double sentinel.
    With ``DL4J_TPU_TELEMETRY`` on, consumer fetches record queue depth +
    wait seconds and producers record full-queue wait seconds — the raw
    signals behind ``telemetry.health.input_verdict()`` (docs/HEALTH.md).

    ``place`` (optional callable DataSet -> DataSet) runs on the PRODUCER
    thread before each enqueue — a caller's own placement or transform
    (``training.engine.place_batch`` with ``jax.device_put`` makes the
    bounded queue hold device-resident batches: ``queue_size`` + 2 of
    them at once). The fit paths pass none: the fit loop itself hands
    batch k+1 to the runtime while step k runs (docs/PERFORMANCE.md
    "One batch of look-ahead"). A raising ``place`` surfaces on the
    consumer like any producer error, and the stop/drain/join teardown
    is unchanged — in-flight device batches are simply dropped."""

    _END = object()
    _ids = itertools.count()

    def __init__(self, underlying: DataSetIterator,
                 queue_size: Optional[int] = None, place=None):
        self.underlying = underlying
        # None = resolve DL4J_TPU_PREFETCH_DEPTH at each (re)start — a
        # LIVE knob: the queue is rebuilt on every reset(), so a tuner
        # override lands at the next epoch boundary without touching a
        # running producer (docs/TUNING.md). An explicit int pins the
        # depth (ParallelWrapper's prefetch_buffer, tests).
        self.queue_size = queue_size
        self.place = place
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._stop: Optional[threading.Event] = None
        self._error: Optional[BaseException] = None

    def prefetch_depth(self) -> int:
        """Effective bounded-queue depth for the NEXT producer start."""
        if self.queue_size is not None:
            return max(1, int(self.queue_size))
        from deeplearning4j_tpu.util import envflags

        return max(1, envflags.int_value("DL4J_TPU_PREFETCH_DEPTH", 4))

    def _start(self):
        q = self._q = queue.Queue(maxsize=self.prefetch_depth())
        stop = self._stop = threading.Event()
        self._error = None
        name = f"{type(self).__name__}-prefetch-{next(self._ids)}"

        def worker():
            from deeplearning4j_tpu.telemetry import health as health_mod
            from deeplearning4j_tpu.telemetry import trace as trace_mod

            mon = health_mod.live()
            if mon is not None:
                trace_mod.tracer().set_thread_name(
                    threading.get_ident(), name)
            try:
                # with `place`, the host->device copy is issued HERE,
                # overlapped with the consumer's compute on the prior batch
                source = (self.underlying if self.place is None
                          else map(self.place, self.underlying))
                # `produce`: this thread's share of the feed, on the
                # profiler's clock beside the fit thread's `etl`
                for d in trace_mod.tracer().spanned("produce", source,
                                                    category="data"):
                    t0 = time.perf_counter()
                    while not stop.is_set():
                        try:
                            q.put(d, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if stop.is_set():
                        break
                    if mon is not None:
                        mon.record_producer_wait(time.perf_counter() - t0)
            except BaseException as e:  # surfaced on the consumer side
                self._error = e
            finally:
                # The sentinel always lands: on cancellation the
                # resetter is draining this queue, otherwise the consumer
                # is pulling from it.
                q.put(self._END)

        self._thread = threading.Thread(target=worker, daemon=True,
                                        name=name)
        self._thread.start()

    def _stop_worker(self):
        """Signal, drain to the sentinel, and join the producer (no-op
        when none is running). Guarantees no stale producer survives and
        the next ``_start`` begins from a fresh queue."""
        t = self._thread
        if t is None:
            return
        if self._stop is not None:
            self._stop.set()
        if t.is_alive():
            while self._q.get() is not self._END:
                pass
        t.join(timeout=10.0)
        self._thread = None
        self._stop = None

    def reset(self):
        self._stop_worker()
        self._start()

    def shutdown(self):
        """Stop the producer thread and release the queue. Idempotent —
        safe to call repeatedly or on a never-started iterator; a later
        iteration simply starts a fresh producer."""
        self._stop_worker()
        self._q = None

    def __iter__(self):
        self.reset()
        return self

    def __next__(self):
        if self._q is None:
            self._start()
        from deeplearning4j_tpu.telemetry import health as health_mod

        mon = health_mod.live()
        if mon is None:
            item = self._q.get()
        else:
            depth = self._q.qsize()
            t0 = time.perf_counter()
            item = self._q.get()
            mon.record_consumer(depth, time.perf_counter() - t0)
        if item is self._END:
            # Re-enqueue the sentinel so further next() calls (e.g. a
            # round-robin consumer revisiting an exhausted stream) see
            # StopIteration again instead of blocking on an empty queue
            # whose worker thread has exited.
            self._q.put(self._END)
            if self._error is not None:
                raise self._error
            raise StopIteration
        return item

    def batch_size(self):
        return self.underlying.batch_size()

    def total_outcomes(self):
        return self.underlying.total_outcomes()


class MultipleEpochsIterator(DataSetIterator):
    """Repeat an iterator for N epochs (MultipleEpochsIterator.java)."""

    def __init__(self, epochs: int, underlying: DataSetIterator):
        self.epochs = epochs
        self.underlying = underlying
        self._epoch = 0
        self._inner: Optional[Iterator] = None

    def reset(self):
        self._epoch = 0
        self._inner = iter(self.underlying)

    def __next__(self):
        if self._inner is None:
            self.reset()
        while True:
            try:
                return next(self._inner)
            except StopIteration:
                self._epoch += 1
                if self._epoch >= self.epochs:
                    raise
                self._inner = iter(self.underlying)

    def batch_size(self):
        return self.underlying.batch_size()


class EarlyTerminationDataSetIterator(DataSetIterator):
    """Cap the number of minibatches (EarlyTerminationDataSetIterator.java)."""

    def __init__(self, underlying: DataSetIterator, max_batches: int):
        self.underlying = underlying
        self.max_batches = max_batches
        self._count = 0

    def reset(self):
        self._count = 0
        self.underlying.reset()

    def __iter__(self):
        self.reset()
        self._inner = iter(self.underlying)
        return self

    def __next__(self):
        if self._count >= self.max_batches:
            raise StopIteration
        self._count += 1
        return next(self._inner)

    def batch_size(self):
        return self.underlying.batch_size()


class SamplingDataSetIterator(DataSetIterator):
    """Sample `batch` examples with replacement from a DataSet each step
    (SamplingDataSetIterator.java)."""

    def __init__(self, data: DataSet, batch: int, total_batches: int, seed: int = 0):
        self.data = data
        self.batch = batch
        self.total_batches = total_batches
        self._rng = np.random.default_rng(seed)
        self._count = 0

    def reset(self):
        self._count = 0

    def __next__(self):
        if self._count >= self.total_batches:
            raise StopIteration
        self._count += 1
        idx = self._rng.integers(0, self.data.num_examples(), self.batch)
        return DataSet(self.data.features[idx], self.data.labels[idx])

    def batch_size(self):
        return self.batch


class BenchmarkDataSetIterator(DataSetIterator):
    """Infinite synthetic batches of fixed shape for throughput measurement
    without I/O (impl/BenchmarkDataSetIterator.java:20). The single allocated
    batch is reused every step, so iteration cost is ~zero."""

    def __init__(self, feature_shape: Sequence[int], num_classes: int,
                 total_batches: int = 100, seed: int = 0,
                 label_shape: Optional[Sequence[int]] = None):
        rng = np.random.default_rng(seed)
        feats = rng.standard_normal(tuple(feature_shape), dtype=np.float32)
        if label_shape is None:
            batch = feature_shape[0]
            ids = rng.integers(0, num_classes, batch)
            labels = np.zeros((batch, num_classes), np.float32)
            labels[np.arange(batch), ids] = 1.0
        else:
            labels = rng.standard_normal(tuple(label_shape)).astype(np.float32)
        self._ds = DataSet(feats, labels)
        self.total_batches = total_batches
        self._count = 0

    def reset(self):
        self._count = 0

    def __next__(self):
        if self._count >= self.total_batches:
            raise StopIteration
        self._count += 1
        return self._ds

    def batch_size(self):
        return self._ds.num_examples()

    def total_outcomes(self):
        return int(self._ds.labels.shape[-1])


class AsyncMultiDataSetIterator(AsyncDataSetIterator):
    """Background prefetch over MultiDataSet streams
    (AsyncMultiDataSetIterator.java) — same bounded-queue machinery; the
    payload type is opaque to the worker thread."""


class AsyncShieldDataSetIterator(DataSetIterator):
    """Marker wrapper: tells fit() NOT to wrap this iterator in async
    prefetch (AsyncShieldDataSetIterator.java) — for underlying iterators
    that are not thread-safe or already prefetch internally."""

    def __init__(self, underlying: DataSetIterator):
        self.underlying = underlying

    def reset(self):
        self.underlying.reset()

    def __iter__(self):
        self.underlying.reset()
        return self

    def __next__(self):
        return next(self.underlying)

    def batch_size(self):
        return self.underlying.batch_size()

    def total_outcomes(self):
        return self.underlying.total_outcomes()

    def async_supported(self):
        return False


class AsyncShieldMultiDataSetIterator(AsyncShieldDataSetIterator):
    """MultiDataSet flavor of the async shield
    (AsyncShieldMultiDataSetIterator.java)."""


class JointParallelDataSetIterator(DataSetIterator):
    """Per-consumer (per-device) iterator affinity
    (datasets/iterator/parallel/JointParallelDataSetIterator.java +
    parallelism/MagicQueue.java): N underlying iterators, one per consumer;
    `next_for(i)` serves consumer i from its own stream with its own async
    prefetch thread, so multi-replica training never serializes on one host
    ETL loop. Plain `next()` round-robins (INTERLEAVE mode)."""

    def __init__(self, *iterators: DataSetIterator, prefetch: int = 2):
        if not iterators:
            raise ValueError("need at least one underlying iterator")
        self.streams = [AsyncDataSetIterator(u, prefetch) for u in iterators]
        self._pos = 0

    def attached(self) -> int:
        return len(self.streams)

    def next_for(self, consumer: int) -> DataSet:
        ds = next(self.streams[consumer % len(self.streams)])
        # per-consumer path bypasses the wrapped __next__, so apply the
        # attached pre-processor here too
        pp = self.pre_processor
        if pp is not None:
            ds = pp.transform(ds) if hasattr(pp, "transform") else pp(ds)
        return ds

    def reset(self):
        for s in self.streams:
            s.reset()
        self._pos = 0

    def shutdown(self):
        """Stop every per-consumer prefetch thread (idempotent)."""
        for s in self.streams:
            s.shutdown()

    def __iter__(self):
        self.reset()
        return self

    def __next__(self):
        n = len(self.streams)
        for _ in range(n):  # skip exhausted streams (uneven lengths)
            i = self._pos % n
            self._pos += 1
            try:
                return next(self.streams[i])
            except StopIteration:
                continue
        raise StopIteration

    def batch_size(self):
        return self.streams[0].batch_size()

    def total_outcomes(self):
        return self.streams[0].total_outcomes()


class BucketSequenceIterator(DataSetIterator):
    """Recompile protection for ragged sequence data (SURVEY §7 'dynamic
    shapes vs XLA static shapes' hard part).

    Every distinct sequence length reaching a jitted train/output step
    compiles a fresh executable; a corpus of N distinct lengths means N
    multi-second compiles. The reference runs on JVM dynamic shapes and
    pads ad hoc (MaskedReductionUtil handles the tail) — the TPU answer
    is to QUANTIZE: each batch's time axis is padded up to the smallest
    admitted bucket boundary (powers of two by default, or explicit
    `buckets`), and features/labels masks are created or extended so the
    padded steps are dead under the reference's masking semantics. The
    compile count is then bounded by the bucket count regardless of how
    many raw lengths the data contains (`tests/test_fetchers_iterators.py`
    pins this).

    Labels whose time axis matches the features' (RnnOutput targets) are
    padded alongside; per-example-vector labels pass through untouched.
    """

    def __init__(self, underlying: DataSetIterator, buckets=None,
                 max_length: int = 4096):
        self.underlying = underlying
        if buckets is not None:
            self.buckets = sorted(int(b) for b in buckets)
        else:
            self.buckets = []
            p = 1
            while p < max_length:
                p *= 2
                self.buckets.append(p)
        self._emitted: set = set()
        self._it = iter(underlying)

    def bucket_for(self, t: int) -> int:
        for b in self.buckets:
            if t <= b:
                return b
        return t  # beyond the largest bucket: pass through unpadded

    def emitted_lengths(self) -> set:
        """Distinct padded lengths produced so far — the bounded-compile
        guarantee made inspectable."""
        return set(self._emitted)

    @staticmethod
    def _pad_time(a: np.ndarray, t_new: int) -> np.ndarray:
        pad = [(0, 0)] * a.ndim
        pad[1] = (0, t_new - a.shape[1])
        return np.pad(a, pad)

    def __next__(self):
        ds = next(self._it)
        f = np.asarray(ds.features)
        if f.ndim != 3:
            return ds  # not sequence data: nothing to quantize
        t = f.shape[1]
        tb = self.bucket_for(t)
        self._emitted.add(tb)
        if tb == t and (not self.buckets or t > self.buckets[-1]):
            return ds  # beyond the largest bucket: true passthrough
        # A features_mask is materialized even for batches that exactly
        # hit a boundary: a mask=None batch and a padded batch at the
        # same bucket would trace two different pytree structures — two
        # compiles for one bucket, breaking the bounded-compile contract.
        fm = (np.asarray(ds.features_mask) if ds.features_mask is not None
              else np.ones((f.shape[0], t), np.float32))
        out_f = self._pad_time(f, tb)
        out_fm = self._pad_time(fm, tb)
        # label-less datasets (pretrain iterators) must stay label-less:
        # np.asarray(None) is a 0-d object array that breaks downstream
        # `labels is None` checks
        labels = ds.labels if ds.labels is None else np.asarray(ds.labels)
        # labels_mask is padded only when the source HAD one — fabricating
        # an all-ones mask would override the loss's fall-back to the
        # features mask and resurrect steps the original data masked dead
        lm = ds.labels_mask
        if labels is not None and labels.ndim == 3 and labels.shape[1] == t:
            labels = self._pad_time(labels, tb)
            if lm is not None:
                lm = self._pad_time(np.asarray(lm), tb)
        return DataSet(out_f, labels, out_fm, lm)

    def __iter__(self):
        self.reset()
        return self

    def reset(self):
        self._it = iter(self.underlying)

    def batch_size(self):
        return self.underlying.batch_size()

    def total_outcomes(self):
        return self.underlying.total_outcomes()

    def input_columns(self):
        return self.underlying.input_columns()


def prefetch_to_device(iterator, size: int = 2, sharding=None):
    """Generator that overlaps host->device transfer with device compute —
    the TPU-native AsyncDataSetIterator analogue from SURVEY.md §7
    ('host-side prefetch + jax.device_put double-buffering'). Yields batches
    already resident on device (optionally placed with a NamedSharding for
    pjit consumption)."""
    import collections

    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet

    def put(a):
        if a is None:
            return None
        return jax.device_put(a, sharding) if sharding is not None else jax.device_put(a)

    def _put(ds):
        if isinstance(ds, DataSet):
            return DataSet(put(ds.features), put(ds.labels),
                           put(ds.features_mask), put(ds.labels_mask))
        if isinstance(ds, MultiDataSet):
            return MultiDataSet(
                [put(f) for f in ds.features],
                [put(l) for l in ds.labels],
                [put(m) for m in ds.features_masks] if ds.features_masks else None,
                [put(m) for m in ds.labels_masks] if ds.labels_masks else None)
        return jax.tree_util.tree_map(put, ds)

    buf = collections.deque()
    it_ = iter(iterator)
    for ds in it_:
        buf.append(_put(ds))
        if len(buf) >= size:
            yield buf.popleft()
    while buf:
        yield buf.popleft()
