"""Data iterators.

Parity: reference ``python/mxnet/io.py`` (DataIter/DataBatch/DataDesc,
NDArrayIter, ResizeIter, PrefetchingIter) plus Python-native equivalents of
the C++ iterators in ``src/io/`` (MNISTIter ← iter_mnist.cc, CSVIter ←
iter_csv.cc, ImageRecordIter ← iter_image_recordio_2.cc). The reference's
PrefetcherIter double-buffering (iter_prefetcher.h) is kept, with produce
ops scheduled on the host dependency engine (mxnet_tpu.engine) — the
host-side pipeline design SURVEY.md §7 maps 1:1.
"""
from __future__ import annotations

import gzip
import os
import struct
import time
from collections import deque, namedtuple

import numpy as np

from . import ndarray as nd
from . import telemetry as _tm
from .base import MXNetError
from .ndarray import NDArray

DataDesc = namedtuple("DataDesc", ["name", "shape"])

_H_FEED_WAIT = _tm.histogram(
    "io.feed_wait_seconds",
    "Host time DeviceFeedIter.next() spends handing over the staged "
    "batch and re-filling the pipeline (the device transfers themselves "
    "are async and overlap compute)")


class DataBatch(object):
    """One mini-batch (parity io.py:82)."""

    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter(object):
    """Base iterator (parity io.py:143)."""

    def __init__(self):
        self.batch_size = 0

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(),
                pad=self.getpad(), index=self.getindex()
            )
        raise StopIteration

    def __next__(self):
        return self.next()

    def iter_next(self):
        pass

    def skip(self, num_batches):
        """Advance past ``num_batches`` batches without using them —
        checkpoint resume repositions a freshly reset iterator this way.
        The generic fallback simply consumes batches; iterators with a
        cheap cursor (NDArrayIter, DeviceFeedIter) override it."""
        for _ in range(int(num_batches)):
            try:
                self.next()
            except StopIteration:
                return

    def getdata(self):
        pass

    def getlabel(self):
        pass

    def getindex(self):
        return None

    def getpad(self):
        pass


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches (parity io.py:233)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__()
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        self.batch_size = data_iter.batch_size

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class PrefetchingIter(DataIter):
    """Prefetcher over one or more iterators, scheduled on the host
    dependency engine.

    Parity: io.py:298 (python PrefetchingIter) and the native
    PrefetcherIter (src/io/iter_prefetcher.h) — the next batch is
    produced on an engine worker while the caller consumes the current
    one, so host decode overlaps device compute. Each source iterator
    owns an engine Var; produce ops take it as their mutable var, which
    serializes production per source exactly like the reference's
    engine-var discipline (and under MXNET_ENGINE_TYPE=NaiveEngine the
    whole pipeline runs synchronously — the same debug escape hatch,
    threaded_engine.h:329, applied to host IO).
    """

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        from . import engine as _engine

        if not isinstance(iters, list):
            iters = [iters]
        self.n_iter = len(iters)
        assert self.n_iter > 0
        self.iters = iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0].shape[0]
        self._engine = _engine.get()
        self._slots = [self._engine.new_variable()
                       for _ in range(self.n_iter)]
        self.current_batch = None
        self.next_batch = [None] * self.n_iter
        self._errors = [None] * self.n_iter
        self._pending = [None] * self.n_iter  # opr handles from push()
        self._prefetch_all()

    def _prefetch(self, i):
        def _produce():
            try:
                self.next_batch[i] = self.iters[i].next()
            except StopIteration:
                self.next_batch[i] = None
            except Exception as e:  # surfaced in the consumer thread —
                # swallowing it would silently re-serve a stale batch
                self.next_batch[i] = None
                self._errors[i] = e

        self._pending[i] = self._engine.push(
            _produce, mutable_vars=(self._slots[i],))

    def _prefetch_all(self):
        for i in range(self.n_iter):
            self._prefetch(i)

    def _await_batches(self):
        for i, opr in enumerate(self._pending):
            # wait on the produce op itself when the engine hands back a
            # completion handle — a wait_for_var would push a whole extra
            # read-op per batch; engines without handles fall back to it
            if opr is not None and hasattr(opr, "done"):
                opr.done.wait()
            else:
                self._engine.wait_for_var(self._slots[i])
        for i, err in enumerate(self._errors):
            if err is not None:
                self._errors[i] = None
                raise err

    @property
    def provide_data(self):
        if self.rename_data is None:
            return sum([i.provide_data for i in self.iters], [])
        return sum(
            [
                [
                    DataDesc(r[x.name], x.shape)
                    if isinstance(x, DataDesc)
                    else DataDesc(r[x[0]], x[1])
                    for x in i.provide_data
                ]
                for r, i in zip(self.rename_data, self.iters)
            ],
            [],
        )

    @property
    def provide_label(self):
        if self.rename_label is None:
            return sum([i.provide_label for i in self.iters], [])
        return sum(
            [
                [
                    DataDesc(r[x.name], x.shape)
                    if isinstance(x, DataDesc)
                    else DataDesc(r[x[0]], x[1])
                    for x in i.provide_label
                ]
                for r, i in zip(self.rename_label, self.iters)
            ],
            [],
        )

    def reset(self):
        self._await_batches()  # let in-flight produces land first
        for i in self.iters:
            i.reset()
        self._prefetch_all()

    def iter_next(self):
        self._await_batches()
        if self.next_batch[0] is None:
            for i in self.next_batch:
                assert i is None, "Number of entry mismatches between iterators"
            return False
        for batch in self.next_batch:
            assert batch.pad == self.next_batch[0].pad, (
                "Number of entry mismatches between iterators"
            )
        self.current_batch = DataBatch(
            sum([batch.data for batch in self.next_batch], []),
            sum([batch.label for batch in self.next_batch], []),
            self.next_batch[0].pad,
            self.next_batch[0].index,
            provide_data=self.provide_data,
            provide_label=self.provide_label,
        )
        # produce the NEXT round while the caller consumes this one
        self._prefetch_all()
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class DeviceFeedIter(DataIter):
    """Device-resident double-buffered feed: overlap the host->device
    batch transfer with device compute (the input stage of the async
    dispatch pipeline, docs/performance.md).

    Wraps any DataIter and keeps up to ``depth`` upcoming batches'
    ``jax.device_put`` transfers IN FLIGHT onto ``sharding`` (e.g. a
    fused trainer's dp-sharded ``batch_sharding()``; for dp×tp meshes
    ``PartitionSpec('dp')`` shards rows over dp and replicates over the
    other axes). device_put is async: by the time the consumer finishes
    computing step i, step i+1's bytes are already resident, and
    Module's fused path recognizes the placement (sharding equality in
    ``_make_fused_batch``) and hands the arrays straight to the
    compiled step — the per-step synchronous asnumpy + device_put
    disappears from the hot loop.

    The reference's PrefetcherIter (iter_prefetcher.h) overlaps host
    DECODE with compute; this adds the host->device TRANSFER overlap
    that TF's input pipelines treat as structural (Abadi et al.,
    arXiv:1605.08695). Labels ride ``label_sharding`` when given,
    ``sharding`` otherwise.

    ``BaseModule.fit`` wraps the training iterator automatically when
    the fused path engages (opt out with MXTPU_DEVICE_FEED=0); wrap
    manually for custom loops. Not used on multi-process feeds (each
    process holds only its local rows — make_array_from_process_local_data
    territory).
    """

    def __init__(self, data_iter, sharding, label_sharding=None, depth=None):
        super().__init__()
        if depth is None:
            try:
                depth = int(os.environ.get("MXTPU_FEED_DEPTH", "2"))
            except ValueError:
                depth = 2
        if depth < 1:
            raise MXNetError("DeviceFeedIter depth must be >= 1, got %d"
                             % depth)
        self.iter = data_iter
        self.depth = depth
        self._sharding = sharding
        self._label_sharding = (label_sharding if label_sharding is not None
                                else sharding)
        self.batch_size = data_iter.batch_size
        self._staged = deque()
        self._exhausted = False
        self.current_batch = None
        self._fill()

    @property
    def provide_data(self):
        return self.iter.provide_data

    @property
    def provide_label(self):
        return self.iter.provide_label

    def _place(self, arr, sharding):
        import jax

        data = arr._data if isinstance(arr, NDArray) else np.asarray(arr)
        return NDArray(jax.device_put(data, sharding))

    def _stage_one(self):
        """Pull one host batch and ENQUEUE its device transfer (async:
        device_put returns immediately; the copy overlaps compute)."""
        if self._exhausted:
            return False
        try:
            b = self.iter.next()
        except StopIteration:
            self._exhausted = True
            return False
        self._staged.append(DataBatch(
            data=[self._place(a, self._sharding) for a in (b.data or [])],
            label=[self._place(a, self._label_sharding)
                   for a in (b.label or [])],
            pad=b.pad, index=b.index, bucket_key=b.bucket_key,
            provide_data=b.provide_data, provide_label=b.provide_label,
        ))
        return True

    def _fill(self):
        with _tm.span("io.feed_fill"):
            while len(self._staged) < self.depth and self._stage_one():
                pass

    def reset(self):
        # staged transfers are abandoned, not awaited: jax arrays are
        # immutable, so dropping the references mid-flight is safe
        self._staged.clear()
        self._exhausted = False
        self.current_batch = None
        self.iter.reset()
        self._fill()

    def rewind(self, seek_inner):
        """Guardrail rewind repositioning: drop every staged transfer
        (mid-flight abandonment is safe — jax arrays are immutable),
        hand the INNER iterator to ``seek_inner`` for repositioning
        (``seek_epoch``/``reset``), then restage from the new cursor."""
        self._staged.clear()
        self._exhausted = False
        self.current_batch = None
        seek_inner(self.iter)
        self._fill()

    def skip(self, num_batches):
        """Resume repositioning: drop already-staged transfers first
        (their references die; jax arrays are immutable so mid-flight
        abandonment is safe), push the remainder down to the inner
        iterator's (possibly O(1)) skip, then restage."""
        num_batches = int(num_batches)
        while num_batches > 0 and self._staged:
            self._staged.popleft()
            num_batches -= 1
        if num_batches > 0:
            self.iter.skip(num_batches)
        self._fill()

    def next(self):
        t0 = time.perf_counter()
        if not self._staged:
            self._fill()
        if not self._staged:
            raise StopIteration
        self.current_batch = self._staged.popleft()
        self._fill()  # keep `depth` transfers in flight
        _H_FEED_WAIT.observe(time.perf_counter() - t0)
        return self.current_batch

    def iter_next(self):
        try:
            self.next()
            return True
        except StopIteration:
            return False

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _init_data(data, allow_empty, default_name):
    """Normalize input data to list of (name, numpy) (parity io.py:431)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {"_%d_%s" % (i, default_name): d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them or dict")
    for k, v in data.items():
        if isinstance(v, NDArray):
            data[k] = v.asnumpy()
    return list(data.items())


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (parity io.py:470)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data", label_name="softmax_label"):
        super().__init__()
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)
        self.idx = np.arange(self.data[0][1].shape[0])
        if shuffle:
            np.random.shuffle(self.idx)
            self.data = [(k, v[self.idx]) for k, v in self.data]
            self.label = [(k, v[self.idx]) for k, v in self.label]
        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]
        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        self.num_source = len(self.data_list)
        self.num_data = self.idx.shape[0]
        assert self.num_data >= batch_size, "batch_size needs to be smaller than data size."
        self.cursor = -batch_size
        self.batch_size = batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self):
        return [
            DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])))
            for k, v in self.data
        ]

    @property
    def provide_label(self):
        return [
            DataDesc(k, tuple([self.batch_size] + list(v.shape[1:])))
            for k, v in self.label
        ]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self.last_batch_handle == "roll_over" and self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) % self.batch_size
        else:
            self.cursor = -self.batch_size

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def skip(self, num_batches):
        # cursor math, no data touched: resume repositioning is O(1).
        # Clamped exactly where sequential next() calls stop (the
        # increment of the first failing iter_next still lands, then
        # the generic DataIter.skip breaks on StopIteration): an
        # unclamped overshoot inflates the cursor past that point, and
        # roll_over's reset() derives the next epoch's wrap offset from
        # the cursor — skip(k) must leave the same value k next()s would.
        target = self.cursor + int(num_batches) * self.batch_size
        if target >= self.num_data:
            to_end = -(-(self.num_data - self.cursor) // self.batch_size)
            target = min(target,
                         self.cursor + max(1, to_end) * self.batch_size)
        self.cursor = target

    def next(self):
        if self.iter_next():
            return DataBatch(
                data=self.getdata(), label=self.getlabel(),
                pad=self.getpad(), index=None
            )
        raise StopIteration

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        if self.cursor + self.batch_size <= self.num_data:
            return [
                nd.array(x[1][self.cursor : self.cursor + self.batch_size])
                for x in data_source
            ]
        pad = self.batch_size - self.num_data + self.cursor
        return [
            nd.array(np.concatenate((x[1][self.cursor :], x[1][:pad]), axis=0))
            for x in data_source
        ]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class MNISTIter(DataIter):
    """MNIST idx-format reader (parity src/io/iter_mnist.cc:241)."""

    def __init__(self, image, label, batch_size=128, shuffle=True, flat=False,
                 silent=False, seed=0, input_shape=None, **kwargs):
        super().__init__()
        with (gzip.open(image, "rb") if image.endswith(".gz") else open(image, "rb")) as f:
            magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
            imgs = np.frombuffer(f.read(), dtype=np.uint8).reshape(num, rows, cols)
        with (gzip.open(label, "rb") if label.endswith(".gz") else open(label, "rb")) as f:
            magic, num = struct.unpack(">II", f.read(8))
            lbls = np.frombuffer(f.read(), dtype=np.uint8)
        imgs = imgs.astype(np.float32) / 255.0
        if flat:
            imgs = imgs.reshape(imgs.shape[0], -1)
        else:
            imgs = imgs.reshape(imgs.shape[0], 1, rows, cols)
        if input_shape is not None:
            imgs = imgs.reshape((imgs.shape[0],) + tuple(input_shape))
        if shuffle:
            rng = np.random.RandomState(seed)
            order = rng.permutation(imgs.shape[0])
            imgs, lbls = imgs[order], lbls[order]
        self._inner = NDArrayIter(
            imgs, lbls.astype(np.float32), batch_size=batch_size,
            last_batch_handle="discard"
        )
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


class CSVIter(DataIter):
    """CSV reader (parity src/io/iter_csv.cc:132)."""

    def __init__(self, data_csv, data_shape, label_csv=None, label_shape=(1,),
                 batch_size=1, round_batch=True, **kwargs):
        super().__init__()
        data = np.loadtxt(data_csv, delimiter=",", dtype=np.float32, ndmin=2)
        data = data.reshape((-1,) + tuple(data_shape))
        if label_csv is not None:
            label = np.loadtxt(label_csv, delimiter=",", dtype=np.float32, ndmin=2)
            label = label.reshape((-1,) + tuple(label_shape))
            if label_shape == (1,):
                label = label.reshape(-1)
        else:
            label = np.zeros(data.shape[0], dtype=np.float32)
        self._inner = NDArrayIter(
            data, label, batch_size=batch_size,
            last_batch_handle="roll_over" if round_batch else "pad",
        )
        self.batch_size = batch_size

    @property
    def provide_data(self):
        return self._inner.provide_data

    @property
    def provide_label(self):
        return self._inner.provide_label

    def reset(self):
        self._inner.reset()

    def next(self):
        return self._inner.next()


def ImageRecordIter(**kwargs):
    """RecordIO image iterator (parity src/io/iter_image_recordio_2.cc:559).
    Implemented over mx.image.ImageIter + PrefetchingIter; accepts the
    reference's main params (path_imgrec, data_shape, batch_size,
    mean_r/g/b, scale, rand_crop, rand_mirror, shuffle,
    preprocess_threads). With ``input_workers`` > 0 (or
    ``MXTPU_INPUT_WORKERS``) the streaming pipeline takes over:
    chunk-sharded reads by (host_rank, num_hosts), a spawn-safe process
    decode pool, and the ``MXTPU_SHUFFLE_BUFFER`` cross-chunk shuffle —
    see ``io_pipeline.StreamingImageRecordIter``."""
    from .image import ImageIter

    return ImageIter.from_recordio_params(**kwargs)


def ImageDetRecordIter(**kwargs):
    """Detection RecordIO iterator (parity
    src/io/iter_image_det_recordio.cc:563): variable-width box labels,
    emitted with the C++ label contract [c, h, w, len, packed..., pad]."""
    from .image import ImageDetIter

    return ImageDetIter(**kwargs)


def DetRecordIter(**kwargs):
    """Module.fit-ready detection feed: ImageDetRecordIter + the SSD
    label reshape to (batch, max_objects, object_width) (reference
    example/ssd/dataset/iterator.py DetRecordIter)."""
    from .image import DetRecordIter as _Det

    return _Det(**kwargs)


MXDataIter = DataIter  # reference exposes C-iterator wrapper under this name
