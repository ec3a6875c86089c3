"""apex_tpu.data — the prefetching input pipeline.

TPU-native equivalent of the reference example's ``data_prefetcher``
(examples/imagenet/main_amp.py:264-300), which overlapped H2D copies and
normalization with compute on a side CUDA stream.  On TPU the device side
is XLA's job; the host side — batch assembly, uint8→fp32 NCHW normalize,
shuffling — is the bottleneck and runs in the C++ runtime
(apex_tpu/_native/apex_tpu_C.cpp, ``apex_loader_*``): worker threads fill
a ring of slots ahead of the training loop, delivery is in batch order,
and the Python step only wraps a ready buffer for ``device_put``.

Falls back to a pure-numpy implementation when the native library is
unavailable (the reference's Python-only build invariant).

    loader = DataLoader(images_u8_nhwc, labels, batch_size=128,
                        shuffle=True, prefetch=3, workers=4)
    for imgs, lbls in loader:           # imgs: (B, C, H, W) fp32
        ...                             # valid until the next iteration

Checkpointable, sharded iteration (PR 12).  The *portable* sample
stream — the python pipeline's per-epoch
``np.random.RandomState(seed + epoch).permutation(n)`` walk — carries
an exportable cursor: ``state_dict()`` / ``load_state_dict()`` round-
trip ``(seed, epoch, cursor, samples_consumed)`` so a preempted run
resumes with a bitwise-identical sample stream.  ``shard_id`` /
``num_shards`` shard every global batch deterministically: global step
``g`` consumes ``perm[cursor : cursor + batch_size * num_shards]`` and
shard ``s`` takes its contiguous ``batch_size`` slice, so the cursor is
WORLD-INDEPENDENT — re-deriving the shards at a different world (an
elastic 8→4 shrink) continues the same global stream and delivers every
sample exactly once.  Corrupt records are quarantined, never a crashed
step: a ``bad_record_fn`` hit is skipped (replaced in-batch by a good
sample), counted on ``data_samples_quarantined_total``, and logged to
the flight ring.  The state protocol is defined over the python
pipeline only — the native ring's shuffle order (splitmix64
Fisher–Yates) and normalize rounding are not bitwise-portable across
paths, so ``state_dict``/``load_state_dict`` raise on a native loader;
construct checkpointable loaders with ``native=False`` (``num_shards >
1`` and ``bad_record_fn`` force the python path automatically).
"""

from __future__ import annotations

import ctypes
import time
from typing import Iterator, Optional, Sequence, Tuple

import numpy as np

from . import _native

__all__ = ["DataLoader", "IMAGENET_MEAN", "IMAGENET_STD"]

IMAGENET_MEAN = (0.485 * 255, 0.456 * 255, 0.406 * 255)
IMAGENET_STD = (0.229 * 255, 0.224 * 255, 0.225 * 255)


class DataLoader:
    """Iterate normalized (images, labels) batches with native prefetch.

    ``images``: (N, H, W, C) uint8, ``labels``: (N,) int-like.  Epochs are
    endless via ``next_batch`` (``__iter__`` yields one epoch, drop-last).

    Delivered batches are owned copies by default.  ``zero_copy=True``
    returns views straight into the prefetch slot — fastest, but the view
    is only valid until the next ``next_batch`` call, and JAX's **CPU**
    backend may alias (not copy) aligned fp32 numpy arrays in
    ``device_put``, so an async in-flight step can read a recycled slot.
    Use zero_copy only when each batch is fully consumed (e.g.
    ``block_until_ready``) before requesting the next.
    """

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 batch_size: int, shuffle: bool = True,
                 mean: Sequence[float] = IMAGENET_MEAN,
                 std: Sequence[float] = IMAGENET_STD,
                 prefetch: int = 3, workers: int = 4, seed: int = 0,
                 native: Optional[bool] = None, zero_copy: bool = False,
                 data_format: str = "NCHW", metrics=None,
                 shard_id: int = 0, num_shards: int = 1,
                 bad_record_fn=None, ring=None):
        if data_format not in ("NCHW", "NHWC"):
            raise ValueError(f"data_format must be NCHW or NHWC, "
                             f"got {data_format!r}")
        # NHWC delivery skips the transpose entirely (a straight
        # sequential normalize walk) — pair with channels_last models so
        # the loader doesn't transpose to NCHW only for the model to
        # transpose back
        self.data_format = data_format
        self.zero_copy = zero_copy
        if np.asarray(images).dtype != np.uint8:
            raise TypeError(
                f"images must be uint8, got {np.asarray(images).dtype} — "
                "normalization happens inside the loader; pass the raw "
                "uint8 pixels")
        self.images = np.ascontiguousarray(images, np.uint8)
        self.labels = np.ascontiguousarray(labels, np.int32)
        if self.images.ndim != 4:
            raise ValueError("images must be (N, H, W, C) uint8")
        if len(self.labels) != len(self.images):
            raise ValueError("labels/images length mismatch")
        self.batch_size = int(batch_size)
        self.n, self.h, self.w, self.c = self.images.shape
        if self.n < self.batch_size:
            raise ValueError("dataset smaller than one batch")
        if num_shards < 1:
            raise ValueError(f"num_shards must be >= 1, got {num_shards}")
        if not 0 <= shard_id < num_shards:
            raise ValueError(f"shard_id must be in [0, {num_shards}), "
                             f"got {shard_id}")
        self.shard_id = int(shard_id)
        self.num_shards = int(num_shards)
        # one GLOBAL batch is what all shards consume together per step;
        # the permutation cursor advances by it, so the cursor (and the
        # samples_consumed census) is world-independent by construction
        self.global_batch = self.batch_size * self.num_shards
        if self.n < self.global_batch:
            raise ValueError(
                f"dataset ({self.n}) smaller than one global batch "
                f"({self.global_batch} = batch_size x num_shards)")
        self.batches_per_epoch = self.n // self.global_batch
        self.bad_record_fn = bad_record_fn
        self._ring = ring
        self.shuffle = shuffle
        self.mean = np.asarray(mean, np.float32)
        self.std = np.asarray(std, np.float32)
        if len(self.mean) != self.c or len(self.std) != self.c:
            raise ValueError("mean/std length must equal channel count")
        self.seed = seed
        self._handle = None
        self._held: Optional[ctypes.c_void_p] = None
        use_native = _native.available() if native is None else native
        if self.num_shards > 1 or bad_record_fn is not None:
            # sharded / quarantining delivery is defined over the
            # portable python permutation (the state-protocol stream);
            # the native ring knows neither shards nor record checks
            use_native = False
        if use_native:
            lib = _native._try_load()
            if lib is not None:
                self._lib = lib
                create_args = [
                    self.images.ctypes.data_as(ctypes.c_void_p),
                    self.labels.ctypes.data_as(ctypes.c_void_p),
                    self.n, self.h, self.w, self.c, self.batch_size,
                    int(prefetch), int(workers), seed,
                    self.mean.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_float)),
                    self.std.ctypes.data_as(
                        ctypes.POINTER(ctypes.c_float)),
                    1 if shuffle else 0,
                    1 if data_format == "NHWC" else 0]
                self._handle = lib.apex_loader_create(*create_args)
        # python fallback state: the checkpointable cursor walk.
        # (epoch, cursor) name a position in the epoch-concatenated
        # permutation stream; both are GLOBAL (shard-independent), so
        # a snapshot taken at world 8 resumes exactly at world 4.
        self._epoch = 0
        self._cursor = 0                 # samples into this epoch
        self._samples_consumed = 0       # global total across epochs
        self._batch_index = 0            # this loader's next_batch calls
        self._quarantined = 0
        self._perm = None
        self._perm_epoch = -1
        # host-side load/wait telemetry: how long the training loop
        # stalls in next_batch().  Near-zero waits mean the prefetch
        # ring is ahead of compute; sustained waits mean the loader is
        # the bottleneck (the thing this pipeline exists to prevent).
        # stats() reads LOADER-LOCAL metrics; the registry (global by
        # default) additionally gets process-wide totals, which
        # aggregate across loaders sharing it.
        from .observability import get_registry
        from .observability.metrics import Counter, Histogram
        self._metrics = metrics if metrics is not None else get_registry()
        self._m_wait = Histogram(
            "data_load_wait_seconds",
            help="training-loop stall per next_batch() call")
        self._m_batches = Counter("data_batches_total")
        self._g_wait = self._metrics.histogram(
            "data_load_wait_seconds",
            help="training-loop stall per next_batch() call (all "
                 "loaders on this registry)")
        self._g_batches = self._metrics.counter(
            "data_batches_total", help="batches delivered (all loaders)")
        self._g_quarantined = self._metrics.counter(
            "data_samples_quarantined_total",
            help="corrupt records skipped by the quarantine (never a "
                 "crashed step)")
        self._g_consumed = self._metrics.gauge(
            "data_samples_consumed",
            help="global samples consumed by the shard group this "
                 "loader belongs to (the exactly-once census)")

    @property
    def native(self) -> bool:
        return self._handle is not None

    @property
    def ring(self):
        from .observability import flightrec
        return flightrec.resolve(self._ring)

    # -- native path -------------------------------------------------------
    def _next_native(self) -> Tuple[np.ndarray, np.ndarray, int]:
        if self._held is not None:
            self._lib.apex_loader_release(self._handle, self._held)
            self._held = None
        img_p = ctypes.c_void_p()
        lbl_p = ctypes.c_void_p()
        b = self._lib.apex_loader_next(self._handle, ctypes.byref(img_p),
                                       ctypes.byref(lbl_p))
        if b < 0:
            # destroy() woke us mid-wait: the slot pointers were never
            # filled — stop cleanly instead of dereferencing NULL
            raise StopIteration("data loader shut down")
        self._held = img_p
        shape = ((self.batch_size, self.h, self.w, self.c)
                 if self.data_format == "NHWC"
                 else (self.batch_size, self.c, self.h, self.w))
        imgs = np.ctypeslib.as_array(
            ctypes.cast(img_p, ctypes.POINTER(ctypes.c_float)),
            shape=shape)
        lbls = np.ctypeslib.as_array(
            ctypes.cast(lbl_p, ctypes.POINTER(ctypes.c_int32)),
            shape=(self.batch_size,))
        if not self.zero_copy:
            imgs, lbls = imgs.copy(), lbls.copy()
            # data is owned now: release the slot immediately so workers
            # refill it during this step's compute (zero_copy defers the
            # release to the next call because the views still alias it)
            self._lib.apex_loader_release(self._handle, self._held)
            self._held = None
        return imgs, lbls, b

    # -- fallback path -----------------------------------------------------
    def _epoch_perm(self) -> np.ndarray:
        if self._perm_epoch != self._epoch:
            self._perm = (np.random.RandomState(
                self.seed + self._epoch).permutation(self.n)
                if self.shuffle else np.arange(self.n))
            self._perm_epoch = self._epoch
        return self._perm

    def _quarantine_sweep(self, idx: np.ndarray) -> np.ndarray:
        """Skip corrupt records without crashing the step: every index
        ``bad_record_fn`` flags is replaced in-batch by the first good
        sample of the same slice (batch shape must stay static for the
        jitted step), counted on ``data_samples_quarantined_total``,
        and logged to the flight ring.  The exactly-once census still
        holds for every GOOD sample; quarantined indices are accounted
        by the counter/ring, not silently re-fed to training."""
        fn = self.bad_record_fn
        if fn is None:
            return idx
        bad = [k for k in range(len(idx)) if fn(int(idx[k]))]
        if not bad:
            return idx
        idx = np.asarray(idx).copy()
        bad_set = set(bad)
        good = [k for k in range(len(idx)) if k not in bad_set]
        if good:
            sub = int(idx[good[0]])
        else:
            # a fully-poisoned batch still never crashes a STEP: fall
            # back to the first dataset record the check accepts.  A
            # fully-poisoned DATASET is the one thing that must be
            # loud — substituting a known-bad record would feed
            # training batch_size copies of exactly what the check
            # quarantined.
            sub = next((j for j in range(self.n) if not fn(j)), None)
            if sub is None:
                raise RuntimeError(
                    "every record in the dataset is flagged by "
                    "bad_record_fn — nothing left to train on")
        for k in bad:
            self._quarantined += 1
            self._g_quarantined.inc()
            self.ring.append("data_sample_quarantined",
                             index=int(idx[k]), replaced_with=sub,
                             shard=self.shard_id, epoch=self._epoch,
                             batch=self._batch_index)
            idx[k] = sub
        return idx

    def _next_python(self) -> Tuple[np.ndarray, np.ndarray, int]:
        if self._cursor + self.global_batch > self.n:
            # drop-last epoch roll (also how a cursor restored from a
            # LARGER old world lands near an epoch edge and moves on)
            self._epoch += 1
            self._cursor = 0
        perm = self._epoch_perm()
        base = self._cursor + self.shard_id * self.batch_size
        idx = perm[base:base + self.batch_size]
        self._cursor += self.global_batch
        self._samples_consumed += self.global_batch
        b = self._batch_index
        self._batch_index += 1
        idx = self._quarantine_sweep(idx)
        imgs = _native.preprocess_images(self.images[idx], self.mean,
                                         self.std, self.data_format)
        return imgs, self.labels[idx], b

    # -- iteration ---------------------------------------------------------
    def next_batch(self) -> Tuple[np.ndarray, np.ndarray, int]:
        """(images, labels, batch_index); endless, in batch order."""
        t0 = time.perf_counter()
        out = self._next_native() if self.native else self._next_python()
        dt = time.perf_counter() - t0
        self._m_wait.observe(dt)
        self._m_batches.inc()
        self._g_wait.observe(dt)
        self._g_batches.inc()
        self._g_consumed.set(float(self._census()["samples_consumed"]))
        return out

    def _census(self) -> dict:
        """The consumed-sample census (world-independent).  The python
        path reads its cursor state; the native path derives the same
        numbers from its delivered-batch counter (its stream is not
        checkpointable, but its census is still scrapeable)."""
        if self.native:
            b = int(self._m_batches.value)
            epoch, i = divmod(b, self.batches_per_epoch)
            return {"samples_consumed": b * self.global_batch,
                    "epoch": epoch, "cursor": i * self.global_batch}
        return {"samples_consumed": self._samples_consumed,
                "epoch": self._epoch, "cursor": self._cursor}

    def stats(self) -> dict:
        """Loader telemetry snapshot: batches delivered, the consumed-
        sample census (``samples_consumed``/``epoch``/``cursor``), the
        shard identity, quarantine count, and the load/wait latency
        summary — the ``/statusz`` source for the exactly-once
        accounting."""
        out = {"batches": int(self._m_batches.value),
               "native": self.native,
               "shard_id": self.shard_id,
               "num_shards": self.num_shards,
               "samples_quarantined": self._quarantined,
               "load_wait": self._m_wait.summary()}
        out.update(self._census())
        return out

    # -- checkpointable state (the preemption-safe resume protocol) --------
    def state_dict(self) -> dict:
        """Exportable cursor of the portable sample stream: everything
        a resumed loader needs to continue bitwise-identically.  All
        fields are JSON-serializable ints/bools — the checkpoint layer
        carries the blob under its content checksum
        (``utils.checkpoint.save_checkpoint(..., data_state=...)``).
        Raises on the native path: its shuffle order and normalize
        rounding are not portable; construct checkpointable loaders
        with ``native=False``."""
        if self.native:
            raise RuntimeError(
                "DataLoader.state_dict() needs the portable (python) "
                "pipeline — the native ring's shuffle order is not "
                "bitwise-portable; construct with native=False")
        return {"version": 1, "seed": int(self.seed),
                "shuffle": bool(self.shuffle), "n": int(self.n),
                "epoch": int(self._epoch), "cursor": int(self._cursor),
                "samples_consumed": int(self._samples_consumed),
                "batch_index": int(self._batch_index),
                "samples_quarantined": int(self._quarantined),
                "shard_id": int(self.shard_id),
                "num_shards": int(self.num_shards)}

    def load_state_dict(self, sd: dict) -> None:
        """Resume the portable stream at ``sd``'s cursor.  The stream
        identity (``seed``/``shuffle``/``n``) must match — resuming a
        different stream is an error, not a silent divergence.  The
        SHARDING may differ: the cursor is global, so an elastic world
        change re-derives the shards (``shard_id``/``num_shards`` of
        THIS loader win) and the global stream continues exactly
        once."""
        if self.native:
            raise RuntimeError(
                "DataLoader.load_state_dict() needs the portable "
                "(python) pipeline — construct with native=False")
        for key in ("seed", "shuffle", "n", "epoch", "cursor",
                    "samples_consumed"):
            if key not in sd:
                raise ValueError(f"data state missing {key!r}")
        if int(sd["seed"]) != self.seed:
            raise ValueError(
                f"data state was captured for seed {sd['seed']}, this "
                f"loader has seed {self.seed} — a different sample "
                f"stream cannot resume deterministically")
        if bool(sd["shuffle"]) != self.shuffle:
            raise ValueError("data state shuffle flag mismatch")
        if int(sd["n"]) != self.n:
            raise ValueError(
                f"data state names a {sd['n']}-sample dataset, this "
                f"loader holds {self.n}")
        cursor = int(sd["cursor"])
        if not 0 <= cursor <= self.n:
            raise ValueError(f"cursor {cursor} out of range [0, {self.n}]")
        self._epoch = int(sd["epoch"])
        self._cursor = cursor
        self._samples_consumed = int(sd["samples_consumed"])
        self._batch_index = int(sd.get("batch_index", 0))
        self._quarantined = int(sd.get("samples_quarantined", 0))
        self._perm_epoch = -1            # force permutation re-derive
        self._g_consumed.set(float(self._samples_consumed))

    def __iter__(self) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        for _ in range(self.batches_per_epoch):
            imgs, lbls, _ = self.next_batch()
            yield imgs, lbls

    def close(self) -> None:
        if self._handle is not None:
            if self._held is not None:
                self._lib.apex_loader_release(self._handle, self._held)
                self._held = None
            self._lib.apex_loader_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
