"""Tracing / profiling utilities (NVTX-range parity for TPU).

Two annotation layers, matching what the reference's nvtx ranges gave it:

- **Trace-time** (``jax.named_scope``): names the HLO emitted while the
  scope is active, so XLA profiles, HLO dumps, and xprof op breakdowns
  attribute time to framework phases ("syncbn_fwd", "allreduce", ...).
- **Host-time** (``jax.profiler.TraceAnnotation``): a real wall-clock range
  on the host timeline for eager sections (data loading, checkpointing).

``range_push/range_pop`` mirror torch.cuda.nvtx.range_push/pop
(reference sync_batchnorm.py:69,87); ``start_profile/stop_profile`` mirror
the cudaProfilerStart/Stop window of examples/imagenet/main_amp.py:325-352
on top of ``jax.profiler.start_trace/stop_trace``.
"""

from __future__ import annotations

import contextlib
import functools
import itertools
import os
import threading
from typing import Optional

import jax

__all__ = ["range_push", "range_pop", "nvtx_range", "annotate",
           "start_profile", "stop_profile", "profile", "profiling_active",
           "current_capture_dir", "last_capture_dir", "AverageMeter"]

_tls = threading.local()


def _stack():
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


def range_push(name: str) -> int:
    """Open a named range (torch.cuda.nvtx.range_push parity).  Returns the
    new nesting depth.  Opens both a named_scope (HLO attribution when
    tracing) and a host profiler annotation (timeline range)."""
    scope = jax.named_scope(name)
    ann = jax.profiler.TraceAnnotation(name)
    scope.__enter__()
    ann.__enter__()
    _stack().append((scope, ann))
    return len(_stack())


def range_pop() -> int:
    """Close the innermost range (torch.cuda.nvtx.range_pop parity)."""
    stack = _stack()
    if not stack:
        raise RuntimeError("range_pop() without matching range_push()")
    scope, ann = stack.pop()
    ann.__exit__(None, None, None)
    scope.__exit__(None, None, None)
    return len(stack)


@contextlib.contextmanager
def nvtx_range(name: str):
    """Context-manager form; exception-safe (prefer over push/pop)."""
    range_push(name)
    try:
        yield
    finally:
        range_pop()


def annotate(name: Optional[str] = None):
    """Decorator: run the function under a named range."""
    def deco(fn):
        label = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with nvtx_range(label):
                return fn(*args, **kwargs)
        return wrapped
    return deco


# Trace-window state: jax.profiler.start_trace is a process-wide
# singleton, so concurrent/nested windows must be refcounted under a
# lock — the bare `_trace_active` bool raced two threads into a double
# start_trace (RuntimeError) and a nested profile() used to stop the
# OUTER window on inner exit.
_trace_lock = threading.Lock()
_trace_depth = 0
# Every outermost window captures into a UNIQUE subdirectory of the
# requested logdir: start_trace names its session dir by wall-clock
# SECOND, so repeated captures into one shared logdir used to land in
# the same session dir and overwrite each other's trace files.  pid +
# a process-local counter keeps the names unique across
# forks and across captures.
_capture_dir: Optional[str] = None
_capture_seq = itertools.count()


def start_profile(logdir: str = "/tmp/apex_tpu_profile") -> str:
    """Begin an xprof trace window (cudaProfilerStart parity,
    main_amp.py:329).  Reentrant: only the outermost call starts the
    trace; nested calls increment the window refcount and no-op.
    Returns the window's unique capture directory (a fresh
    ``capture_<pid>_<n>`` subdirectory of ``logdir`` per outermost
    window); a nested call joins the outer window and returns ITS
    directory — the nested ``logdir`` argument is ignored, exactly as
    its start/stop always was."""
    global _trace_depth, _capture_dir
    with _trace_lock:
        if _trace_depth == 0:
            cap = os.path.join(
                logdir, f"capture_{os.getpid()}_{next(_capture_seq):04d}")
            os.makedirs(cap, exist_ok=True)
            # start first, increment after: a failed start_trace (e.g. a
            # foreign trace already active) must not leave a phantom
            # refcount that makes every later call a silent no-op —
            # nor an orphaned empty capture dir (a caller retrying
            # against a long-lived foreign trace would grow one per
            # attempt)
            try:
                jax.profiler.start_trace(cap)
            except BaseException:
                try:
                    os.rmdir(cap)       # still empty: nothing traced
                except OSError:
                    pass
                raise
            _capture_dir = cap
        _trace_depth += 1
        return _capture_dir


def stop_profile() -> Optional[str]:
    """End the trace window (cudaProfilerStop parity, main_amp.py:351).
    Only the outermost matching call stops the trace (and returns the
    finished window's capture directory); an inner or unmatched stop is
    a no-op returning None."""
    global _trace_depth
    with _trace_lock:
        if _trace_depth == 0:
            return None
        _trace_depth -= 1
        if _trace_depth == 0:
            jax.profiler.stop_trace()
            return _capture_dir
        return None


def profiling_active() -> bool:
    """True while a trace window is open (any nesting depth)."""
    with _trace_lock:
        return _trace_depth > 0


def current_capture_dir() -> Optional[str]:
    """The ACTIVE window's unique capture directory (None when no
    window is open)."""
    with _trace_lock:
        return _capture_dir if _trace_depth > 0 else None


def last_capture_dir() -> Optional[str]:
    """The most recent window's capture directory — still set after
    ``stop_profile``, which is when the trace file exists.  None
    before the first window."""
    with _trace_lock:
        return _capture_dir


@contextlib.contextmanager
def profile(logdir: str = "/tmp/apex_tpu_profile"):
    """Context-manager trace window; nesting-safe — an inner profile()
    joins the outer window instead of racing jax.profiler.start_trace
    or closing the outer window early.  Yields the window's unique
    capture directory (read it AFTER the block exits — the trace file
    is written at stop; ``benchmark/lib/trace.py`` is the reader)."""
    cap = start_profile(logdir)
    try:
        yield cap
    finally:
        stop_profile()


class AverageMeter:
    """Running average tracker (reference examples/imagenet/main_amp.py:
    415-430); used by the examples for loss/throughput reporting."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = 0.0
        self.avg = 0.0
        self.sum = 0.0
        self.count = 0

    def update(self, val: float, n: int = 1):
        self.val = val
        self.sum += val * n
        self.count += n
        self.avg = self.sum / max(self.count, 1)
