"""apex_tpu.utils — profiling/tracing shims and small training utilities.

The reference annotates hot boundaries with NVTX ranges
(apex/parallel/sync_batchnorm.py:69,87,132; examples/imagenet/main_amp.py:
325-352 gates cudaProfilerStart/Stop windows behind ``--prof``).  The TPU
equivalents are ``jax.named_scope`` (names HLO ops so XLA profiles/dumps
carry them) and ``jax.profiler`` trace annotations (host-side timeline
ranges); this module provides both behind the reference's push/pop shape.
"""

from .profiler import (range_push, range_pop, nvtx_range, annotate,
                       start_profile, stop_profile, profile,
                       profiling_active, current_capture_dir,
                       last_capture_dir, AverageMeter)
from .checkpoint import (save_checkpoint, restore_checkpoint, latest_step,
                         available_steps)
from .compile_cache import configure_compile_cache
from . import ema

__all__ = ["ema", "range_push", "range_pop", "nvtx_range", "annotate",
           "start_profile", "stop_profile", "profile", "profiling_active",
           "current_capture_dir", "last_capture_dir",
           "AverageMeter", "save_checkpoint", "restore_checkpoint",
           "latest_step", "available_steps", "configure_compile_cache"]
